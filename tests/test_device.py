"""Device selection that does not hide the device (ISSUE 21): the
compile-cache placement rule, unknown chips as errors, kernels that are
never interpreted unless a test asks, the dispatch gate's stated
refusals, chip_smoke.py failing without a TPU, and records that name
only files the checkout holds."""
import os
import pathlib
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flexflow_tpu import device

REPO = pathlib.Path(__file__).resolve().parent.parent


@pytest.fixture
def config_updates(monkeypatch):
    """Record jax.config.update calls instead of applying them: the test
    process must not start caching compiles into the checkout."""
    calls = []
    monkeypatch.setattr(jax.config, "update", lambda *a: calls.append(a))
    return calls


def test_compile_cache_env_set_code_sets_nothing(monkeypatch, tmp_path, config_updates):
    monkeypatch.setenv(device.COMPILE_CACHE_ENV, str(tmp_path))
    assert device.enable_compile_cache() == str(tmp_path)
    assert config_updates == []  # JAX reads the variable itself


def test_compile_cache_unset_is_one_fixed_path_in_the_checkout(monkeypatch, config_updates):
    monkeypatch.delenv(device.COMPILE_CACHE_ENV, raising=False)
    expected = str(REPO / ".jax_cache")
    assert device.enable_compile_cache() == expected
    assert config_updates == [("jax_compilation_cache_dir", expected)]
    # a second process derives the identical path (no pid, no time, no tempfile)
    env = {k: v for k, v in os.environ.items() if k != device.COMPILE_CACHE_ENV}
    env["JAX_PLATFORMS"] = "cpu"
    child = subprocess.run(
        [sys.executable, "-c",
         "import jax\n"
         "from flexflow_tpu.device import enable_compile_cache\n"
         "print(enable_compile_cache()); print(jax.config.jax_compilation_cache_dir)"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=120,
    )
    assert child.returncode == 0, child.stderr
    assert child.stdout.split() == [expected, expected]


def test_require_tpu_names_the_missing_device():
    with pytest.raises(RuntimeError, match="no TPU.*platform='cpu'"):
        device.require_tpu()
    assert not device.on_tpu()


def test_unknown_device_kind_has_no_assumed_peak():
    from flexflow_tpu.search.calibration import chip_spec_for

    assert chip_spec_for("TPU v5 lite").bf16_flops == 197e12
    with pytest.raises(ValueError, match="weird future chip"):
        chip_spec_for("weird future chip")


def test_calibration_tables_come_from_the_checkout_only(monkeypatch, tmp_path):
    """Search decisions must not depend on a file outside the checkout:
    the committed table is read (and nothing under $HOME) unless
    FLEXFLOW_TPU_CACHE names a directory."""
    from flexflow_tpu.search.calibration import Calibration, cache_dir, load_calibration

    monkeypatch.delenv("FLEXFLOW_TPU_CACHE", raising=False)
    monkeypatch.setenv("HOME", str(tmp_path))
    monkeypatch.setenv("XDG_CACHE_HOME", str(tmp_path / ".cache"))
    shadow = tmp_path / ".cache" / "flexflow_tpu" / "opcosts_tpu_v5_lite.json"
    shadow.parent.mkdir(parents=True)
    shadow.write_text(Calibration(device_kind="TPU v5 lite", derates={"matmul": 99.0}).to_json())
    assert cache_dir() is None
    cal = load_calibration("TPU v5 lite")
    assert pathlib.Path(cal.source).is_relative_to(REPO) and cal.derates["matmul"] < 2.0
    assert Calibration(device_kind="nowhere-chip").save() is None  # nowhere to keep it
    assert not list(tmp_path.rglob("opcosts_nowhere*"))
    monkeypatch.setenv("FLEXFLOW_TPU_CACHE", str(shadow.parent))
    assert load_calibration("TPU v5 lite").derates["matmul"] == 99.0


def _paged_args(w=1):
    rs = np.random.RandomState(0)
    q = jnp.asarray(rs.randn(2, w, 4, 64), jnp.float32)
    kc = jnp.asarray(rs.randn(2, 5, 8, 4, 64), jnp.float32)  # [L, nb, bs, H, D]
    bt = jnp.asarray(rs.randint(1, 5, (2, 3)), jnp.int32)
    qp = jnp.asarray(np.tile(np.arange(w), (2, 1)) + 3, jnp.int32)
    return q, kc, kc, 1, bt, qp


def test_kernels_are_not_interpreted_unless_a_test_asks():
    """Without interpret=True the kernels lower for Mosaic, which the
    CPU backend refuses — they never fall back to interpret mode."""
    from flexflow_tpu.ops.kernels.decode_attention import paged_append_attention
    from flexflow_tpu.ops.kernels.flash_attention import flash_attention

    x = jnp.ones((1, 128, 2, 64), jnp.float32)
    with pytest.raises(ValueError, match="[Ii]nterpret mode"):
        flash_attention(x, x, x)
    with pytest.raises(ValueError, match="[Ii]nterpret mode"):
        paged_append_attention(*_paged_args())


def test_paged_kernel_gate_states_its_refusals():
    from flexflow_tpu.ops.kernels.decode_attention import (
        MAX_KERNEL_WINDOW,
        paged_kernel_refusal,
    )

    # what the server runs: 16 heads of 64 (4 per shard at tp=4), block 16
    for heads in (16, 4):
        for window in (1, 5, MAX_KERNEL_WINDOW):
            assert paged_kernel_refusal(heads, 64, 16, window) is None
    assert "window 64" in paged_kernel_refusal(16, 64, 16, 64)  # a suffix-prefill bucket
    assert "VMEM" in paged_kernel_refusal(64, 128, 128, 1)
    assert paged_kernel_refusal(16, 64, 16, 1, itemsize=2) is None  # bf16 cache


def test_flash_attention_sharded_matches_reference():
    """The multi-device wrapper (Mosaic kernels cannot be partitioned by
    GSPMD): per-shard kernels over batch and heads, interpret mode on
    the CPU mesh."""
    from flexflow_tpu.ops.attention import reference_attention
    from flexflow_tpu.ops.kernels.flash_attention import flash_attention_sharded
    from flexflow_tpu.parallel.mesh import build_mesh

    rs = np.random.RandomState(1)
    q, k, v = (jnp.asarray(rs.randn(4, 128, 4, 64), jnp.float32) for _ in range(3))
    mesh = build_mesh({"data": 2, "model": 2}, jax.devices()[:4])
    out = jax.jit(
        lambda q, k, v: flash_attention_sharded(q, k, v, mesh, causal=True, interpret=True)
    )(q, k, v)
    ref = reference_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)
    # a batch the data axis does not divide stays whole on every device
    out3 = flash_attention_sharded(q[:3], k[:3], v[:3], mesh, interpret=True)
    np.testing.assert_allclose(
        np.asarray(out3), np.asarray(reference_attention(q[:3], k[:3], v[:3])),
        atol=2e-5, rtol=2e-5,
    )


def test_kv_cache_holds_two_buffers_created_where_they_live():
    """The decode/verify jits donate K and V: one shared buffer would be
    donated twice. With a sharding each device materializes its shard."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from flexflow_tpu.generation.cache import CacheConfig, KVCache
    from flexflow_tpu.parallel.mesh import serving_mesh

    cfg = CacheConfig(num_layers=2, num_heads=4, head_dim=8, num_blocks=5, block_size=4)
    cache = KVCache.create(cfg)
    assert cache.k is not cache.v
    assert cache.k.unsafe_buffer_pointer() != cache.v.unsafe_buffer_pointer()
    sh = NamedSharding(serving_mesh(4), P(None, None, None, "model", None))
    sharded = KVCache.create(cfg, sharding=sh)
    assert sharded.k.sharding == sh and len(sharded.k.addressable_shards) == 4
    assert sharded.k.addressable_shards[0].data.shape == (2, 5, 4, 1, 8)
    sharded.reset()
    assert sharded.k.sharding == sh and sharded.k is not sharded.v
    # stored rows: heads that divide the 128 lanes share a row, packed
    # shard by shard, so sharding the row axis is sharding the heads
    wide = dict(num_layers=2, num_heads=16, head_dim=64, num_blocks=5, block_size=4)
    assert CacheConfig(**wide).row_shape == (8, 128)
    assert CacheConfig(**wide, kv_shards=4).row_shape == (8, 128)  # 4 heads = 2 rows a shard
    assert CacheConfig(**wide, kv_shards=16).row_shape == (16, 64)  # one head a shard: no pair
    assert cfg.row_shape == (4, 8)  # 4 heads of 8 do not fill a row: stored as they are
    packed = KVCache.create(CacheConfig(**wide, kv_shards=4), sharding=sh)
    assert packed.k.shape == (2, 5, 4, 8, 128)
    assert packed.k.addressable_shards[0].data.shape == (2, 5, 4, 2, 128)


def test_chip_smoke_fails_without_a_tpu():
    """Under JAX_PLATFORMS=cpu the smoke exits non-zero, names the
    missing device, and prints no result line."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, str(REPO / "chip_smoke.py")],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode != 0
    assert "no TPU" in proc.stderr and "platform='cpu'" in proc.stderr
    assert '"ok"' not in proc.stdout


# a script a record tells its reader to run or read: `python <path>.py`, or
# a path under a directory of scripts
_SCRIPT = re.compile(
    r"python3?\s+(?:-u\s+)?([\w./-]+\.py)"
    r"|(?<![\w./-])((?:tools|benchmark|examples)/[\w./-]+\.py)"
)


def _tier1_line():
    return next(
        l for l in (REPO / "ROADMAP.md").read_text().splitlines() if "Tier-1 verify" in l
    )


@pytest.mark.parametrize(
    "record",
    [
        lambda: (REPO / "README.md").read_text(),
        lambda: (REPO / ".github" / "workflows" / "tpu-ci.yml").read_text(),
        lambda: _tier1_line() + (REPO / "PERF.md").read_text(),
    ],
    ids=["README.md", "tpu-ci.yml", "ROADMAP-tier1+PERF.md"],
)
def test_records_name_scripts_that_exist(record):
    """What a record says to run resolves in the checkout (PR 29: the
    README's "Running" was built on three scripts the ledger had replaced)."""
    text = record()
    named = {m.group(1) or m.group(2) for m in _SCRIPT.finditer(text)}
    assert named, "the record names no script: the pattern has rotted"
    assert sorted(n for n in named if not (REPO / n).exists()) == []
