"""Flash / ring / Ulysses attention correctness tests.

The Pallas kernel runs in interpret mode on the CPU mesh (same code path
as TPU); ring and Ulysses run under shard_map on the virtual 8-device
mesh — real SPMD partitioning, matching the reference's
multi-process-on-one-box test strategy (SURVEY §4).
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flexflow_tpu.ops.attention import reference_attention
from flexflow_tpu.ops.kernels.flash_attention import flash_attention, supports_shapes
from flexflow_tpu.ops.kernels.ring_attention import (
    ring_attention_sharded,
    ulysses_attention_sharded,
)
from flexflow_tpu.parallel.mesh import build_mesh


def _qkv(B=2, S=256, H=4, D=64, seed=0):
    rs = np.random.RandomState(seed)
    return tuple(jnp.asarray(rs.randn(B, S, H, D), jnp.float32) for _ in range(3))


@pytest.mark.parametrize("causal", [False, True])
def test_flash_attention_matches_reference(causal):
    q, k, v = _qkv()
    o1 = flash_attention(q, k, v, causal=causal, interpret=True)
    o2 = reference_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2), atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_attention_gradients_match(causal):
    q, k, v = _qkv(B=1, S=128, H=2)

    def loss(fn):
        return lambda q, k, v: jnp.sum(jnp.sin(fn(q, k, v, causal=causal)))

    flash = functools.partial(flash_attention, interpret=True)
    g1 = jax.grad(loss(flash), argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss(reference_attention), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-5, rtol=5e-5)


def test_supports_shapes():
    assert supports_shapes((2, 256, 4, 64), (2, 256, 4, 64))
    assert not supports_shapes((2, 100, 4, 64), (2, 100, 4, 64))  # ragged seq
    assert not supports_shapes((2, 256, 4, 80), (2, 256, 4, 80))  # odd head dim


@pytest.mark.parametrize("causal", [False, True])
def test_ring_attention_matches_reference(causal):
    q, k, v = _qkv(B=2, S=512, H=4, D=32)
    mesh = build_mesh({"data": 2, "seq": 4})
    o1 = ring_attention_sharded(q, k, v, mesh, causal=causal)
    o2 = reference_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2), atol=2e-5, rtol=2e-5)


def test_ring_attention_differentiable():
    q, k, v = _qkv(B=2, S=256, H=2, D=32)
    mesh = build_mesh({"seq": 8})

    def f(q, k, v):
        return jnp.sum(jnp.sin(ring_attention_sharded(q, k, v, mesh, causal=True)))

    def g(q, k, v):
        return jnp.sum(jnp.sin(reference_attention(q, k, v, causal=True)))

    ga = jax.grad(f, argnums=(0, 1, 2))(q, k, v)
    gb = jax.grad(g, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(ga, gb):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-5, rtol=5e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_ulysses_attention_matches_reference(causal):
    q, k, v = _qkv(B=2, S=256, H=8, D=32)
    mesh = build_mesh({"seq": 4})
    o1 = ulysses_attention_sharded(q, k, v, mesh, causal=causal)
    o2 = reference_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2), atol=2e-5, rtol=2e-5)


def test_context_parallel_training_e2e():
    """A transformer step with seq-sharded activations + ring attention."""
    from flexflow_tpu import FFConfig, LossType, SGDOptimizer
    from flexflow_tpu.models import TransformerConfig, build_transformer
    from flexflow_tpu.parallel.strategy import context_parallel_strategy

    cfg = TransformerConfig(num_layers=1, hidden_size=32, num_heads=2, ff_size=64, seq_length=64)
    config = FFConfig(batch_size=4)
    model = build_transformer(config, cfg)
    strategy = context_parallel_strategy(model.graph, dp=2, cp=4)
    model.compile(
        optimizer=SGDOptimizer(lr=0.01),
        loss_type=LossType.MEAN_SQUARED_ERROR,
        strategy=strategy,
    )
    assert model.mesh.shape.get("seq") == 4
    rs = np.random.RandomState(0)
    x = jnp.asarray(rs.randn(4, 64, 32), jnp.float32)
    y = jnp.asarray(rs.randn(4, 64, 32), jnp.float32)
    m1 = model.executor.train_batch([x], y, jax.random.key(0))
    m2 = model.executor.train_batch([x], y, jax.random.key(1))
    assert np.isfinite(float(m1["loss"]))
    assert float(m2["loss"]) < float(m1["loss"])


def test_search_proposes_context_parallelism_for_long_sequences():
    """Round-3: the search proposes sequence/context parallelism (NEW
    capability — the reference has none, SURVEY §5). Long sequences with
    a batch too small to fill the machine pick dp x cp; the compiled
    model trains with ring attention over the "seq" axis. Short
    sequences stay non-CP."""
    from flexflow_tpu import FFConfig, LossType, SGDOptimizer
    from flexflow_tpu.models import TransformerConfig, build_transformer
    from flexflow_tpu.search.unity import unity_optimize

    cfg = TransformerConfig(
        num_layers=2, hidden_size=128, num_heads=4, ff_size=256, seq_length=512
    )
    config = FFConfig(batch_size=4, workers_per_node=8, search_budget=3)
    m = build_transformer(config, cfg)
    strategy, sr = unity_optimize(m.graph, config)
    assert sr.context_parallel is not None, "long-context should pick dp x cp"
    dp, cp = sr.context_parallel
    assert cp >= 2 and strategy.axis_sizes.get("seq", 1) == cp

    m.compile(
        optimizer=SGDOptimizer(lr=0.01),
        loss_type=LossType.MEAN_SQUARED_ERROR,
        strategy=strategy,
    )
    rs = np.random.RandomState(0)
    x = jnp.asarray(rs.randn(4, 512, 128), jnp.float32)
    y = x * 0.5
    losses = [
        float(m.executor.train_batch([x], y, jax.random.key(0))["loss"])
        for _ in range(3)
    ]
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0], losses

    # short sequences: no CP proposed
    cfg2 = TransformerConfig(
        num_layers=2, hidden_size=128, num_heads=4, ff_size=256, seq_length=128
    )
    m2 = build_transformer(config, cfg2)
    _, sr2 = unity_optimize(m2.graph, config)
    assert sr2.context_parallel is None


def test_flash_env_block_rejects_nonpositive(monkeypatch):
    """ADVICE r4: FF_FLASH_BLOCK_Q=0 (or negative) must fall back to the
    adaptive policy rather than arming a ZeroDivisionError in
    supports_shapes."""
    from flexflow_tpu.ops.kernels.flash_attention import _env_block

    for bad in ("0", "-64", "nonsense", ""):
        monkeypatch.setenv("FF_TEST_BLOCK", bad)
        assert _env_block("FF_TEST_BLOCK") is None, bad
    monkeypatch.setenv("FF_TEST_BLOCK", "256")
    assert _env_block("FF_TEST_BLOCK") == 256
    monkeypatch.delenv("FF_TEST_BLOCK")
    assert _env_block("FF_TEST_BLOCK") is None


def test_flash_adaptive_block_policy(monkeypatch):
    """Round-5 on-chip sweep: 256 blocks beat 128 by 1.49x at seq 512,
    so the default picks the largest candidate dividing the sequence —
    while seq not divisible by 256 (e.g. 384) must keep flash via 128
    instead of silently falling back to dense."""
    from flexflow_tpu.ops.kernels import flash_attention as fa
    from flexflow_tpu.ops.kernels.flash_attention import (
        effective_blocks,
        pick_block,
        supports_shapes,
    )

    # isolate from a leaked FF_FLASH_BLOCK_Q/K (captured at import)
    monkeypatch.setattr(fa, "ENV_BLOCK_Q", None)
    monkeypatch.setattr(fa, "ENV_BLOCK_K", None)

    assert pick_block(512, None) == 256
    assert pick_block(128, None) == 128
    assert pick_block(384, None) == 128  # 384 % 256 != 0
    assert pick_block(64, None) == 64  # clamp below smallest candidate
    assert pick_block(512, 128) == 128  # env override wins
    assert pick_block(64, 512) == 64  # override still clamped to seq
    assert effective_blocks(512, 512) == (256, 256)
    for seq in (128, 256, 384, 512, 1024):
        assert supports_shapes((2, seq, 4, 64), (2, seq, 4, 64)), seq


# ---------------------------------------------------------------------------
# split-KV (flash-decoding) paged kernel parity — ISSUE 13
# ---------------------------------------------------------------------------


def _paged_fixtures(seed, b, w, max_blocks, nb=33, bs=8, h=4, d=64, layers=2, rows=None):
    """A whole cache ([L, nb, bs, R, LW], as the kernels take it since
    ISSUE 24: ``rows`` = (R, LW), by default one head a row), queries,
    tables and per-query positions."""
    rs = np.random.RandomState(seed)
    rows = rows or (h, d)
    k_cache = jnp.asarray(rs.randn(layers, nb, bs, *rows).astype(np.float32))
    v_cache = jnp.asarray(rs.randn(layers, nb, bs, *rows).astype(np.float32))
    q = jnp.asarray(rs.randn(b, w, h, d).astype(np.float32))
    tables = jnp.asarray(rs.randint(1, nb, (b, max_blocks)).astype(np.int32))
    qpos = []
    for _ in range(b):
        base = int(rs.randint(0, max_blocks * bs - w))
        qpos.append([base + j if rs.rand() > 0.2 else -1 for j in range(w)])
    qpos = jnp.asarray(np.asarray(qpos, np.int32))
    return q, k_cache, v_cache, tables, qpos


@pytest.mark.parametrize(
    "b,w,max_blocks,splits",
    [
        (1, 1, 32, 8),  # decode shape, even split
        (1, 1, 32, 2),
        (2, 4, 16, 3),  # append window, non-dividing split (padding steps)
        (3, 5, 7, 4),   # odd table, split > blocks-per-split coverage
        (1, 3, 9, 2),
    ],
)
def test_split_kv_append_matches_reference(b, w, max_blocks, splits):
    """Flash-decoding split-KV kernel (interpret mode): every split
    count — including ones that do not divide the table, exercising the
    clamped-index padding grid steps — recombines partial softmaxes to
    the reference result, padding queries emit zeros."""
    from flexflow_tpu.ops.kernels.decode_attention import (
        paged_append_attention,
        reference_paged_append_attention,
    )

    q, k_cache, v_cache, tables, qpos = _paged_fixtures(
        100 + b + w + splits, b, w, max_blocks
    )
    ref = reference_paged_append_attention(q, k_cache, v_cache, 1, tables, qpos)
    out = paged_append_attention(
        q, k_cache, v_cache, 1, tables, qpos, interpret=True, kv_splits=splits
    )
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)
    # padding queries emit exact zeros, like the single-pass kernel
    pad = np.asarray(qpos) < 0
    if pad.any():
        assert np.all(np.asarray(out)[pad] == 0.0)


def test_split_kv_decode_wrapper_and_heuristic():
    """The decode (W=1) wrapper auto-splits only where flash-decoding
    pays: small batch over a long table; parity holds either way."""
    from flexflow_tpu.ops.kernels.decode_attention import (
        default_kv_splits,
        paged_decode_attention,
        reference_paged_attention,
    )

    assert default_kv_splits(1, 32) > 1        # long context, single stream
    assert default_kv_splits(8, 32) == 1       # batch already fills the chip
    assert default_kv_splits(1, 8) == 1        # short table: not worth it
    q, k_cache, v_cache, tables, _ = _paged_fixtures(7, 2, 1, 24)
    ctx = jnp.asarray(np.asarray([150, 40], np.int32))
    ref = reference_paged_attention(q[:, 0], k_cache, v_cache, 0, tables, ctx)
    out = paged_decode_attention(
        q[:, 0], k_cache, v_cache, 0, tables, ctx, interpret=True, kv_splits=4
    )
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)


def test_split_kv_single_split_is_the_sequential_kernel():
    """kv_splits=1 (and out-of-range values clamp there) takes the
    original sequential-grid path bit-for-bit."""
    from flexflow_tpu.ops.kernels.decode_attention import paged_append_attention

    q, k_cache, v_cache, tables, qpos = _paged_fixtures(3, 2, 3, 9)
    base = paged_append_attention(
        q, k_cache, v_cache, 1, tables, qpos, interpret=True, kv_splits=1
    )
    clamped = paged_append_attention(
        q, k_cache, v_cache, 1, tables, qpos, interpret=True, kv_splits=0
    )
    assert np.array_equal(np.asarray(base), np.asarray(clamped))


@pytest.mark.parametrize("rows", [(4, 64), (2, 128)], ids=["head_per_row", "two_heads_per_row"])
@pytest.mark.parametrize("splits", [1, 4])
@pytest.mark.parametrize("layer", [0, 1, 4])
def test_paged_kernel_reads_its_layer_of_the_whole_cache(layer, splits, rows):
    """The kernel takes the 5-D cache and a static layer index (its
    index map is ``(layer, table[b, j], 0, 0, 0)``): for the first, a
    middle and the last layer it equals the XLA composition on that
    layer, whether a cache row holds one head or two side by side, and
    the composition equals itself on the layer sliced out as a one-layer
    cache in the plain [.., H, D] view — so neither reads a neighbouring
    layer, and the packed rows are a row-major reshape and nothing more."""
    from flexflow_tpu.ops.kernels.decode_attention import (
        cache_row_shape,
        paged_append_attention,
        reference_paged_append_attention,
    )

    assert cache_row_shape(4, 64) == (2, 128)
    q, k_cache, v_cache, tables, qpos = _paged_fixtures(
        11, 2, 3, 8, nb=12, layers=5, rows=rows
    )
    ref = reference_paged_append_attention(q, k_cache, v_cache, layer, tables, qpos)
    plain = lambda c: c[layer].reshape(1, 12, 8, 4, 64)
    alone = reference_paged_append_attention(q, plain(k_cache), plain(v_cache), 0, tables, qpos)
    assert np.array_equal(np.asarray(ref), np.asarray(alone))
    out = paged_append_attention(
        q, k_cache, v_cache, layer, tables, qpos, interpret=True, kv_splits=splits
    )
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)
    assert np.all(np.asarray(out)[np.asarray(qpos) < 0] == 0.0)  # padding queries: zeros
    others = [l for l in range(5) if l != layer]
    far = reference_paged_append_attention(q, k_cache, v_cache, others[0], tables, qpos)
    assert not np.allclose(np.asarray(out), np.asarray(far), atol=1e-3)


def test_paged_kernel_refuses_rows_that_do_not_hold_the_heads():
    from flexflow_tpu.ops.kernels.decode_attention import paged_append_attention

    q, k_cache, v_cache, tables, qpos = _paged_fixtures(2, 2, 1, 4, rows=(3, 128))
    with pytest.raises(ValueError, match="do not hold 4 heads of 64"):
        paged_append_attention(q, k_cache, v_cache, 0, tables, qpos, interpret=True)
