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


def _rooms(q, k, v, w, causal, unit):
    """What the kernels' roundings leave around float32 attention and its
    gradients on the same values, element by element (first order in
    ``unit``, one operand rounding; sums of magnitudes, so no cancellation
    is counted on). The kernels round: the probabilities as the operand
    of ``P V`` and ``P^T dO``, ``dS`` as the operand of ``dS K`` and
    ``dS^T Q``, and each result once; ``delta = sum(dO * O)`` is taken from
    the ROUNDED result. Everything else (scores, softmax state, ``dP``,
    accumulation) is float32."""
    q, k, v, w = (np.asarray(x, np.float64).transpose(0, 2, 1, 3) for x in (q, k, v, w))  # [B, H, S, D]
    scale = q.shape[-1] ** -0.5
    s = np.einsum("bhqd,bhkd->bhqk", q, k) * scale
    if causal:
        s = np.where(np.tril(np.ones(s.shape[-2:], bool)), s, -np.inf)
    p = np.exp(s - s.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    o = p @ v
    ds = p * (w @ v.transpose(0, 1, 3, 2) - (w * o).sum(-1, keepdims=True))
    delta_room = unit * (np.abs(w) * np.abs(o)).sum(-1, keepdims=True)
    ds_room = unit * np.abs(ds) + p * delta_room
    pt, ds_t = p.transpose(0, 1, 3, 2), ds.transpose(0, 1, 3, 2)
    rooms = {
        "fwd": unit * (p @ np.abs(v) + np.abs(o)),
        "dq": scale * (ds_room @ np.abs(k)) + unit * np.abs(scale * ds @ k),
        "dk": scale * (ds_room.transpose(0, 1, 3, 2) @ np.abs(q)) + unit * np.abs(scale * ds_t @ q),
        "dv": unit * (pt @ np.abs(w) + np.abs(pt @ w)),
    }
    return {name: room.transpose(0, 2, 1, 3) for name, room in rooms.items()}


@functools.lru_cache(maxsize=None)
def _flash_against_float32(dtype, causal, blocks):
    """(kernel's result and gradients, float32 reference's, rooms) at
    operands of ``dtype`` holding values that bfloat16 holds too."""
    q, k, v = (x.astype(jnp.bfloat16) for x in _qkv(B=1, S=256, H=2, seed=3))
    w = jnp.asarray(np.random.RandomState(4).randn(*q.shape), jnp.bfloat16)  # dO: the same in every type

    def loss(fn, *operands):
        return jnp.sum(fn(*operands, causal=causal).astype(jnp.float32) * w.astype(jnp.float32))

    flash = functools.partial(flash_attention, block_q=blocks[0], block_k=blocks[1], interpret=True)
    typed = tuple(x.astype(dtype) for x in (q, k, v))
    exact = tuple(x.astype(jnp.float32) for x in (q, k, v))
    got = (flash(*typed, causal=causal),) + jax.grad(functools.partial(loss, flash), (0, 1, 2))(*typed)
    want = (reference_attention(*exact, causal=causal),) + jax.grad(functools.partial(loss, reference_attention), (0, 1, 2))(*exact)
    # a rounding of the operands' type (bfloat16: 2^-8 of the value), and float32's own over a sum of S terms
    unit = float(jnp.finfo(dtype).eps) / 2 + q.shape[1] * 2.0 ** -24
    names = ("fwd", "dq", "dk", "dv")
    return dict(zip(names, got)), dict(zip(names, want)), _rooms(q, k, v, w, causal, unit)


@pytest.mark.parametrize("what", ["fwd", "dq", "dk", "dv"])
@pytest.mark.parametrize("blocks", [(128, 128), (64, 128), (256, 256)], ids=lambda b: f"{b[0]}x{b[1]}")
@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
def test_flash_kernels_lie_within_their_operands_roundings(dtype, causal, blocks, what):
    """The three kernels multiply in the operands' type and accumulate in
    float32: bfloat16 operands put the result and each gradient within
    :func:`_rooms` of float32 attention on the same values (a rounding
    is 2^-8 of the value), float32 operands within float32's own. Several
    loop trips with the running softmax's rescale, a block pair that is
    not square, and the whole sequence in one trip."""
    got, want, rooms = _flash_against_float32(dtype, causal, blocks)
    assert got[what].dtype == jnp.dtype(dtype)
    excess = np.abs(np.asarray(got[what], np.float64) - np.asarray(want[what], np.float64)) - rooms[what]
    assert excess.max() <= 0.0, f"{what}: {excess.max()} past its room of {rooms[what].flat[excess.argmax()]}"


@pytest.mark.parametrize("dtype,element", [("bfloat16", "bf16"), ("float32", "f32")])
def test_flash_kernels_hand_the_mxu_the_operands_type(dtype, element):
    """What reaches the MXU at the training cells' shape ([16, 512, 16,
    64]), lowered for a TPU: three Mosaic modules under the names the
    trace readers look for (``benchmark/kernel_model.py::FLASH_KERNELS``),
    2 / 3 / 4 ``tpu.matmul`` (all of them plain: no operand is
    transposed on the way), every operand vector of the operands' own
    type and every accumulator float32. An ``.astype(jnp.float32)`` on
    an operand of a product in a body turns a ``bf16`` here into ``f32``."""
    import re

    from benchmark import kernel_model

    products = {name: matmuls for name, (matmuls, _) in kernel_model._FLASH.items()}  # what the roofline's reader counts
    assert products == {"flash_attention_fwd": 2, "flash_attention_bwd_dq": 3, "flash_attention_bwd_dkv": 4}
    t = jax.ShapeDtypeStruct((16, 512, 16, 64), jnp.dtype(dtype))
    grads = jax.grad(lambda q, k, v: jnp.sum(flash_attention(q, k, v).astype(jnp.float32)), (0, 1, 2))
    modules = _mosaic_modules(grads, t, t, t)
    assert [re.match(r"module @(\w+)", m).group(1) for m in modules] == list(kernel_model.FLASH_KERNELS)
    for (name, count), module in zip(products.items(), modules):
        matmuls = re.findall(r"tpu\.matmul.*?: \(vector<\w+x(\w+)>, vector<\w+x(\w+)>, vector<\w+x(\w+)>\) -> vector<\w+x(\w+)>", module)
        assert len(matmuls) == module.count("tpu.matmul") == count, name
        assert set(matmuls) == {(element, element, "f32", "f32")}, (name, matmuls)
        assert "transpose_lhs = true" not in module and "tpu.transpose" not in module and "vector.transpose" not in module, name


STREAMED_PREFILL_MODULES = {
    # cell: (q, k, v shapes of its longest prefill's call, window, the module's hash at 7a94bda, PR 46)
    "command-a-plus.long-doc": ((1, 6144, 128, 128), (1, 6144, 8, 128), (1, 6144, 8, 128), 4096, "3edc5ecbd9f168ce"),
    "longcat-flash-chat.agent-turns": ((1, 4096, 64, 192), (1, 4096, 64, 192), (1, 4096, 64, 128), 0, "ab1d8c446c22c485"),
}


@pytest.mark.parametrize("cell", sorted(STREAMED_PREFILL_MODULES))
def test_streamed_prefill_call_lowers_to_the_parents_mosaic_module(cell):
    """The serving prefill's kernel shares the training kernels' file
    and none of their code: its Mosaic module at the two cells' calls,
    printed without source locations, hashes to what it hashed to before
    PR 47 rewrote the training bodies above it."""
    import hashlib

    from flexflow_tpu.ops.kernels.flash_attention import prefill_stream_attention

    q, k, v, window, digest = STREAMED_PREFILL_MODULES[cell]
    shape = lambda s: jax.ShapeDtypeStruct(s, jnp.bfloat16)
    (module,) = _mosaic_modules(
        lambda q, k, v, lens: prefill_stream_attention(q, k, v, lens, window=window),
        shape(q), shape(k), shape(v), jax.ShapeDtypeStruct((1,), jnp.int32),
    )
    assert hashlib.sha256(module.encode()).hexdigest()[:16] == digest


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
    """The chip's sweep of PR 47 (the table in ops/kernels/flash_attention.py):
    the largest block wins in all three kernels, 512 (at seq 512 the whole
    sequence in one loop trip) over 256 over 128, so the default picks the
    largest candidate dividing the sequence — while seq not divisible by
    256 (e.g. 384) must keep flash via 128 instead of silently falling
    back to dense."""
    from flexflow_tpu.ops.kernels import flash_attention as fa
    from flexflow_tpu.ops.kernels.flash_attention import (
        effective_blocks,
        pick_block,
        supports_shapes,
    )

    # isolate from a leaked FF_FLASH_BLOCK_Q/K (captured at import)
    monkeypatch.setattr(fa, "ENV_BLOCK_Q", None)
    monkeypatch.setattr(fa, "ENV_BLOCK_K", None)

    assert pick_block(512, None) == 512 and pick_block(1024, None) == 512
    assert pick_block(256, None) == 256 and pick_block(768, None) == 256
    assert pick_block(128, None) == 128
    assert pick_block(384, None) == 128  # 384 % 256 != 0
    assert pick_block(64, None) == 64  # clamp below smallest candidate
    assert pick_block(512, 128) == 128  # env override wins
    assert pick_block(64, 512) == 64  # override still clamped to seq
    assert effective_blocks(512, 512) == (512, 512) and effective_blocks(256, 1024) == (256, 512)
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


# ---------------------------------------------------------------------------
# grouped queries: a K/V block scored for all of a group's heads on the MXU
# (PR 33); plain multi-head calls keep the parent's lowering
# ---------------------------------------------------------------------------

# the two layouts the benchmark's grouped cells store: LFM2's (8 K/V heads
# of 64, two to a 128-lane row, group 4) and Mellum2's (4 K/V heads of 128,
# one a row, group 8); both rows of [4, 128]
GROUPED_LAYOUTS = {
    "packed_rows_group4": dict(heads=32, kv_heads=8, head_dim=64, rows=(4, 128)),
    "head_rows_group8": dict(heads=32, kv_heads=4, head_dim=128, rows=(4, 128)),
}


def _grouped_fixtures(seed, layout, dtype, w, bs, windowed, b=3, window=40):
    """A grouped call: the cache in ``dtype``, a W-token window a row
    with padding queries, row 0 wholly inactive, row 1 ending one
    position into a block. ``windowed``: the table starts at the first
    block each sequence still holds, contexts lie past the window."""
    rs = np.random.RandomState(seed)
    lay = GROUPED_LAYOUTS[layout]
    cols = -(-(window + w) // bs) + 1 if windowed else 6
    nb = b * cols + 1
    k_cache = jnp.asarray(rs.randn(2, nb, bs, *lay["rows"]), dtype)
    v_cache = jnp.asarray(rs.randn(2, nb, bs, *lay["rows"]), dtype)
    q = jnp.asarray(rs.randn(b, w, lay["heads"], lay["head_dim"]), dtype)
    tables = jnp.asarray(1 + rs.permutation(nb - 1).reshape(b, cols), jnp.int32)
    span = cols * bs
    last = rs.randint(window + 2 * bs, 4 * span, size=b) if windowed else rs.randint(w, span, size=b)
    last[1] = last[1] // bs * bs  # the context ends one position into a block
    first = np.maximum(last - w + 1 - (window - 1), 0) // bs * bs if windowed else np.zeros(b, np.int64)
    qpos = last[:, None] - (w - 1) + np.arange(w)[None, :]
    qpos[-1, w // 2 + 1:] = -1  # padding queries
    qpos[0, :] = -1  # an inactive row
    bounds = {"window": window, "first_positions": jnp.asarray(first, jnp.int32)} if windowed else {}
    assert int(np.max(last - first)) < span
    return q, k_cache, v_cache, tables, jnp.asarray(qpos, jnp.int32), bounds


@pytest.mark.parametrize("windowed", [False, True], ids=["full", "windowed"])
@pytest.mark.parametrize("splits", [1, 4])
@pytest.mark.parametrize("bs", [16, 64])
@pytest.mark.parametrize("w", [1, 5])
@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
@pytest.mark.parametrize("layout", sorted(GROUPED_LAYOUTS))
def test_grouped_call_matches_reference(layout, dtype, w, bs, splits, windowed):
    """The grouped body (interpret mode) against the XLA composition,
    which computes in float32 throughout and rounds its result to the
    query's dtype. Tolerance from the arithmetic: a float32 cache differs
    by the order of float32 sums (2e-5, as the group-1 tests). A bfloat16
    cache's products are exact in float32, so the scores agree to float32
    rounding; the body then rounds its probabilities to bfloat16 (8
    significant bits: unit roundoff 2^-8) before ``P x V``, which moves
    an element by at most 2^-8 of ``sum p |v|`` (the composition run on
    ``|V|`` gives that sum), and each side rounds its result to
    bfloat16: 2^-8 of it each."""
    from flexflow_tpu.ops.kernels.decode_attention import (
        paged_append_attention,
        reference_paged_append_attention,
    )

    dtype = jnp.dtype(dtype)
    q, k_cache, v_cache, tables, qpos, bounds = _grouped_fixtures(
        500 + w + bs + splits, layout, dtype, w, bs, windowed
    )
    f32 = lambda x: np.asarray(x.astype(jnp.float32))
    ref = f32(reference_paged_append_attention(q, k_cache, v_cache, 1, tables, qpos, **bounds))
    out = f32(paged_append_attention(
        q, k_cache, v_cache, 1, tables, qpos, interpret=True, kv_splits=splits, **bounds
    ))
    room = _grouped_room(q, k_cache, v_cache, tables, qpos, bounds, ref, layer=1)
    err = np.abs(out - ref)
    assert np.all(err <= room), (float(err.max()), float((err / room).max()))
    # padding queries and the inactive row emit exact zeros
    assert np.all(out[np.asarray(qpos) < 0] == 0.0)


# ---------------------------------------------------------------------------
# the grouped call's walk over the block table (PR 43): several table
# columns a grid step folded as one matrix, no copy past the live context
# ---------------------------------------------------------------------------

WALK_LAYOUTS = {
    **GROUPED_LAYOUTS,
    # the long-document cell's: 128 query heads over 8 K/V heads of 128, rows of [8, 128]
    "head_rows_group16": dict(heads=128, kv_heads=8, head_dim=128, rows=(8, 128)),
}
_G4, _G8, _G16 = "packed_rows_group4", "head_rows_group8", "head_rows_group16"
# (layout, block, table columns, W, windowed, kv_splits, dtype): the widths of the cells' tables (17 and 65
# the windowed ones, neither a multiple of the columns a step; 48, 64 and 128 the full ones) and narrower
# ones that end inside a step, at the three groups, blocks of 16 and 64, decode and verify windows
WALK_CASES = [
    (_G8, 64, 17, 1, True, 1, "bfloat16"),
    (_G8, 64, 17, 5, True, 1, "float32"),
    (_G8, 64, 17, 1, True, 4, "bfloat16"),
    (_G8, 64, 48, 1, False, 1, "bfloat16"),
    (_G8, 64, 48, 5, False, 4, "float32"),
    (_G4, 16, 64, 1, False, 1, "bfloat16"),
    (_G4, 16, 64, 5, False, 1, "float32"),
    (_G4, 16, 64, 1, False, 3, "bfloat16"),
    (_G4, 16, 17, 1, True, 1, "float32"),
    (_G4, 16, 65, 5, True, 1, "bfloat16"),
    (_G16, 64, 65, 1, True, 1, "bfloat16"),
    (_G16, 64, 65, 1, True, 4, "float32"),
    (_G16, 64, 128, 1, False, 1, "bfloat16"),
    (_G16, 16, 17, 5, False, 1, "float32"),
    (_G4, 64, 48, 1, False, 1, "float32"),
    (_G8, 16, 128, 1, False, 8, "bfloat16"),
]


def _walk_fixtures(seed, layout, dtype, w, bs, cols, windowed):
    """A grouped call over tables of ``cols`` columns. Sequence 0 has a
    context of 0 (every query a padding one), sequence 1 ends one
    position into a block early in the table (the steps after it hold
    no live column), sequence 2 fills its table to the last position
    (the last step is the table's remainder), sequence 3 ends somewhere
    inside and its window's later queries are padding. ``windowed``: a
    window of ``(cols - 1) * bs - w`` positions, the tables starting at
    the first block each sequence still holds (sequence 1's context is
    shorter than the window: its table starts at 0 and ends dead)."""
    rs = np.random.RandomState(seed)
    lay = WALK_LAYOUTS[layout]
    b, span = 4, cols * bs
    window = (cols - 1) * bs - w if windowed else 0
    nb = b * cols + 1
    k_cache = jnp.asarray(rs.randn(1, nb, bs, *lay["rows"]), dtype)
    v_cache = jnp.asarray(rs.randn(1, nb, bs, *lay["rows"]), dtype)
    q = jnp.asarray(rs.randn(b, w, lay["heads"], lay["head_dim"]), dtype)
    tables = 1 + rs.permutation(nb - 1).reshape(b, cols).astype(np.int32)
    last = np.array([w - 1, 2 * bs + w - 1, span - 1, rs.randint(span // 2, span - bs)])
    last[1] = max(last[1] - w + 1, w - 1) // bs * bs + w - 1  # the first query one position into a block
    first = np.zeros(b, np.int64)
    if windowed:
        last[2:] += rs.randint(2, 5) * span  # far past the window
        first = np.maximum(last - w + 1 - (window - 1), 0) // bs * bs
        last[2] = first[2] + span - 1
    qpos = last[:, None] - (w - 1) + np.arange(w)[None, :]
    qpos[3, w // 2 + 1:] = -1  # padding queries
    qpos[0, :] = -1  # a context of 0
    assert np.all(qpos[1:, 0] >= 0) and int(np.max(last - first)) < span
    bounds = {"window": window, "first_positions": jnp.asarray(first, jnp.int32)} if windowed else {}
    return q, k_cache, v_cache, tables, jnp.asarray(qpos, jnp.int32), bounds, first


def _grouped_room(q, k_cache, v_cache, tables, qpos, bounds, ref, layer=0):
    """What the arithmetic lets a grouped call's result differ from the
    XLA composition's ``ref`` by, per element
    (:func:`test_grouped_call_matches_reference` derives it)."""
    from flexflow_tpu.ops.kernels.decode_attention import reference_paged_append_attention

    if k_cache.dtype == jnp.float32:
        return 2e-5 + 2e-5 * np.abs(ref)
    weight = np.asarray(reference_paged_append_attention(
        q.astype(jnp.float32), k_cache.astype(jnp.float32), jnp.abs(v_cache).astype(jnp.float32),
        layer, tables, qpos, **bounds
    ))
    return 2.0 ** -8 * weight + 2.0 ** -7 * np.abs(ref) + 1e-6


@pytest.mark.parametrize("layout,bs,cols,w,windowed,splits,dtype", WALK_CASES)
def test_grouped_walk_matches_reference(layout, bs, cols, w, windowed, splits, dtype):
    """The grouped call (interpret mode) against the XLA composition at
    the cells' table widths: several columns a grid step, a last step of
    the table's remainder, contexts that end inside a step, steps with no
    live column, a context of 0 and padding queries, split and
    sequential. Tolerances as :func:`test_grouped_call_matches_reference`."""
    from flexflow_tpu.ops.kernels.decode_attention import (
        grouped_columns_per_step, paged_append_attention, reference_paged_append_attention,
    )

    dtype = jnp.dtype(dtype)
    q, k_cache, v_cache, tables, qpos, bounds, _ = _walk_fixtures(900 + cols + w + bs, layout, dtype, w, bs, cols, windowed)
    lay = WALK_LAYOUTS[layout]
    per_step = grouped_columns_per_step(bs, lay["rows"], w * lay["heads"], dtype.itemsize, cols)
    assert per_step > 1, "the case walks one column a step: it tests nothing new"
    f32 = lambda x: np.asarray(x.astype(jnp.float32))
    tables = jnp.asarray(tables)
    ref = f32(reference_paged_append_attention(q, k_cache, v_cache, 0, tables, qpos, **bounds))
    out = f32(paged_append_attention(q, k_cache, v_cache, 0, tables, qpos, interpret=True, kv_splits=splits, **bounds))
    room = _grouped_room(q, k_cache, v_cache, tables, qpos, bounds, ref)
    err = np.abs(out - ref)
    assert np.all(err <= room), (float(err.max()), float((err / room).max()))
    assert np.all(out[np.asarray(qpos) < 0] == 0.0)  # padding queries and the context of 0: exact zeros
    assert np.any(out[1] != 0.0) and np.any(out[2] != 0.0)


@pytest.mark.parametrize("windowed", [False, True], ids=["full", "windowed"])
@pytest.mark.parametrize("splits", [1, 3])
def test_grouped_result_does_not_depend_on_dead_table_entries(splits, windowed):
    """The columns past a sequence's last position are never copied: with
    their table entries pointed at a block of NaN (whose products with a
    zero probability would still be NaN) the result is the same, bit for
    bit, as with the entries the table came with."""
    from flexflow_tpu.ops.kernels.decode_attention import paged_append_attention

    bs, cols, w = 16, 17, 1
    q, k_cache, v_cache, tables, qpos, bounds, first = _walk_fixtures(77, _G4, jnp.float32, w, bs, cols, windowed)
    poison = k_cache.shape[1]
    k_cache, v_cache = (jnp.concatenate([c, jnp.full_like(c[:, :1], jnp.nan)], axis=1) for c in (k_cache, v_cache))
    live = (np.max(np.asarray(qpos), axis=1) - first) // bs  # the last live column; -1: none
    dead = np.arange(cols)[None, :] > live[:, None]
    assert dead[0].all() and dead[1].sum() >= cols - 4 and not dead[2].any()
    call = lambda t: np.asarray(paged_append_attention(
        q, k_cache, v_cache, 0, jnp.asarray(t), qpos, interpret=True, kv_splits=splits, **bounds
    ))
    clean, poisoned = call(tables), call(np.where(dead, poison, tables).astype(np.int32))
    assert np.all(np.isfinite(poisoned)) and np.array_equal(clean, poisoned)


@pytest.mark.parametrize("bs,cols", [(16, 64), (64, 17), (64, 48), (64, 65), (64, 128)])
def test_the_walk_ends_at_the_last_column_a_query_sees(bs, cols):
    """``_live_columns``, the bound of a sequence's walk: the table
    columns up to its largest position's (from the position of column 0
    where the table starts behind a window), none for a sequence whose
    queries are all padding; a padding query beside live ones counts for
    nothing."""
    from flexflow_tpu.ops.kernels.decode_attention import _live_columns

    qpos = np.array([[-1, -1], [0, -1], [bs - 2, bs - 1], [bs - 1, bs], [5 * bs, -1], [cols * bs - 2, cols * bs - 1]], np.int32)
    want = [0, 1, 1, 2, 6, cols]
    assert list(np.asarray(_live_columns(jnp.asarray(qpos), None, bs, cols))) == want
    first = np.array([0, 0, 0, 0, 3 * bs, 0], np.int32)
    want[4] = 3
    assert list(np.asarray(_live_columns(jnp.asarray(qpos), jnp.asarray(first), bs, cols))) == want
    # positions far past a table that starts behind a window: the table's width bounds the walk
    far = jnp.asarray(qpos + 40 * cols * bs * (qpos >= 0))
    assert list(np.asarray(_live_columns(far, jnp.asarray(first), bs, cols))) == [0, cols, cols, cols, cols, cols]


# (block, rows, query rows M, itemsize) of the three grouped cells' decode calls and what the rule gives them
CELL_WALKS = {
    "lfm2-8b-a1b.gen-batch": ((16, (4, 128), 32, 2), 32),  # 32 query heads over 8 K/V heads of 64, two a row
    "mellum2-12b.code-gen": ((64, (4, 128), 32, 2), 8),  # 32 over 4 of 128 (its windowed table of 17 columns: 6)
    "command-a-plus.long-doc": ((64, (8, 128), 128, 2), 4),  # 128 over 8 of 128: the VMEM budget halves 8
}


@pytest.mark.parametrize("cell", sorted(CELL_WALKS))
def test_columns_per_step_at_the_cells_shapes(cell):
    """The one rule for the columns a grid step folds, at the three
    grouped cells' decode calls: what fills a step with
    ``GROUPED_STEP_POSITIONS`` positions, inside the VMEM budget, and no
    more columns than the table has."""
    from flexflow_tpu.ops.kernels import decode_attention as kernels

    (bs, rows, m, itemsize), want = CELL_WALKS[cell]
    got = kernels.grouped_columns_per_step(bs, rows, m, itemsize)
    assert got == want
    assert kernels._grouped_vmem_bytes(*rows, bs, m, itemsize, got) <= kernels._VMEM_BUDGET_BYTES
    assert (
        2 * got * bs > kernels.GROUPED_STEP_POSITIONS or 2 * got > kernels.MAX_COLUMNS_PER_STEP
        or kernels._grouped_vmem_bytes(*rows, bs, m, itemsize, 2 * got) > kernels._VMEM_BUDGET_BYTES
    )
    assert kernels.grouped_columns_per_step(bs, rows, m, itemsize, max_blocks=3) == 3
    # a table is walked in as few steps as the most columns a step allow, and those steps are even
    for cols in (17, 48, 64, 65, 128):
        per_step = kernels.grouped_columns_per_step(bs, rows, m, itemsize, max_blocks=cols)
        assert per_step <= got and -(-cols // per_step) == -(-cols // min(got, cols))
        assert per_step * -(-cols // per_step) - cols < -(-cols // per_step)  # fewer dead columns than steps
    if cell == "mellum2-12b.code-gen":
        assert kernels.grouped_columns_per_step(bs, rows, m, itemsize, max_blocks=17) == 6  # 6 + 6 + 5, not 8 + 8 + 1
    # a verify window of 5 holds five times the query rows: never more columns than the decode call
    assert 1 <= kernels.grouped_columns_per_step(bs, rows, 5 * m, itemsize) <= got


def test_walk_report_counts_the_steps_of_a_call():
    from flexflow_tpu.ops.kernels.decode_attention import paged_grid, paged_walk

    report = lambda columns, grid, walk: {"columns_per_step": columns, "grid_steps": grid, "walk_steps_at_most": walk}
    # Mellum2's decode calls: 48 slots over 48 columns (8 a step) and over 17 (6 a step), a grid step a slot
    assert paged_walk(4, 128, 64, 8, 2, 8, 48, 48) == report(8, 48, 48 * 6)
    assert paged_walk(4, 128, 64, 8, 2, 8, 48, 17) == report(6, 48, 48 * 3)
    # LFM2's: 64 slots over 64 columns of 16 positions, 32 a step; Command A+'s: 16 over 128 and 65, 4 a step
    assert paged_walk(8, 64, 16, 4, 2, 4, 64, 64) == report(32, 64, 64 * 2)
    assert paged_walk(8, 128, 64, 16, 2, 16, 16, 65) == report(4, 16, 16 * 17)
    assert paged_walk(8, 128, 64, 16, 2, 16, 16, 128) == report(4, 16, 16 * 32)
    # a group-1 call walks one column a grid step, every column of the table, as it did
    assert paged_walk(16, 64, 16, 1, 4, 1, 8, 64) == report(1, 8 * 64, 8 * 64)
    assert paged_walk(16, 64, 16, 1, 4, 1, 2, 64, kv_splits=8) == report(1, 2 * 8 * 8, 2 * 8 * 8)
    # a split grouped call: a grid step a split, whose walk is over its share of the table
    assert paged_walk(4, 128, 64, 8, 2, 8, 2, 48, kv_splits=4) == report(6, 2 * 4, 2 * 4 * 2)
    assert paged_grid(1, 8, 64, 4) == (8, 4, 16) and paged_grid(8, 2, 33, 3) == (2, 3) and paged_grid(8, 2, 33) == (2,)


def _kernel_bodies(fn, *args):
    """The Pallas calls in ``fn``'s jaxpr: per call its name, grid,
    scratch shapes and the primitive counts of the kernel body (nested
    jaxprs walked)."""
    import collections

    calls = []

    def walk(jaxpr, counts):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                inner = collections.Counter()
                walk(eqn.params["jaxpr"], inner)
                scratch = eqn.params["grid_mapping"].num_scratch_operands
                calls.append({
                    "name": eqn.params["name"],
                    "grid": tuple(eqn.params["grid_mapping"].grid),
                    "scratch": [tuple(v.aval.shape) for v in eqn.params["jaxpr"].invars[-scratch:]],
                    "primitives": dict(inner),
                })
                continue
            if counts is not None:
                counts[eqn.primitive.name] += 1
            for v in eqn.params.values():
                for sub in v if isinstance(v, (list, tuple)) else [v]:
                    sub = getattr(sub, "jaxpr", sub)
                    if hasattr(sub, "eqns"):
                        walk(sub, counts)

    walk(jax.make_jaxpr(fn)(*args).jaxpr, None)
    return calls


# The group-1 kernel bodies at GPT-2's decode and verify shapes (8 slots,
# 16 heads of 64 as rows [8, 128], float32, block 16, 64 table columns),
# counted AT THE PARENT COMMIT (25a2f61, before the grouped body existed):
# a plain multi-head call lowers to what it lowered to then.
_SEQUENTIAL = {
    "add": 4, "and": 1, "broadcast_in_dim": 16, "cond": 3, "convert_element_type": 8, "div": 2, "eq": 4,
    "exp": 2, "get": 10, "iota": 2, "jit": 8, "le": 2, "max": 2, "mul": 6, "ne": 2, "program_id": 2,
    "reduce_max": 1, "reduce_sum": 4, "rem": 1, "scan": 1, "select_n": 7, "sign": 2, "sub": 3, "swap": 7,
}
_SPLIT = {
    "add": 5, "and": 2, "broadcast_in_dim": 16, "cond": 3, "convert_element_type": 8, "div": 1, "eq": 4,
    "exp": 2, "get": 11, "iota": 2, "jit": 8, "le": 2, "lt": 1, "max": 1, "mul": 7, "ne": 2, "program_id": 3,
    "reduce_max": 1, "reduce_sum": 4, "rem": 1, "scan": 1, "select_n": 7, "sign": 2, "sub": 3, "swap": 9,
}
# (W, kv_splits): name, primitives, and the sha256 (16 hex digits) of the
# Mosaic module the call lowers to for a TPU, printed without source
# locations: the decode step (W = 1) and a verify window (W = 5) over 8
# slots, a suffix prefill's window (W = 32) over one
GROUP1_KERNELS = {
    (1, 1): ("paged_append_attention", _SEQUENTIAL, "66288da2493959ef"),
    (5, 1): ("paged_append_attention", _SEQUENTIAL, "7788ec92cfa9b617"),
    (32, 1): ("paged_append_attention", _SEQUENTIAL, "df2b39aa8c923546"),
    (1, 4): ("paged_append_attention_split", _SPLIT, "ac2ec5f53ef12125"),
    (5, 4): ("paged_append_attention_split", _SPLIT, "fbc2432fb25c5d42"),
    (32, 4): ("paged_append_attention_split", _SPLIT, "0a3715ab04a6abe6"),
}


def _gpt2_call(w, splits, heads=16):
    """GPT-2-medium's paged call (24 layers of 16 K/V heads of 64 stored
    as rows [8, 128], float32, block 16, 64 table columns) under ``heads``
    query heads, as abstract arguments."""
    from flexflow_tpu.ops.kernels.decode_attention import paged_append_attention

    shape = jax.ShapeDtypeStruct
    b = 8 if w < 32 else 1
    cache = shape((24, 8 * 64 + 1, 16, 8, 128), jnp.float32)
    args = (shape((b, w, heads, 64), jnp.float32), cache, cache, shape((b, 64), jnp.int32), shape((b, w), jnp.int32))
    return (lambda q, k, v, t, p: paged_append_attention(q, k, v, 3, t, p, kv_splits=splits), *args)


def _mosaic_modules(fn, *args):
    """The Mosaic kernels ``fn`` lowers to for a TPU (a lowering needs
    no chip and no libtpu), as MLIR text without source locations: line
    numbers move with every edit of the file, the program does not."""
    import base64
    import re

    from jax._src.interpreters import mlir as jax_mlir
    from jax._src.lib.mlir import ir

    text = jax.jit(fn).trace(*args).lower(lowering_platforms=("tpu",)).as_text()
    modules = []
    for body in re.finditer(r"body\\22: \\22([A-Za-z0-9+/=]+)\\22", text):
        ctx = jax_mlir.make_ir_context()
        ctx.allow_unregistered_dialects = True  # the serialised dialect is `stable_mosaic`
        with ctx:
            modules.append(ir.Module.parse(base64.b64decode(body.group(1))).operation.get_asm(enable_debug_info=False))
    return modules


@pytest.mark.parametrize("w,splits", sorted(GROUP1_KERNELS))
def test_group1_call_lowers_to_the_parents_kernel(w, splits):
    """GPT-2's decode (W = 1) and verify (W = 5) calls, sequential and
    split: ONE Pallas call of the parent's name, grid and scratch shapes,
    whose body holds the parent's primitives, count for count (the walk
    over the window is still the ``scan`` of a ``fori_loop``, and no
    ``dot_general`` has appeared)."""
    name, primitives, _ = GROUP1_KERNELS[(w, splits)]
    (call,) = _kernel_bodies(*_gpt2_call(w, splits))
    slots = 8 if w < 32 else 1
    assert (call["name"], call["grid"]) == (name, (slots, 64) if splits == 1 else (slots, 4, 16))
    assert call["scratch"] == [(w, 8, 128)] * 3
    assert call["primitives"] == primitives


@pytest.mark.parametrize("w,splits", sorted(GROUP1_KERNELS))
def test_group1_call_lowers_to_the_parents_mosaic_module(w, splits):
    """Letter for letter: the Mosaic module of the group-1 call, printed
    without source locations, hashes to what it hashed to at the parent
    commit (25a2f61; the same jax and jaxlib). A float32 product on the
    MXU in this call is what PR 32 was refused for in chat-steady."""
    import hashlib

    (module,) = _mosaic_modules(*_gpt2_call(w, splits))
    assert "tpu.matmul" not in module and "vector.contract" not in module
    assert hashlib.sha256(module.encode()).hexdigest()[:16] == GROUP1_KERNELS[(w, splits)][2]


@pytest.mark.parametrize("splits", [1, 4])
def test_grouped_call_scores_on_the_mxu(splits):
    """The same cache rows under 32 query heads (group 2) take the other
    body: two ``dot_general`` a block and no walk over the window, query
    rows for scratch, under the Pallas names the trace readers look for."""
    from flexflow_tpu.ops.kernels.decode_attention import kernel_body, query_group

    assert kernel_body(query_group(32, 64, (8, 128))) == "mxu"
    assert kernel_body(query_group(16, 64, (8, 128))) == "vpu"
    (call,) = _kernel_bodies(*_gpt2_call(1, splits, heads=32))
    assert call["name"] == ("paged_append_attention" if splits == 1 else "paged_append_attention_split")
    assert call["primitives"]["dot_general"] == 2 and "scan" not in call["primitives"]
    rows = 2 * 8 * 2  # window queries x cache rows x heads a row
    # two buffers of a step's 16 columns of 16 positions for K and for V, their copies' semaphores, what a
    # program hands the next, and the query rows' softmax state
    assert call["scratch"] == [(2, 256, 8, 128)] * 2 + [(2, 2), (2,), (rows, 1), (rows, 1), (rows, 128)]
    # a grid step a sequence (a split of one): the walk over the table's columns is the kernel's own
    assert call["grid"] == ((8,) if splits == 1 else (8, 4))
    # a copy of K and one of V a column of the step, in a loop over the step's live columns: waited for in one
    # place and started in two (a program's own first step; the step after the one being folded, which at a
    # program's last step is the next program's first)
    assert call["primitives"]["dma_wait"] == 2 and call["primitives"]["dma_start"] == 2 * 2
    (module,) = _mosaic_modules(*_gpt2_call(1, splits, heads=32))
    assert module.count("tpu.matmul") == 2


def test_windowed_grouped_call_keeps_its_name():
    from flexflow_tpu.ops.kernels.decode_attention import paged_append_attention

    shape = jax.ShapeDtypeStruct
    cache = shape((9, 48 * 17 + 1, 64, 4, 128), jnp.bfloat16)
    args = (shape((48, 1, 32, 128), jnp.bfloat16), cache, cache, shape((48, 17), jnp.int32),
            shape((48, 1), jnp.int32), shape((48,), jnp.int32))
    (call,) = _kernel_bodies(
        lambda q, k, v, t, p, f: paged_append_attention(q, k, v, 8, t, p, window=1024, first_positions=f), *args
    )
    assert call["name"] == "paged_window_attention" and call["grid"] == (48,)
    assert call["primitives"]["dot_general"] == 2


def test_refusal_and_vmem_estimate_describe_the_body_that_runs():
    from flexflow_tpu.ops.kernels.decode_attention import _vmem_bytes, paged_kernel_refusal

    # group 1: the parent's estimate (three block-sized float32 temporaries)
    assert _vmem_bytes(16, 64, 16, 1, 4) == 487424
    assert "one query at a time on the VPU" in paged_kernel_refusal(16, 64, 16, 33, 4)
    # grouped: the score matrix and the query rows' state, and no such sentence
    said = paged_kernel_refusal(4, 128, 64, 40, 2, group=8)
    assert "VPU" not in said and "group 8" in said and "window 40 > 32" in said
    assert paged_kernel_refusal(4, 128, 64, 8, 2, group=8) is None  # Mellum2's decode call
    assert paged_kernel_refusal(8, 64, 16, 4, 2, group=4) is None  # LFM2's
    assert _vmem_bytes(4, 128, 64, 8, 2, group=8) != _vmem_bytes(4, 128, 64, 8, 2)
    # at the walk the call takes: two buffers of K and of V of a step's columns and the step once more (3 x
    # 2 MiB: 8 columns of 64 positions of rows padded to 8 x 128, as 32 columns of 16), the scores of its 2,048
    # lines under 32 query rows four times over (1 MiB), Q / O / positions / state (112 KiB)
    assert _vmem_bytes(4, 128, 64, 8, 2, group=8) == _vmem_bytes(8, 64, 16, 4, 2, group=4) == 3 * (2 << 20) + (1 << 20) + 114688
    # a block of 8 K/V heads under 128 query rows: 4 columns a step (8 would be 20 MiB), 4 MiB of scores
    assert _vmem_bytes(8, 128, 64, 16, 2, group=16) == 3 * (1 << 20) + (4 << 20) + 458752
    assert "MiB of VMEM" in paged_kernel_refusal(64, 128, 64, 32, 4, group=4)


def _toy_engine(which):
    """A GPT-2 (plain multi-head), an LFM2-like (grouped, one pool) and a
    Mellum2-like (grouped, window layers beside full ones) engine at
    rehearsal widths."""
    import json
    import pathlib
    import sys

    root = pathlib.Path(__file__).resolve().parents[1]
    if str(root) not in sys.path:
        sys.path.insert(0, str(root))
    from benchmark import spec
    from benchmark.reference import lfm2, mellum2
    from flexflow_tpu.generation import GenerationEngine, init_decoder_params
    from flexflow_tpu.models.transformer import TransformerConfig

    if which == "gpt2":
        cfg = TransformerConfig(num_layers=2, hidden_size=64, num_heads=4, ff_size=128, seq_length=64,
                                vocab_size=512, causal=True)
        return GenerationEngine(init_decoder_params(jax.random.key(0), cfg), cfg, max_batch_slots=2, block_size=8)
    ref = {"lfm2": lfm2, "mellum2": mellum2}[which]
    body = json.loads((root / "benchmark/configs" / {"lfm2": "lfm2-8b-a1b.json", "mellum2": "mellum2-12b.json"}[which]).read_text())
    config = spec._merge(body, body["rehearsal"])  # 4 query heads over 2 K/V heads of 16
    params = ref.cast_params(ref.init_params(3, config), jnp.float32)
    return GenerationEngine(params, ref.engine_config(config, 64), max_batch_slots=2, block_size=8,
                            prompt_buckets=[16, 32], max_seq_len=64)


@pytest.mark.parametrize("on_chip", [False, True], ids=["cpu_backend", "tpu_gate"])
@pytest.mark.parametrize("which,kinds,group,body", [
    # (table columns a step of the walk, grid steps a call, the walk's steps at most) of a kind's decode call:
    # two slots over tables of 8 columns of 8 positions (the window kind: 3), a group-1 call split by its own
    # rule (two slots or fewer) or not, a grouped call a grid step a slot
    ("gpt2", {"full": (1, 16, 16)}, 1, "vpu"),
    ("lfm2", {"full": (8, 2, 2)}, 2, "mxu"),
    ("mellum2", {"full": (8, 2, 2), "window": (3, 2, 2)}, 2, "mxu"),
])
def test_stats_and_startup_line_name_the_body_of_each_attention_kind(which, kinds, group, body, on_chip, monkeypatch, caplog):
    """``/v2/stats`` ``kernels`` and the server's start-up line say, per
    attention kind of the loaded model, which body its paged decode call
    lowered to and at what group: the XLA composition on the CPU backend;
    through the TPU's gate the VPU body for GPT-2 and the MXU body for
    the two grouped models, the group read off the shapes, and beside
    them the walk that call runs over its block table: the columns a
    step and the steps a call (nothing for the composition)."""
    import logging

    import flexflow_tpu.ops.attention as attention
    from flexflow_tpu.serving.generation import GenerationModel

    engine = _toy_engine(which)
    if on_chip:
        monkeypatch.setattr(attention, "on_tpu", lambda: True)
        engine.backend = "tpu"
        engine.attention_kernels = engine.paged_lowerings()
    keys = ("columns_per_step", "grid_steps", "walk_steps_at_most")
    want = {
        kind: {"body": body if on_chip else "reference", "group": group, **(dict(zip(keys, walk)) if on_chip else {})}
        for kind, walk in kinds.items()
    }
    model = GenerationModel(engine, name=which)
    assert model.scheduler.stats.snapshot()["kernels"] == want
    with caplog.at_level(logging.INFO, logger="flexflow_tpu.serving.generation"):
        model.start()
        model.stop(drain=False)
    (line,) = [r.getMessage() for r in caplog.records if "paged attention" in r.getMessage()]
    assert line.startswith(f"generation model '{which}' starts: paged attention ")
    for kind, low in want.items():
        walk = f", {low['columns_per_step']} columns a step over {low['grid_steps']} grid steps a call" if on_chip else ""
        assert f"{kind}: {low['body']} body at group {group}{walk}" in line and ("columns a step" in line) == on_chip
