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
    if dtype == jnp.float32:
        room = 2e-5 + 2e-5 * np.abs(ref)
    else:
        weight = f32(reference_paged_append_attention(
            q.astype(jnp.float32), k_cache.astype(jnp.float32), jnp.abs(v_cache).astype(jnp.float32),
            1, tables, qpos, **bounds
        ))
        room = 2.0 ** -8 * weight + 2.0 ** -7 * np.abs(ref) + 1e-6
    err = np.abs(out - ref)
    assert np.all(err <= room), (float(err.max()), float((err / room).max()))
    # padding queries and the inactive row emit exact zeros
    assert np.all(out[np.asarray(qpos) < 0] == 0.0)


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
    assert call["scratch"] == [(rows, 1), (rows, 1), (rows, 128)]
    assert call["grid"] == ((8, 64) if splits == 1 else (8, 4, 16))
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
    assert call["name"] == "paged_window_attention" and call["grid"] == (48, 17)
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
    ("gpt2", ["full"], 1, "vpu"),
    ("lfm2", ["full"], 2, "mxu"),
    ("mellum2", ["full", "window"], 2, "mxu"),
])
def test_stats_and_startup_line_name_the_body_of_each_attention_kind(which, kinds, group, body, on_chip, monkeypatch, caplog):
    """``/v2/stats`` ``kernels`` and the server's start-up line say, per
    attention kind of the loaded model, which body its paged decode call
    lowered to and at what group: the XLA composition on the CPU backend;
    through the TPU's gate the VPU body for GPT-2 and the MXU body for
    the two grouped models, the group read off the shapes."""
    import logging

    import flexflow_tpu.ops.attention as attention
    from flexflow_tpu.serving.generation import GenerationModel

    engine = _toy_engine(which)
    if on_chip:
        monkeypatch.setattr(attention, "on_tpu", lambda: True)
        engine.backend = "tpu"
        engine.attention_kernels = engine.paged_lowerings()
    want = {kind: {"body": body if on_chip else "reference", "group": group} for kind in kinds}
    model = GenerationModel(engine, name=which)
    assert model.scheduler.stats.snapshot()["kernels"] == want
    with caplog.at_level(logging.INFO, logger="flexflow_tpu.serving.generation"):
        model.start()
        model.stop(drain=False)
    (line,) = [r.getMessage() for r in caplog.records if "paged attention" in r.getMessage()]
    assert line.startswith(f"generation model '{which}' starts: paged attention ")
    for kind in kinds:
        assert f"{kind}: {want[kind]['body']} body at group {group}" in line
