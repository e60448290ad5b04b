"""Generation subsystem tests: KV-cache correctness, the prefill/decode
split, continuous batching, and the serving surface.

Acceptance criteria covered (ISSUE 2):
  * incremental KV-cache decode logits == full-context forward logits
    (fp32, ~1e-5) across prompt lengths straddling bucket boundaries
  * scheduler property tests on a virtual clock: join-mid-flight,
    free-on-finish, preempt-on-full (with exact stream continuity)
  * steady-state decode never recompiles (trace counters)
  * resilience parity with the batcher: queue-full, deadlines, retry,
    breaker — through the generation.prefill / generation.decode_step
    fault sites
  * HTTP generate (JSON + SSE) and /v2/stats
"""
import json
import urllib.error
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flexflow_tpu.generation import (
    BlockAllocator,
    CacheConfig,
    ContinuousBatchingScheduler,
    GenerationEngine,
    KVCache,
    SamplingParams,
    forward_full,
    init_decoder_params,
)
from flexflow_tpu.generation.decoder import decode_step, prefill
from flexflow_tpu.generation.cache import slot_mapping
from flexflow_tpu.models.transformer import TransformerConfig
from flexflow_tpu.runtime import faults
from flexflow_tpu.runtime.faults import FaultInjected, FaultPlan, TransientDeviceError
from flexflow_tpu.serving import RetryPolicy
from flexflow_tpu.serving.resilience import (
    CircuitBreaker,
    CircuitOpenError,
    DeadlineExceededError,
    QueueFullError,
)

pytestmark = pytest.mark.generation

CFG = TransformerConfig(
    num_layers=2, hidden_size=32, num_heads=4, ff_size=64,
    seq_length=64, vocab_size=50, causal=True,
)
BUCKETS = (8, 16, 32, 64)
BLOCK = 8


from conftest import FakeClock  # noqa: E402


@pytest.fixture(scope="module")
def decoder_params():
    return init_decoder_params(jax.random.key(0), CFG)


@pytest.fixture(scope="module")
def engine(decoder_params):
    """Shared engine: jit traces amortize across the module's tests."""
    return GenerationEngine(
        decoder_params, CFG, max_batch_slots=3, block_size=BLOCK, prompt_buckets=BUCKETS
    )


def make_engine(decoder_params, num_blocks, slots=3):
    cc = CacheConfig(
        num_layers=CFG.num_layers, num_heads=CFG.num_heads,
        head_dim=CFG.hidden_size // CFG.num_heads,
        num_blocks=num_blocks, block_size=BLOCK,
    )
    return GenerationEngine(
        decoder_params, CFG, cache_config=cc, max_batch_slots=slots, prompt_buckets=BUCKETS
    )


@pytest.fixture(autouse=True)
def _no_leaked_plan():
    yield
    assert faults.active_plan() is None, "a test leaked an installed FaultPlan"


def naive_greedy(params, prompt, n):
    seq = list(prompt)
    for _ in range(n):
        logits = forward_full(params, jnp.asarray([seq], jnp.int32))
        seq.append(int(jnp.argmax(logits[0, -1])))
    return seq[len(prompt):]


# ---------------------------------------------------------------------------
# cache + allocator
# ---------------------------------------------------------------------------


def test_block_allocator_roundtrip():
    cc = CacheConfig(num_layers=1, num_heads=2, head_dim=8, num_blocks=5, block_size=4)
    alloc = BlockAllocator(cc)
    assert alloc.num_total == 4  # block 0 reserved as scratch
    a = alloc.allocate(3)
    assert a is not None and 0 not in a and len(set(a)) == 3
    assert alloc.allocate(2) is None  # atomic: no partial grab
    assert alloc.num_free == 1
    alloc.free(a)
    assert alloc.num_free == 4
    with pytest.raises(ValueError):
        alloc.free(a[:1])  # double free
    with pytest.raises(ValueError):
        alloc.free([0])  # scratch is never allocatable


def test_cache_budget_sizing():
    cc = CacheConfig.from_budget(
        1 << 20, num_layers=2, num_heads=4, head_dim=8, block_size=16
    )
    assert cc.bytes_per_block == 2 * 2 * 16 * 4 * 8 * 4
    assert cc.num_blocks == (1 << 20) // cc.bytes_per_block
    assert cc.total_bytes <= 1 << 20
    with pytest.raises(ValueError):
        CacheConfig.from_budget(100, num_layers=2, num_heads=4, head_dim=8)


def test_slot_mapping_out_of_table_hits_scratch():
    table = jnp.asarray([3, 7], jnp.int32)
    block, offset = slot_mapping(table, jnp.asarray([0, 5, 9, 100], jnp.int32), 4)
    np.testing.assert_array_equal(np.asarray(block), [3, 7, 0, 0])
    np.testing.assert_array_equal(np.asarray(offset), [0, 1, 0, 0])


# ---------------------------------------------------------------------------
# KV-cache correctness: incremental decode == full-context forward
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("prompt_len", [5, 8, 9, 15, 16, 17, 31])
def test_decode_logits_match_full_forward(decoder_params, prompt_len):
    """The acceptance criterion, at logits level: prefill a prompt into
    the cache, decode step by step, and compare every decode logit
    vector to the full-context forward at that position. Lengths
    straddle the 8/16/32 bucket boundaries."""
    rs = np.random.RandomState(prompt_len)
    prompt = rs.randint(0, CFG.vocab_size, prompt_len).tolist()
    n_new = 4
    cc = CacheConfig(
        num_layers=CFG.num_layers, num_heads=CFG.num_heads,
        head_dim=CFG.hidden_size // CFG.num_heads, num_blocks=10, block_size=BLOCK,
    )
    cache = KVCache.create(cc)
    blocks = list(range(1, 9))
    table = jnp.asarray(blocks + [0] * 0, jnp.int32)

    # prefill: bucketed/padded like the engine does it
    bucket = next(b for b in BUCKETS if b >= prompt_len)
    padded = np.zeros((1, bucket), np.int32)
    padded[0, :prompt_len] = prompt
    logits_pre, ks, vs = prefill(
        decoder_params, jnp.asarray(padded), jnp.asarray([prompt_len], jnp.int32)
    )
    positions = jnp.arange(bucket, dtype=jnp.int32)
    block, offset = slot_mapping(table, positions, BLOCK)
    block = jnp.where(positions < prompt_len, block, 0)  # padding -> scratch
    offset = jnp.where(positions < prompt_len, offset, 0)
    ck = cache.k.at[:, block, offset].set(ks[:, 0])
    cv = cache.v.at[:, block, offset].set(vs[:, 0])

    seq = list(prompt)
    full = forward_full(decoder_params, jnp.asarray([seq], jnp.int32))
    np.testing.assert_allclose(
        np.asarray(logits_pre[0, prompt_len - 1]),
        np.asarray(full[0, -1]),
        atol=1e-5,
        err_msg="padded prefill logits != unpadded forward",
    )
    tables = jnp.asarray([blocks], jnp.int32)
    for step in range(n_new):
        tok = int(jnp.argmax(full[0, -1]))
        seq.append(tok)
        pos = len(seq) - 1
        logits, ck, cv = decode_step(
            decoder_params,
            jnp.asarray([tok], jnp.int32),
            jnp.asarray([pos], jnp.int32),
            ck, cv, tables,
            jnp.asarray([pos + 1], jnp.int32),
            backend="cpu",
        )
        full = forward_full(decoder_params, jnp.asarray([seq], jnp.int32))
        np.testing.assert_allclose(
            np.asarray(logits[0]), np.asarray(full[0, -1]), atol=1e-5,
            err_msg=f"decode logits diverged at step {step} (prompt_len {prompt_len})",
        )


@pytest.mark.parametrize("prompt_len", [7, 8, 9, 16, 17])
def test_engine_greedy_matches_naive(engine, decoder_params, prompt_len):
    """End-to-end through the engine + scheduler: greedy generation
    equals argmax-over-full-recompute, across bucket boundaries."""
    rs = np.random.RandomState(100 + prompt_len)
    prompt = rs.randint(0, CFG.vocab_size, prompt_len).tolist()
    (out,) = engine.generate([prompt], SamplingParams(max_new_tokens=5))
    assert out == naive_greedy(decoder_params, prompt, 5)


def test_eos_stops_generation(engine, decoder_params):
    prompt = [1, 2, 3]
    ref = naive_greedy(decoder_params, prompt, 8)
    eos = ref[2]
    (out,) = engine.generate([prompt], SamplingParams(max_new_tokens=8, eos_id=eos))
    assert out == ref[:3] and out[-1] == eos


def test_pallas_decode_kernel_matches_reference():
    """The TPU lowering, in interpret mode, against the XLA path."""
    from flexflow_tpu.ops.kernels.decode_attention import (
        paged_decode_attention,
        reference_paged_attention,
    )

    rs = np.random.RandomState(0)
    b, h, d, nb, bs, mb = 3, 4, 64, 10, 8, 4
    q = jnp.asarray(rs.randn(b, h, d).astype(np.float32))
    kc = jnp.asarray(rs.randn(3, nb, bs, h, d).astype(np.float32))  # [L, nb, bs, H, D]
    vc = jnp.asarray(rs.randn(3, nb, bs, h, d).astype(np.float32))
    bt = jnp.asarray(rs.randint(0, nb, (b, mb)).astype(np.int32))
    cl = jnp.asarray(np.array([5, 17, 0], np.int32))  # incl. inactive slot
    ref = reference_paged_attention(q, kc, vc, 2, bt, cl)
    ker = paged_decode_attention(q, kc, vc, 2, bt, cl, interpret=True)
    np.testing.assert_allclose(np.asarray(ker), np.asarray(ref), atol=1e-5)
    assert float(jnp.max(jnp.abs(ref[2]))) == 0.0  # inactive -> zeros, not NaN


# ---------------------------------------------------------------------------
# the cache stays where it is (ISSUE 24): rows written in place, the whole
# 5-D array handed to the kernel, stored in the shape whose default device
# layout the kernel reads
# ---------------------------------------------------------------------------


def _cfg3(hidden):
    """Three layers, four heads: of 8 (stored as they are: 4 x 8) or of
    32 (four heads fill one 128-lane row: stored 1 x 128)."""
    return TransformerConfig(
        num_layers=3, hidden_size=hidden, num_heads=4, ff_size=64,
        seq_length=64, vocab_size=50, causal=True,
    )


@pytest.mark.parametrize("hidden", [32, 128], ids=["rows4x8", "rows1x128"])
@pytest.mark.parametrize("step,window", [("decode", 1), ("verify", 1), ("verify", 4)])
def test_step_writes_only_its_rows(step, window, hidden):
    """decode_step / verify_step hand back the cache they were given
    with ONLY the step's (layer, block, offset) rows and scratch block 0
    changed — every other element bit-identical, whatever noise sat
    there — and their logits are forward_full's."""
    from flexflow_tpu.generation.decoder import verify_step

    cfg = _cfg3(hidden)
    params = init_decoder_params(jax.random.key(1), cfg)
    rs = np.random.RandomState(40 + window)
    n, nb = 11, 12  # prompt length (mid-block), cache blocks
    seq = rs.randint(0, cfg.vocab_size, n + window).tolist()
    cc = CacheConfig(num_layers=3, num_heads=4, head_dim=hidden // 4, num_blocks=nb, block_size=BLOCK)
    assert cc.row_shape == ((4, 8) if hidden == 32 else (1, 128))
    shape = (3, nb, BLOCK, *cc.row_shape)
    ck = jnp.asarray(rs.randn(*shape), jnp.float32)  # noise everywhere
    cv = jnp.asarray(rs.randn(*shape), jnp.float32)
    blocks = [5, 2, 9]  # covers positions 0..23
    table = jnp.asarray(blocks, jnp.int32)
    _, ks, vs = prefill(params, jnp.asarray([seq[:n]], jnp.int32), jnp.asarray([n], jnp.int32))
    block, offset = slot_mapping(table, jnp.arange(n, dtype=jnp.int32), BLOCK)
    ck = ck.at[:, block, offset].set(ks[:, 0].reshape(3, n, *cc.row_shape))
    cv = cv.at[:, block, offset].set(vs[:, 0].reshape(3, n, *cc.row_shape))

    # slot 0 runs the window at positions n..n+window-1; slot 1 is inactive
    tables = jnp.asarray([blocks, [0, 0, 0]], jnp.int32)
    if step == "decode":
        logits, nk, nv = decode_step(
            params, jnp.asarray([seq[n], 0], jnp.int32), jnp.asarray([n, 0], jnp.int32),
            ck, cv, tables, jnp.asarray([n + 1, 0], jnp.int32), backend="cpu",
        )
        logits = logits[:, None]
    else:
        positions = np.full((2, window), -1, np.int32)
        positions[0] = np.arange(n, n + window)
        tokens = np.zeros((2, window), np.int32)
        tokens[0] = seq[n:]
        logits, nk, nv = verify_step(
            params, jnp.asarray(tokens), jnp.asarray(positions), ck, cv, tables, backend="cpu",
        )
    full = forward_full(params, jnp.asarray([seq], jnp.int32))
    np.testing.assert_allclose(
        np.asarray(logits[0]), np.asarray(full[0, n:n + window]), atol=1e-5,
        err_msg=f"{step} W={window} logits != forward_full",
    )
    step_rows = {
        (li, blocks[p // BLOCK], p % BLOCK)
        for li in range(3) for p in range(n, n + window)
    }
    for before, after in ((ck, nk), (cv, nv)):
        diff = np.any(np.asarray(before) != np.asarray(after), axis=(-1, -2))  # [L, nb, bs]
        changed = {tuple(int(i) for i in idx) for idx in np.argwhere(diff)}
        assert step_rows <= changed, "a step row was not written"
        stray = {c for c in changed - step_rows if c[1] != 0}
        assert not stray, f"{step} W={window} wrote outside its rows and scratch: {sorted(stray)}"


_MOVES = ("copy", "slice", "dynamic-slice", "dynamic-update-slice")


def _layer_sized_moves(hlo_text, layer_elems):
    """(op, shape) of every instruction of the optimized HLO, fused
    computations included, that copies or cuts a layer's worth of
    elements or more."""
    import re

    found = []
    for m in re.finditer(r"= \w+\[([\d,]*)\](?:\{[^}]*\})? ([\w\-]+)\(", hlo_text):
        elems = int(np.prod([int(d) for d in m.group(1).split(",") if d]))
        if m.group(2) in _MOVES and elems >= layer_elems:
            found.append((m.group(2), m.group(1)))
    return found


def _step_arguments(eng, kind, cache, put):
    """Arguments of the engine's decode / verify jit: ``cache`` for both
    cache arrays, zeros through ``put(dtype, *shape)`` for the rest."""
    b, mb, v, w = eng.max_batch_slots, eng.max_blocks_per_seq, eng.cfg.vocab_size, eng.spec_window
    i32, f32, u32 = np.int32, np.float32, np.uint32
    tail = (put(f32, b), put(i32, b), put(f32, b), put(u32, b), put(i32, b))
    if kind == "decode":
        return (put(i32, b), put(i32, b), cache, cache, put(i32, b, mb), put(i32, b),
                *tail, put(f32, b, v))
    return (put(i32, b, w), put(i32, b), put(i32, b), cache, cache, put(i32, b, mb),
            *tail, put(f32, b, w, v))


@pytest.mark.parametrize("kind", ["decode", "verify"])
def test_engine_step_programs_hold_no_layer_sized_copy(kind):
    """The engine's OWN decode and verify jits (both cache arrays
    donated), as the CPU compiler optimizes them: no copy, slice,
    dynamic-slice or dynamic-update-slice as large as one layer of the
    cache — the rows are scattered into the aliased arrays and nothing
    else touches them."""
    cfg = _cfg3(128)
    params = init_decoder_params(jax.random.key(1), cfg)
    eng = GenerationEngine(
        params, cfg, max_batch_slots=3, block_size=BLOCK, prompt_buckets=BUCKETS,
        donate_cache=True,
    )
    jit = eng._decode_jit if kind == "decode" else eng._verify_jit
    args = _step_arguments(eng, kind, eng.cache.k, lambda dt, *s: np.zeros(s, dt))
    compiled = jit.lower(eng.params, *args).compile()
    layer = int(np.prod(eng.cache.k.shape[1:]))
    assert _layer_sized_moves(compiled.as_text(), layer) == []
    cache_bytes = 2 * eng.cache.k.size * 4
    assert compiled.memory_analysis().alias_size_in_bytes >= cache_bytes


@pytest.fixture(scope="module")
def one_v5e_chip():
    """A described (not attached) v5e chip, for compiles only."""
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no libtpu here, or its lock is held elsewhere
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _compile_uncached(jit, *args):
    """A deviceless compile cannot be read back from the persistent
    compile cache: keep it out of one."""
    cache_was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        return jit.lower(*args).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_was)


@pytest.mark.parametrize("kind", ["decode", "verify"])
def test_engine_step_programs_on_the_v5e_hold_no_cache_sized_temporary(
    kind, one_v5e_chip, monkeypatch
):
    """The same two jits compiled by the TPU's own compiler at the
    serving cells' shapes (24 layers, 8 slots, 514 blocks of 16, 16 x 64
    heads, float32), with the layouts the compiler picks by default: the
    Mosaic kernel is in every layer, both cache arrays are aliased at
    their nominal, unpadded size, no layer-sized copy or slice is left
    and the program's temporaries stay under 0.5 GiB (4.67 GiB before
    ISSUE 24, beside four relayouts of the cache a step); and the
    computation every step runs holds no sort of the vocabulary and no
    reverse: those sit in the branch of a conditional, which only a
    batch that samples with ``top_k`` enters (ISSUE 28).
    Nothing runs: shapes in, a compiled program out."""
    import flexflow_tpu.ops.attention as attention

    monkeypatch.setattr(attention, "on_tpu", lambda: True)  # the chip's dispatch
    cfg = TransformerConfig(
        num_layers=24, hidden_size=1024, num_heads=16, ff_size=4096,
        seq_length=1024, vocab_size=50257, causal=True,
    )
    sds = lambda dt, *s: jax.ShapeDtypeStruct(s, dt, sharding=one_v5e_chip)
    shapes = jax.eval_shape(lambda k: init_decoder_params(k, cfg), jax.random.key(0))
    params = jax.tree.map(lambda a: sds(a.dtype, *a.shape), shapes)
    # the engine's own cache is a stand-in of two blocks: the programs
    # take their shapes from their arguments
    cc = CacheConfig(num_layers=24, num_heads=16, head_dim=64, num_blocks=2)
    eng = GenerationEngine(
        shapes, cfg, max_batch_slots=8, block_size=16, prompt_buckets=[1024], max_seq_len=1024,
        cache_config=cc, donate_cache=True,
    )
    eng.backend = "tpu"
    cache = sds(np.float32, 24, 514, 16, *cc.row_shape)
    jit = eng._decode_jit if kind == "decode" else eng._verify_jit
    compiled = _compile_uncached(jit, params, *_step_arguments(eng, kind, cache, sds))
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 24
    assert _layer_sized_moves(text, 514 * 16 * 16 * 64) == []
    m = compiled.memory_analysis()
    nominal = 2 * 24 * 514 * 16 * 16 * 64 * 4
    assert m.alias_size_in_bytes == nominal, m
    assert m.temp_size_in_bytes < 0.5 * 2**30, m
    entry = text[text.index("\nENTRY "):]
    sorts = (" sort(", " reverse(")
    assert " conditional(" in entry
    assert [s for s in sorts if s in entry] == []
    assert [s for s in sorts if s in text] == list(sorts)  # in the branches


@pytest.mark.parametrize("kv_shards", [1, 4])
def test_stored_cache_shape_is_row_major_and_unpadded_on_the_v5e(kv_shards, one_v5e_chip):
    """What the whole arrangement rests on: the layout the TPU compiler
    gives the stored shape BY DEFAULT is row-major (the paged kernel
    DMAs blocks out of it as it is) and holds no padding — for the
    cells' cache and for one shard of a four-way head-sharded one."""
    cc = CacheConfig(num_layers=24, num_heads=16, head_dim=64, num_blocks=514, kv_shards=kv_shards)
    rows, lanes = cc.row_shape
    shard = (24, 514, 16, rows // kv_shards, lanes)
    x = jax.ShapeDtypeStruct(shard, np.float32, sharding=one_v5e_chip)
    compiled = _compile_uncached(jax.jit(lambda a: a[0, 0, 0]), x)
    (fmt,), _ = compiled.input_formats
    assert fmt.layout.major_to_minor == (0, 1, 2, 3, 4), fmt
    assert compiled.memory_analysis().argument_size_in_bytes == int(np.prod(shard)) * 4


@pytest.mark.parametrize("seq, heads, kv_heads, score, value, window", [
    (3072, 64, 64, 192, 128, 0), (4096, 64, 64, 192, 128, 0),        # agent-turns: a latent layer's expanded form, head-major
    (5120, 128, 8, 128, 128, 4096), (6144, 128, 8, 128, 128, 0),    # long-doc: group 16, a window layer and a full one
])
def test_the_streamed_prefill_call_compiles_for_the_v5e_at_the_cells_buckets(seq, heads, kv_heads, score, value, window, one_v5e_chip, monkeypatch):
    """``prefill_attention`` at the two streaming cells' prefill calls
    (bfloat16), compiled by the TPU's own compiler: the rule names the
    Pallas call, Mosaic takes it (a contraction over 192, a block of
    positions of one head, K at 192 beside V at 128 in fast memory), the
    result has the value's width, and no ``[heads, S, S]`` float32 exists:
    the temporaries hold the head-major copies alone."""
    import flexflow_tpu.ops.attention as attention

    sds = lambda dt, *s: jax.ShapeDtypeStruct(s, dt, sharding=one_v5e_chip)  # noqa: E731
    q, k, v = (sds(jnp.bfloat16, 1, seq, h, w) for h, w in ((heads, score), (kv_heads, score), (kv_heads, value)))
    monkeypatch.setattr(attention, "on_tpu", lambda: True)  # the chip's dispatch
    call = jax.jit(lambda q, k, v, n: attention.prefill_attention(q, k, v, n, window=window, backend="tpu"))
    text = (compiled := _compile_uncached(call, q, k, v, sds(jnp.int32, 1))).as_text()
    assert text.count("tpu_custom_call") == 1 and "prefill_stream_attention" in text and f"bf16[1,{seq},{heads},{value}]" in text
    moved = 2 * seq * (heads * (score + value) + kv_heads * (score + value))  # q, the result, K and V, once each
    assert compiled.memory_analysis().temp_size_in_bytes <= moved < 4 * heads * seq * seq / 8


@pytest.mark.parametrize("heads, kv_heads, head_dim, block, slots, columns, window, w, splits", [
    (32, 8, 64, 16, 64, 64, 0, 1, 1),          # gen-batch: LFM2's decode call, 32 columns of 16 positions a step
    (32, 4, 128, 64, 48, 48, 0, 1, 1),         # code-gen: Mellum2's full layers, 8 columns a step
    (32, 4, 128, 64, 48, 17, 1024, 1, 1),      # ... and its window layers: 17 columns in steps of 6
    (32, 4, 128, 64, 48, 48, 0, 5, 1),         # ... a verify window of 5: 160 query rows
    (128, 8, 128, 64, 16, 128, 0, 1, 1),       # long-doc: Command A+'s full layer at group 16, 4 columns a step
    (128, 8, 128, 64, 16, 65, 4096, 1, 1),     # ... and its window layers: 65 columns
    (32, 4, 128, 64, 2, 48, 0, 1, 4),          # two slots: the decode call's own rule splits the table
])
def test_the_grouped_paged_call_compiles_for_the_v5e_at_the_cells_shapes(
    heads, kv_heads, head_dim, block, slots, columns, window, w, splits, one_v5e_chip
):
    """``paged_append_attention`` at the three grouped cells' calls
    (bfloat16), compiled by the TPU's own compiler: Mosaic takes the walk
    (copies of its own out of the whole cache, a step's columns as one
    matrix, a loop as long as the context), ONE custom call under the
    name the trace readers look for, and neither cache is copied,
    sliced or converted on the way in (the temporaries are the query
    rows' and the partials')."""
    from flexflow_tpu.ops.kernels.decode_attention import cache_row_shape, paged_append_attention

    sds = lambda dt, *s: jax.ShapeDtypeStruct(s, dt, sharding=one_v5e_chip)  # noqa: E731
    cache = sds(jnp.bfloat16, 3, slots * columns + 1, block, *cache_row_shape(kv_heads, head_dim))
    args = (sds(jnp.bfloat16, slots, w, heads, head_dim), cache, cache, sds(jnp.int32, slots, columns),
            sds(jnp.int32, slots, w), sds(jnp.int32, slots))

    def call(q, k, v, tables, positions, first):
        bounds = {"window": window, "first_positions": first} if window else {}
        return paged_append_attention(q, k, v, 2, tables, positions, kv_splits=splits, **bounds)

    text = (compiled := _compile_uncached(jax.jit(call), *args)).as_text()
    name = ("paged_window_attention" if window else "paged_append_attention") + ("_split" if splits > 1 else "")
    assert text.count("tpu_custom_call") == 1 and f"{name}" in text
    assert compiled.memory_analysis().temp_size_in_bytes < 16 * slots * w * heads * head_dim * max(splits, 2)


@pytest.mark.parametrize("hidden, width, held, experts, k, heads", [(2304, 896, 64, 64, 8, 32), (2048, 768, 16, 256, 8, 64)])
@pytest.mark.parametrize("rows", [1536, 2048])
def test_the_grouped_expert_layer_compiles_for_the_v5e_at_the_cells_buckets(rows, hidden, width, held, experts, k, heads, one_v5e_chip):
    """One expert layer of Mellum2 (64 of 64 experts) and of JoyAI (16 of
    256 held) over the rows of ``prefill[1536]`` and ``prefill[2048]``,
    compiled by the TPU's own compiler in the grouped form
    (ops/expert_product.py): three Mosaic calls (one grouped product a
    weight matrix), no dense product left, and temporaries under
    the float32 scores of the same prefill's attention (heads x rows x
    rows), which set the program's peak; where every expert is held,
    under the dense form's too (16 held of 256 leave the dense form
    little to hold, and the grouped one still has a place for every one
    of the rows x k pairs). The compiler has refused this program for want of fast
    memory at SOME row counts (its own gather of 12,288 rows of 2,304
    among them; a product's tile at LFM2's 2,048 x 1,792): a compile here
    is what says a bucket is safe. Nothing runs."""
    from flexflow_tpu.ops import expert_product

    sds = lambda dt, *s: jax.ShapeDtypeStruct(s, dt, sharding=one_v5e_chip)  # noqa: E731
    stack = (None if held == experts else tuple(range(held)))
    args = (sds(jnp.bfloat16, rows, hidden), sds(np.float32, rows, experts), sds(np.int32, rows, k),
            sds(jnp.bfloat16, held, hidden, width), sds(jnp.bfloat16, held, hidden, width), sds(jnp.bfloat16, held, width, hidden),
            sds(np.bool_, rows))

    def grouped(v, gates, chosen, w1, w3, w2, live):
        return expert_product.grouped_expert_sum(v, gates, chosen, w1, w3, w2, held=stack, live=live)

    def dense(v, gates, chosen, w1, w3, w2, live):
        mine = gates if stack is None else gates[:, jnp.asarray(stack)]
        up, gate_up = (jnp.einsum("te,nef->ntf", v, w, preferred_element_type=jnp.float32) for w in (w1, w3))
        hidden_ = (jax.nn.silu(up) * gate_up * mine.T[:, :, None]).astype(v.dtype)
        return jnp.einsum("ntf,nfe->te", hidden_, w2, preferred_element_type=jnp.float32).astype(v.dtype)

    compiled = _compile_uncached(jax.jit(grouped), *args)
    assert compiled.as_text().count("tpu_custom_call") == 3
    temporaries = compiled.memory_analysis().temp_size_in_bytes
    assert temporaries < heads * rows * rows * 4
    if held == experts:
        assert temporaries < _compile_uncached(jax.jit(dense), *args).memory_analysis().temp_size_in_bytes


@pytest.mark.parametrize("tail", [False, True], ids=["fused", "composed"])
def test_the_train_steps_vocabulary_chain_on_the_v5e(tail, one_v5e_chip):
    """The trainer's step over a small ``build_transformer`` in bfloat16
    (this file holds every compile for a described chip), compiled by the
    TPU's own compiler: with the loss taken from the softmax's input
    (``loss_form`` ``fused_softmax_ce``) the program scatters nothing
    into the logits' shape and its entry computation holds NO float32
    ``[tokens, vocabulary]`` result: the log-sum-exp and the gradient
    stay inside fusions. The composed chain (forced by an identity that
    reads the softmax) scatters one value a row into such an array and
    writes the float32 log-probabilities out. Nothing runs."""
    import re

    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    from flexflow_tpu import AdamOptimizer, DataType, FFConfig, LossType
    from flexflow_tpu.models import build_transformer
    from flexflow_tpu.parallel.strategy import data_parallel_strategy

    b, s, v = 4, 128, 250
    cfg = TransformerConfig(num_layers=1, hidden_size=128, num_heads=2, ff_size=256, seq_length=s, vocab_size=v,
                            dtype=DataType.BFLOAT16)
    model = build_transformer(FFConfig(batch_size=b), cfg)
    if tail:
        model.identity(model.get_output(), name="tail")
    model.compile(optimizer=AdamOptimizer(alpha=1e-4), loss_type=LossType.SPARSE_CATEGORICAL_CROSSENTROPY,
                  strategy=data_parallel_strategy(model.graph, 1))
    ex = model.executor
    assert ex.loss_form == ("composed" if tail else "fused_softmax_ce")
    (chip,) = one_v5e_chip.device_set
    ex.mesh, ex.backend = Mesh(np.array([chip]).reshape(model.mesh.devices.shape), model.mesh.axis_names), "tpu"
    on_chip = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=NamedSharding(ex.mesh, PartitionSpec()))  # noqa: E731
    params, opt, state = (jax.tree.map(on_chip, t) for t in (ex.params, ex.opt_state, ex.state))
    tokens = on_chip(jax.ShapeDtypeStruct((b, s), jnp.int32))
    lowered = jax.jit(ex._train_step_fn).lower(params, opt, state, (tokens,), tokens, on_chip(jax.eval_shape(lambda: jax.random.key(0))))
    scattered = re.findall(r"stablehlo\.scatter.*?-> tensor<([^>]*)>", lowered.as_text(), flags=re.S)
    cache_was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        text = lowered.compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_was)
    entry = text[text.index("\nENTRY "):]
    float32_logits = re.findall(rf"^\s+(?:ROOT )?%?[\w.\-]+ = f32\[{b},{s},{v}\]", entry[: entry.index("\n}")], flags=re.M)
    if tail:
        assert f"{b}x{s}x{v}xf32" in scattered and float32_logits
    else:
        assert f"{b}x{s}x{v}xf32" not in scattered and float32_logits == []


def test_packed_cache_through_every_engine_program():
    """An engine whose heads share cache rows (four heads of 32 in one
    128-lane row) against the stateless forward: prefill, decode, a
    suffix prefill on cached blocks, a COW copy, speculative verify —
    and the block programs, which speak the LOGICAL [L, bs, H, D] shape
    off the device (host tier, wire) and round-trip bit for bit."""
    from flexflow_tpu.generation.speculative import SpeculationConfig

    cfg = TransformerConfig(
        num_layers=2, hidden_size=128, num_heads=4, ff_size=64,
        seq_length=64, vocab_size=50, causal=True,
    )
    params = init_decoder_params(jax.random.key(0), cfg)
    eng = GenerationEngine(
        params, cfg, max_batch_slots=3, block_size=BLOCK, prompt_buckets=BUCKETS,
    )
    assert eng.cache.k.shape[3:] == (1, 128)
    prompt = list(range(1, 21))  # 20 tokens: two full blocks and a half
    out = eng.generate([prompt], SamplingParams(max_new_tokens=4))  # prefill, decode
    assert out == [naive_greedy(params, prompt, 4)]
    for other in (prompt[:16] + [7, 8, 9], prompt[:16]):  # suffix prefill; COW copy
        got = eng.generate([other], SamplingParams(max_new_tokens=3))
        assert got == [naive_greedy(params, other, 3)]
    assert eng.prefix_cache.hits >= 2 and eng.prefix_cache.cow_copies_total >= 1
    rep = [3, 4] * 6
    got = eng.generate([rep], SamplingParams(max_new_tokens=6), speculation=SpeculationConfig(k=4))
    assert got == [naive_greedy(params, rep, 6)] and eng.step_counts["verify"] >= 1

    blk = eng.allocator.allocate(3)
    rs = np.random.RandomState(5)
    hk, hv = (rs.randn(2, BLOCK, 4, 32).astype(np.float32) for _ in range(2))
    eng.import_kv_block(blk[0], hk, hv)
    assert eng.cache.k.shape[3:] == (1, 128)
    rk, rv = eng._read_block_jit(eng.cache.k, eng.cache.v, jnp.int32(blk[0]))
    assert rk.shape == (2, BLOCK, 4, 32)
    assert np.array_equal(np.asarray(rk), hk) and np.array_equal(np.asarray(rv), hv)
    # head h of the block sits on lanes [32 h, 32 h + 32) of its row
    assert np.array_equal(np.asarray(eng.cache.k[1, blk[0], 3, 0, 64:96]), hk[1, 3, 2])
    payload = eng.pack_kv_blocks([blk[0]], BLOCK)  # the wire: logical too
    assert np.array_equal(payload.blocks[0].host_k, hk)
    eng.import_kv_blocks(blk[1:2], payload.blocks)
    rk2, _ = eng._read_block_jit(eng.cache.k, eng.cache.v, jnp.int32(blk[1]))
    assert np.array_equal(np.asarray(rk2), hk)
    eng.allocator.free(blk)


# ---------------------------------------------------------------------------
# recompilation discipline
# ---------------------------------------------------------------------------


def test_steady_state_decode_never_recompiles(decoder_params):
    eng = make_engine(decoder_params, num_blocks=30, slots=3)
    prompts = [[1, 2, 3], list(range(10)), [7] * 17, [4, 5], list(range(30))]
    eng.generate(prompts, SamplingParams(max_new_tokens=6))
    assert eng.trace_counts.get("decode") == 1, eng.trace_counts
    assert eng.recompiles() == {}, eng.trace_counts
    # a second wave of different lengths/batch compositions: still no
    # new traces for warm buckets
    eng.generate([[9] * 5, [8] * 12], SamplingParams(max_new_tokens=3))
    assert eng.trace_counts.get("decode") == 1, eng.trace_counts
    assert eng.recompiles() == {}, eng.trace_counts


# ---------------------------------------------------------------------------
# continuous-batching scheduler properties (virtual clock, manual step)
# ---------------------------------------------------------------------------


def test_scheduler_join_mid_flight(decoder_params):
    """A request submitted while another is decoding joins the running
    batch at the next step, not at a batch boundary — and both outputs
    match solo runs."""
    eng = make_engine(decoder_params, num_blocks=30, slots=3)
    solo_a = naive_greedy(decoder_params, [1, 2, 3], 8)
    solo_b = naive_greedy(decoder_params, [9, 8, 7, 6], 4)
    sched = ContinuousBatchingScheduler(eng, clock=FakeClock())
    ha = sched.submit([1, 2, 3], SamplingParams(max_new_tokens=8))
    for _ in range(3):
        sched.step()
    a_progress = len(ha._request.generated)
    assert 0 < a_progress < 8
    hb = sched.submit([9, 8, 7, 6], SamplingParams(max_new_tokens=4))
    sched.step()  # B admitted mid-flight...
    assert len(hb._request.generated) >= 1  # ...and already producing
    assert not ha.done()
    for _ in range(20):
        if ha.done() and hb.done():
            break
        sched.step()
    assert ha.result(0) == solo_a
    assert hb.result(0) == solo_b


def test_scheduler_free_on_finish(decoder_params):
    """Blocks return to the allocator the step a sequence finishes."""
    eng = make_engine(decoder_params, num_blocks=30, slots=2)
    sched = ContinuousBatchingScheduler(eng, clock=FakeClock())
    free0 = eng.allocator.num_free
    h = sched.submit([1, 2, 3, 4, 5], SamplingParams(max_new_tokens=3))
    sched.step()
    assert eng.allocator.num_free < free0
    for _ in range(10):
        if h.done():
            break
        sched.step()
    assert h.done()
    assert eng.allocator.num_free == free0


def test_scheduler_preempt_on_full_recomputes_exactly(decoder_params):
    """Cache exhaustion preempts the youngest sequence by recompute;
    sampled token streams continue exactly where they left off."""
    sp1 = SamplingParams(max_new_tokens=10, temperature=0.8, top_k=10, seed=42)
    sp2 = SamplingParams(max_new_tokens=10, temperature=0.7, top_k=8, seed=7)
    big = make_engine(decoder_params, num_blocks=40)
    ref1 = big.generate([[1, 2, 3, 4, 5]], sp1)[0]
    ref2 = big.generate([[9, 8, 7]], sp2)[0]

    small = make_engine(decoder_params, num_blocks=4)  # 24 usable positions
    sched = ContinuousBatchingScheduler(small, clock=FakeClock())
    h1 = sched.submit([1, 2, 3, 4, 5], sp1)
    h2 = sched.submit([9, 8, 7], sp2)
    for _ in range(200):
        if h1.done() and h2.done():
            break
        sched.step()
    assert sched.preemptions > 0
    assert h1.result(0) == ref1
    assert h2.result(0) == ref2
    # blocks not free after drain are exactly the prefix index's warm
    # cache (preempt-stashed content kept for reuse), never a leak
    used = small.allocator.num_total - small.allocator.num_free
    assert used == small.prefix_cache.resident_blocks


def test_scheduler_deadline_and_queue_bounds(decoder_params):
    eng = make_engine(decoder_params, num_blocks=30, slots=1)
    clock = FakeClock()
    sched = ContinuousBatchingScheduler(eng, clock=clock, max_queue=2)
    with pytest.raises(DeadlineExceededError):
        sched.submit([1, 2], SamplingParams(), deadline_s=0)
    h = sched.submit([1, 2], SamplingParams(max_new_tokens=50), deadline_s=5.0)
    sched.submit([3, 4], SamplingParams())
    with pytest.raises(QueueFullError):  # bound counts WAITING requests
        sched.submit([5, 6], SamplingParams())
    sched.step()
    assert not h.done()
    clock.advance(10.0)  # h expires mid-generation, queued ones still live
    sched.step()
    with pytest.raises(DeadlineExceededError):
        h.result(0)
    assert eng.allocator.num_free == eng.allocator.num_total - 1  # only the running seq holds blocks
    assert sched.stats.get("expired") == 2


def test_scheduler_chaos_transient_retry_and_poison(decoder_params):
    """A transient decode fault is retried invisibly; a hard fault fails
    the affected requests and trips the breaker toward OPEN."""
    eng = make_engine(decoder_params, num_blocks=30, slots=2)
    clock = FakeClock()
    retry = RetryPolicy(max_attempts=3, sleep=lambda _s: None)
    breaker = CircuitBreaker(failure_threshold=2, recovery_s=30.0, clock=clock)
    sched = ContinuousBatchingScheduler(eng, clock=clock, retry=retry, breaker=breaker)
    ref = naive_greedy(decoder_params, [1, 2, 3], 4)

    plan = FaultPlan(seed=0)
    plan.on("generation.decode_step", mode="error", error=TransientDeviceError, nth=(1,))
    with plan.active():
        h = sched.submit([1, 2, 3], SamplingParams(max_new_tokens=4))
        for _ in range(10):
            if h.done():
                break
            sched.step()
    assert h.result(0) == ref  # retry made the fault invisible
    assert plan.fired("generation.decode_step") == 1

    plan = FaultPlan(seed=0)
    plan.on("generation.prefill", mode="error", error=FaultInjected, nth=(0, 1))
    with plan.active():
        h1 = sched.submit([4, 5], SamplingParams(max_new_tokens=2))
        h2 = sched.submit([6, 7], SamplingParams(max_new_tokens=2))
        for _ in range(5):
            sched.step()
    with pytest.raises(FaultInjected):
        h1.result(0)
    with pytest.raises(FaultInjected):
        h2.result(0)
    assert breaker.state == CircuitBreaker.OPEN  # 2 consecutive failures
    with pytest.raises(CircuitOpenError):
        sched.submit([1], SamplingParams())
    assert eng.allocator.num_free == eng.allocator.num_total


# ---------------------------------------------------------------------------
# serving surface
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def gen_server(decoder_params):
    from flexflow_tpu.serving import InferenceServer
    from flexflow_tpu.serving.generation import GenerationModel

    eng = GenerationEngine(
        decoder_params, CFG, max_batch_slots=2, block_size=BLOCK, prompt_buckets=BUCKETS
    )
    srv = InferenceServer(port=0)
    srv.register_generation(GenerationModel(eng, name="lm"))
    srv.start()
    yield srv
    srv.stop()


def _post(url, payload):
    req = urllib.request.Request(
        url, data=json.dumps(payload).encode(), headers={"Content-Type": "application/json"}
    )
    return urllib.request.urlopen(req, timeout=60)


def test_http_generate_json(gen_server, decoder_params):
    base = f"http://127.0.0.1:{gen_server.port}"
    resp = json.load(_post(f"{base}/v2/models/lm/generate", {"prompt": [1, 2, 3], "max_new_tokens": 5}))
    assert resp["tokens"] == naive_greedy(decoder_params, [1, 2, 3], 5)
    assert resp["num_generated"] == 5


def test_http_generate_sse_stream(gen_server, decoder_params):
    base = f"http://127.0.0.1:{gen_server.port}"
    r = _post(f"{base}/v2/models/lm/generate", {"prompt": [4, 5], "max_new_tokens": 4, "stream": True})
    assert r.headers["Content-Type"] == "text/event-stream"
    # each SSE chunk is an `id: N` line (durable resume cursor) + a data line
    events = [json.loads(l.split("data: ", 1)[1])
              for l in r.read().decode().strip().split("\n\n")]
    ref = naive_greedy(decoder_params, [4, 5], 4)
    assert [e["token"] for e in events[:-1]] == ref
    # the done event carries the journey id so clients can fetch the stitched trace
    jid = events[-1].pop("journey_id")
    assert len(jid) == 32 and all(c in "0123456789abcdef" for c in jid)
    assert events[-1] == {"done": True, "tokens": ref}


def test_http_stats_endpoint(gen_server):
    base = f"http://127.0.0.1:{gen_server.port}"
    stats = json.load(urllib.request.urlopen(f"{base}/v2/stats", timeout=30))
    lm = stats["generation"]["lm"]
    assert lm["completed"] >= 2
    assert lm["tokens_generated"] >= 9
    assert "tokens_per_s" in lm and "cache_occupancy" in lm
    assert lm["latency"]["count"] >= 2
    assert lm["recompiles"] == 0


def test_http_generate_bad_request(gen_server):
    base = f"http://127.0.0.1:{gen_server.port}"
    with pytest.raises(urllib.error.HTTPError) as exc:
        _post(f"{base}/v2/models/lm/generate", {"prompt": []})
    assert exc.value.code == 400
    with pytest.raises(urllib.error.HTTPError) as exc:
        _post(f"{base}/v2/models/nope/generate", {"prompt": [1]})
    assert exc.value.code == 404


def test_http_generation_model_ready(gen_server):
    base = f"http://127.0.0.1:{gen_server.port}"
    assert urllib.request.urlopen(f"{base}/v2/models/lm/ready", timeout=30).status == 200
    meta = json.load(urllib.request.urlopen(f"{base}/v2/models/lm", timeout=30))
    assert meta["platform"] == "flexflow_tpu_generation"
    assert meta["prompt_buckets"] == list(BUCKETS)


def test_batcher_stats_counters():
    """The satellite: batcher exports queue/admission/latency stats."""
    from flexflow_tpu import CompMode, FFConfig, FFModel
    from flexflow_tpu.serving import DynamicBatcher, InferenceModel

    cfg = FFConfig(batch_size=4)
    ff = FFModel(cfg)
    x = ff.create_tensor([4, 8], name="x")
    out = ff.dense(x, 2)
    ff.compile(comp_mode=CompMode.INFERENCE, outputs=[out])
    model = InferenceModel(ff, name="m", max_batch=4)
    b = DynamicBatcher(model, max_delay_s=0.001, max_queue=4)
    b.start()
    try:
        b.infer([np.zeros((2, 8), np.float32)], timeout=30)
        with pytest.raises(DeadlineExceededError):
            b.submit([np.zeros((1, 8), np.float32)], deadline_s=0)
        snap = b.stats.snapshot()
        assert snap["admitted"] == 1 and snap["completed"] == 1
        assert snap["expired"] == 1
        assert snap["latency"]["count"] == 1 and snap["latency"]["mean_s"] > 0
        assert snap["queue_depth"] == 0
    finally:
        b.stop()
