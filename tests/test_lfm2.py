"""LFM2-8B-A1B through the normal engine, at the configuration's
rehearsal widths on the CPU (hidden 64, 4 / 2 heads of 16, dense 128,
8 experts of 32 top-2, 6 layers ``conv conv attn conv conv attn`` with 2
dense, vocabulary 512), on the benchmark's own seeded weights cast to
float32, against the benchmark's plain reference
(``benchmark/reference/lfm2.py``).

Tolerance 1e-5: program and reference compute the same float32
equations in a different order (fused q/k/v einsums against separate
ones, all experts at once against one at a time, a gathered cache
against the whole sequence), so they differ by float32 rounding of sums
of a few hundred terms of magnitude ~1: some 1e-6. (On the chip the
model is bfloat16 and the comparison is the cell's ``gap_ratio``.)
"""
import json
import pathlib
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from benchmark import spec  # noqa: E402
from benchmark.reference import lfm2  # noqa: E402
from flexflow_tpu.generation import (  # noqa: E402
    ContinuousBatchingScheduler,
    GenerationEngine,
    SamplingParams,
    forward_full,
    init_decoder_params,
)
from flexflow_tpu.generation import decoder  # noqa: E402
from flexflow_tpu.generation.speculative import SpeculationConfig  # noqa: E402
from flexflow_tpu.models.transformer import TransformerConfig  # noqa: E402
from flexflow_tpu.ops.kernels.decode_attention import (  # noqa: E402
    paged_append_attention,
    reference_paged_append_attention,
)

pytestmark = pytest.mark.generation

BLOCK, BUCKETS, MAX_SEQ = 8, (16, 32, 64), 64


@pytest.fixture(scope="module")
def model():
    body = json.loads((ROOT / "benchmark/configs/lfm2-8b-a1b.json").read_text())
    config = spec._merge(body, body["rehearsal"])
    params = lfm2.cast_params(lfm2.init_params(11, config), jnp.float32)
    return config, lfm2.engine_config(config, MAX_SEQ), params


def make_engine(model, slots=3, **kw):
    _, cfg, params = model
    kw.setdefault("block_size", BLOCK)
    kw.setdefault("prompt_buckets", BUCKETS)
    return GenerationEngine(params, cfg, max_batch_slots=slots, max_seq_len=MAX_SEQ, **kw)


def tokens_of(seed, n):
    return [int(t) for t in np.random.RandomState(seed).randint(0, 512, size=n)]


def reference_greedy(model, prompt, n_new):
    """Greedy continuation by the plain reference, one whole forward a
    token, and the logits that chose each token."""
    config, _, params = model
    seq, logits = list(prompt), []
    for _ in range(n_new):
        row = lfm2.logits_at(params, jnp.asarray([seq], jnp.int32), jnp.asarray([[len(seq) - 1]]), config)[0, 0]
        logits.append(np.asarray(row))
        seq.append(int(np.argmax(logits[-1])))
    return seq[len(prompt):], logits


# (a) ---------------------------------------------------------------------
def test_forward_full_is_the_reference(model):
    config, cfg, params = model
    toks = jnp.asarray([tokens_of(0, 40), tokens_of(1, 40)], jnp.int32)
    at = jnp.tile(jnp.arange(40)[None], (2, 1))
    ref = lfm2.logits_at(params, toks, at, config)
    got = forward_full(params, toks, cfg=cfg)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=1e-5)
    assert float(jnp.abs(ref).max()) > 0.5  # not a comparison of zeros


# (b) ---------------------------------------------------------------------
@pytest.mark.parametrize("n", [1, 2, 3, 16, 19])
def test_prefill_then_decode_through_the_cache_is_the_full_forward(model, n):
    """Prompts of length 1, 2, 3 (shorter than the convolution's reach),
    a bucket boundary and a padded bucket: the state is handed from
    prefill to decode at the sequence's own length."""
    eng = make_engine(model)
    prompt = tokens_of(n, n)
    want, _ = reference_greedy(model, prompt, 5)
    assert eng.generate([prompt], SamplingParams(max_new_tokens=5)) == [want]


def test_decode_step_logits_are_the_full_forward_s(model):
    """Not only the argmax: the logits of a decode step against the
    cache, with two slots at different lengths and one inactive."""
    _, cfg, params = model
    eng = make_engine(model, prefix_cache=False)
    prompts = [tokens_of(21, 5), tokens_of(22, 17)]
    tables = np.zeros((3, eng.max_blocks_per_seq), np.int32)
    nxt = []
    for slot, p in enumerate(prompts):
        blocks = eng.allocator.allocate(eng.cache_config.blocks_for(len(p) + 1))
        tables[slot, : len(blocks)] = blocks
        nxt.append(eng.prefill_one(p, blocks, SamplingParams(), jax.random.key(0), slot=slot))
    positions = np.asarray([5, 17, 0], np.int32)
    out = decoder.decode_step(
        params, jnp.asarray(nxt + [0], jnp.int32), jnp.asarray(positions), eng.cache.k, eng.cache.v,
        jnp.asarray(tables), jnp.asarray([6, 18, 0], jnp.int32), cfg=cfg, conv=eng.cache.state["conv"],
    )
    for slot, p in enumerate(prompts):
        full = forward_full(params, jnp.asarray([p + [nxt[slot]]], jnp.int32), cfg=cfg)[0, -1]
        np.testing.assert_allclose(np.asarray(out[0][slot]), np.asarray(full), atol=1e-5)
    # the inactive slot's state is left as it was
    np.testing.assert_array_equal(np.asarray(out[3][:, 2]), np.asarray(eng.cache.state["conv"][:, 2]))


# (c) ---------------------------------------------------------------------
def test_a_prefix_hit_restores_the_convolution_state(model):
    samp = SamplingParams(max_new_tokens=6)
    shared = tokens_of(31, 24)  # three whole blocks
    a, b = shared + tokens_of(32, 5), shared + tokens_of(33, 7)
    cold = make_engine(model, prefix_cache=False)
    want = cold.generate([a], samp) + cold.generate([b], samp)
    eng = make_engine(model)
    assert eng.generate([a], samp) + eng.generate([b], samp) == want
    pc, cs = eng.prefix_cache, eng.conv_state_stats()
    assert pc.hits == 1 and pc.tokens_reused_total == 24
    assert cs["restores_total"] == 1 and cs["snapshots_total"] == pc.registered_total >= 3
    assert cs["bytes_per_sequence"] == 4 * 2 * 64 * 4
    # a fully covered prompt resumes from the last WHOLE block before its
    # end (a state is stored at a block's end and nowhere else): no COW
    plan = eng.prefix_plan(shared)
    assert plan.cow is None and plan.reuse_tokens == 16
    assert eng.generate([shared], samp) == cold.generate([shared], samp)


def test_the_state_goes_to_the_host_tier_and_back_with_its_block(model):
    samp = SamplingParams(max_new_tokens=4)
    shared = tokens_of(41, 16)
    want = make_engine(model, prefix_cache=False).generate([shared + [7, 8, 9]], samp)
    eng = make_engine(model)
    eng.prefix_cache.swap_overhead_s = 0.0
    eng.generate([shared + [1, 2]], samp)
    assert eng.reclaim_cached(2) == 2
    pc = eng.prefix_cache
    entries = list(pc._by_id.values())
    assert all(e.host_s is not None and e.host_s.shape == (4, 2, 64) for e in entries)
    per_block = eng.cache_config.bytes_per_block + eng.state_config.bytes_per_sequence
    assert pc.host_bytes == 2 * per_block
    eng.cache.state["snap"] = jnp.zeros_like(eng.cache.state["snap"])  # only the host holds it now
    assert eng.generate([shared + [7, 8, 9]], samp) == want
    assert pc.swaps_in_total == 2 and eng.state_restores_total == 1


def test_a_block_without_a_stored_state_is_never_matched(model):
    """Blocks filled while DECODING get no snapshot, so a preemption's
    stash registers nothing beyond what the admission's prefill wrote,
    and a later prompt that runs through generated content matches only
    the prompt's blocks."""
    eng = make_engine(model, slots=1)
    sched = ContinuousBatchingScheduler(eng)
    prompt = tokens_of(51, 9)  # one whole block + 1
    h = sched.submit(prompt, SamplingParams(max_new_tokens=20))
    while not h.done():
        state = next(iter(sched._running.values()), None)
        if state is not None and state.cached_len >= 26:  # three blocks written, two of them by decode steps
            eng.stash_prefix(state)
        sched.step()
    longer = prompt + h.result(0)
    assert len(longer) >= 26
    assert [e.depth for e in eng.prefix_cache.match(longer)] == [0]
    assert eng.prefix_plan(longer).reuse_tokens == BLOCK


# (d) ---------------------------------------------------------------------
def test_rollback_and_reset_leave_no_stale_state(model):
    eng = make_engine(model, slots=1, prefix_cache=False, donate_cache=False)
    first, second = tokens_of(61, 11), tokens_of(62, 6)
    want, _ = reference_greedy(model, second, 4)
    eng.generate([first], SamplingParams(max_new_tokens=7))  # the slot now holds `first`'s state
    assert eng.generate([second], SamplingParams(max_new_tokens=4)) == [want]  # slot reused
    # decode_async's rollback: K, V and the slots' state go back together
    blocks = eng.allocator.allocate(2)
    tok = eng.prefill_one(second, blocks, SamplingParams(), jax.random.key(0), slot=0)
    before = (eng.cache.k, eng.cache.v, eng.cache.state["conv"])
    table = np.zeros((1, eng.max_blocks_per_seq), np.int32)
    table[0, :2] = blocks
    one = lambda v, dt: np.asarray([v], dt)  # noqa: E731
    step = eng.decode_async(one(tok, np.int32), one(6, np.int32), table, one(True, bool), one(0, np.float32),
                            one(0, np.int32), one(0, np.uint32), one(1, np.int32))
    assert eng.cache.state["conv"] is not before[2] and step.prev_conv is before[2]
    eng.rollback_decode(step)
    assert (eng.cache.k, eng.cache.v, eng.cache.state["conv"]) == before
    assert int(eng.consume_decode(step)[0]) == want[1]
    eng.reset()
    assert not np.asarray(eng.cache.state["conv"]).any() and not np.asarray(eng.cache.state["snap"]).any()
    assert eng.generate([second], SamplingParams(max_new_tokens=4)) == [want]


def test_crash_replay_recomputes_the_state(model):
    from flexflow_tpu.generation import RecoveryPolicy
    from flexflow_tpu.runtime.faults import FaultPlan

    prompt = tokens_of(71, 13)
    want, _ = reference_greedy(model, prompt, 8)
    eng = make_engine(model)
    sched = ContinuousBatchingScheduler(eng, recovery=RecoveryPolicy(sleep=lambda _s: None))
    plan = FaultPlan(seed=0)
    plan.on("generation.decode_step", mode="error", error=RuntimeError("crash"), nth=(2, 3))
    with plan.active():
        h = sched.submit(prompt, SamplingParams(max_new_tokens=8))
        for _ in range(300):
            if h.done():
                break
            sched.step()
    assert h.result(timeout=0) == want and eng.resets == 1


# (e), (f) ----------------------------------------------------------------
def expert_layer(model):
    _, cfg, params = model
    layer = next(l for l in params["layers"] if "router" in l)
    rows = jnp.asarray(np.random.RandomState(5).standard_normal((24, 64)), jnp.float32)
    return cfg, layer, rows


@pytest.mark.parametrize("n_rows", [24, 3])
def test_expert_shares_add_up_to_the_whole_layer(model, n_rows):
    """The layer told it holds experts [0, 1], [2, 3], [4, 5], [6, 7]
    (8 experts over 4 chips) gives parts that add up to the whole
    layer's result (a prompt's worth of rows; fewer rows than shares)."""
    cfg, layer, rows = expert_layer(model)
    rows = rows[:n_rows]
    whole, gates = decoder.expert_ffn(cfg, layer, rows)
    parts = []
    for share in ([0, 1], [2, 3], [4, 5], [6, 7]):
        held = dict(layer, **{k: layer[k][jnp.asarray(share)] for k in ("ew1", "ew3", "ew2")})
        parts.append(decoder.expert_ffn(cfg, held, rows, held=share)[0])
    np.testing.assert_allclose(np.asarray(sum(parts)), np.asarray(whole), atol=1e-5)
    assert float(jnp.abs(whole).max()) > 1e-3 and not np.allclose(np.asarray(parts[0]), np.asarray(whole), atol=1e-4)
    # and the whole is the reference's layer
    config = model[0]
    s = dict(lfm2.sizes(config), dtype=jnp.dtype("float32"))
    with jax.default_matmul_precision("highest"):
        np.testing.assert_allclose(np.asarray(whole), np.asarray(lfm2._experts(rows, layer, s)), atol=1e-5)
    assert int((gates > 0).sum()) == n_rows * 2


def test_the_bias_moves_the_choice_and_never_the_gate(model):
    cfg, layer, rows = expert_layer(model)
    gates, chosen = decoder.route(cfg, layer, rows)
    lifted = dict(layer, router_bias=layer["router_bias"].at[3].add(10.0))
    gates_l, chosen_l = decoder.route(cfg, lifted, rows)
    assert bool((chosen_l == 3).any(axis=1).all()) and not bool((chosen == 3).any(axis=1).all())
    score = jax.nn.sigmoid(rows @ layer["router"])
    picked = jnp.take_along_axis(score, chosen_l, axis=1)
    want = picked / (picked.sum(axis=1, keepdims=True) + 1e-6)
    np.testing.assert_allclose(np.asarray(jnp.take_along_axis(gates_l, chosen_l, axis=1)), np.asarray(want), atol=1e-6)
    np.testing.assert_allclose(np.asarray(gates_l.sum(axis=1)), 1.0, atol=1e-5)  # no bias in the gate: it sums to 1
    same = np.asarray((jnp.sort(chosen, axis=1) == jnp.sort(chosen_l, axis=1)).all(axis=1))
    np.testing.assert_allclose(np.asarray(gates)[same], np.asarray(gates_l)[same], atol=1e-7)


# (g) ---------------------------------------------------------------------
@pytest.mark.parametrize("window", [1, 5])
@pytest.mark.parametrize("kv_splits", [1, 4])
@pytest.mark.parametrize("group", [1, 4])
def test_grouped_query_paged_kernel_in_interpret_mode(group, kv_splits, window):
    """Query head i reads K/V head i // group, out of cache rows that
    hold two K/V heads of 64 each (the cell's layout)."""
    rs = np.random.RandomState(group * 10 + window)
    b, hk, d, bs, nb, mb = 2, 4, 64, 8, 9, 4
    f32 = lambda *s: jnp.asarray(rs.standard_normal(s), jnp.float32)  # noqa: E731
    q = f32(b, window, hk * group, d)
    k_cache, v_cache = f32(2, nb, bs, hk * d // 128, 128), f32(2, nb, bs, hk * d // 128, 128)
    tables = jnp.asarray(rs.permutation(np.arange(1, nb))[: b * mb].reshape(b, mb), jnp.int32)
    start = np.asarray([13, 20])
    positions = start[:, None] + np.arange(window)[None, :]
    positions[1, -1] = -1 if window > 1 else positions[1, -1]  # a padding query
    positions = jnp.asarray(positions, jnp.int32)
    want = reference_paged_append_attention(q, k_cache, v_cache, 1, tables, positions)
    got = paged_append_attention(q, k_cache, v_cache, 1, tables, positions, interpret=True, kv_splits=kv_splits)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)
    # the reference itself against plain attention over the gathered K/V heads, repeated per group
    kk = jnp.repeat(k_cache[1, tables].reshape(b, mb * bs, hk, d), group, axis=2)
    vv = jnp.repeat(v_cache[1, tables].reshape(b, mb * bs, hk, d), group, axis=2)
    s = jnp.einsum("bwhd,bkhd->bhwk", q, kk) / 8.0
    s = jnp.where(jnp.arange(mb * bs)[None, None, None, :] <= positions[:, None, :, None], s, -jnp.inf)
    plain = jnp.einsum("bhwk,bkhd->bwhd", jax.nn.softmax(s, axis=-1), vv)
    live = np.asarray(positions >= 0)
    np.testing.assert_allclose(np.asarray(want)[live], np.asarray(plain)[live], atol=2e-5)
    assert not np.asarray(want)[~live].any()


# (h) ---------------------------------------------------------------------
def test_paths_that_cannot_carry_the_state_refuse_by_name(model):
    _, cfg, params = model
    with pytest.raises(NotImplementedError, match="tp_degree > 1 is refused .* convolution"):
        GenerationEngine(params, cfg, max_batch_slots=2, max_seq_len=MAX_SEQ, tp_degree=2)
    eng = make_engine(model)
    assert set(eng.unsupported) == {"speculation", "kv_handoff", "tensor_parallel"}
    z = np.zeros
    with pytest.raises(NotImplementedError, match="speculative verification .* accepted length"):
        eng.verify(z((3, 5), np.int32), z(3, np.int32), z(3, np.int32), z((3, 8), np.int32), z(3), z(3), z(3), z(3))
    with pytest.raises(NotImplementedError, match="speculative verification"):
        ContinuousBatchingScheduler(eng).submit([1, 2, 3], SamplingParams(), speculation=SpeculationConfig(k=2))
    for call in (lambda: eng.pack_kv_blocks([1], 4), lambda: eng.import_kv_block(1, None, None),
                 lambda: eng.import_kv_blocks([1], [])):
        with pytest.raises(NotImplementedError, match="disaggregation wire .* no convolution state"):
            call()
    # and nothing is refused for the configuration the engine always served
    gpt2 = TransformerConfig(num_layers=1, hidden_size=32, num_heads=4, ff_size=64, seq_length=32,
                             vocab_size=50, causal=True)
    assert GenerationEngine(init_decoder_params(jax.random.key(0), gpt2), gpt2).unsupported == {}


# (i) ---------------------------------------------------------------------
def test_expert_counters_equal_a_count_from_the_reference_s_own_routing(model):
    config, _, params = model
    eng = make_engine(model, prefix_cache=False)
    sched = ContinuousBatchingScheduler(eng)
    prompts = [tokens_of(81, 7), tokens_of(82, 18), tokens_of(83, 3)]
    outs = eng.generate(prompts, SamplingParams(max_new_tokens=6))
    stats = None
    assert "experts" in sched.stats.snapshot() and "conv_state" in sched.stats.snapshot()
    stats = eng.expert_stats()
    # the program routed every prompt position once (prefill) and every
    # generated token but the last once (decode): those sequences, whole
    want = np.asarray(lfm2.expert_tokens(params, config, [p + o[:-1] for p, o in zip(prompts, outs)]))
    np.testing.assert_array_equal(np.asarray(stats["tokens_total_by_layer"]), want)
    assert stats["tokens_total"] == want.sum(axis=0).tolist() and stats["layers"] == [2, 3, 4, 5]
    n_tokens = sum(len(p) + 5 for p in prompts)
    assert want.sum() == n_tokens * 2 * 4  # top-2 in each of 4 expert layers
    assert stats["prefill_calls_total"] == 3 and stats["decode_calls_total"] == eng.step_counts["decode"] == 5


# the GPT-2 setting of the block definition is the program it was -----------
GPT2_PROGRAMS = {  # StableHLO of the parent's programs (PR 26), 2 layers / 64 / 4 heads / 128, 4 slots
    "decode": {"dot_general": 17, "scatter": 8, "custom_call": 0},
    "prefill": {"dot_general": 17, "scatter": 8, "custom_call": 0},
    "verify": {"dot_general": 17, "scatter": 8, "custom_call": 0},
    "prefix_prefill": {"dot_general": 17, "scatter": 8, "custom_call": 0},
}
GPT2_COMPILED = {"dot": 17, "scatter": 4, "custom-call": 0}  # the CPU compiler's, every program alike


@pytest.mark.parametrize("program", sorted(GPT2_PROGRAMS))
def test_gpt2_through_the_block_definition_is_the_same_program(program):
    """gpt2-medium's cells run through the block definition this PR
    made of the decoder: its decode, ``prefill[N]``, verify and suffix
    prefill hold the dots, scatters and custom calls they held on the
    parent (counted there, pinned here), and carry no state array."""
    cfg = TransformerConfig(num_layers=2, hidden_size=64, num_heads=4, ff_size=128, seq_length=128,
                            vocab_size=512, causal=True)
    eng = GenerationEngine(init_decoder_params(jax.random.key(0), cfg), cfg, max_batch_slots=4, block_size=8,
                           prompt_buckets=[128], max_seq_len=128, donate_cache=True)
    assert eng.cache.state == {} and eng.expert_counts == {} and eng.state_config is None
    b, mb, v, ck = 4, eng.max_blocks_per_seq, 512, eng.cache.k
    z = lambda dt, *s: np.zeros(s, dt)  # noqa: E731
    i32, f32, u32 = np.int32, np.float32, np.uint32
    one = (jnp.float32(0), jnp.int32(0), jax.random.key(0), z(f32, v), {}, None, {})
    lowered = {
        "decode": lambda: eng._decode_jit.lower(
            eng.params, z(i32, b), z(i32, b), ck, ck, z(i32, b, mb), z(i32, b), z(f32, b), z(i32, b), z(f32, b),
            z(u32, b), z(i32, b), z(f32, b, v), {}, {}),
        "prefill": lambda: eng._prefill_jit.lower(eng.params, z(i32, 1, 128), jnp.int32(5), ck, ck, z(i32, mb), *one),
        "verify": lambda: eng._verify_jit.lower(
            eng.params, z(i32, b, 5), z(i32, b), z(i32, b), ck, ck, z(i32, b, mb), z(f32, b), z(i32, b), z(f32, b),
            z(u32, b), z(i32, b), z(f32, b, 5, v)),
        "prefix_prefill": lambda: eng._prefix_prefill_jit.lower(
            eng.params, z(i32, 1, 128), jnp.int32(8), jnp.int32(5), ck, ck, z(i32, mb), *one),
    }[program]()
    text = lowered.as_text()
    assert {op: len(re.findall(rf"stablehlo\.{op}\b", text)) for op in GPT2_PROGRAMS[program]} == GPT2_PROGRAMS[program]
    n_params = len(jax.tree.leaves(eng.params))
    assert len(re.findall(r"%arg\d+: tensor", text.split("{", 2)[1])) <= n_params + 12  # no state, slot or counter came in
    hlo = lowered.compile().as_text()
    assert {op: len(re.findall(rf"= [^ ]+ {op}\(", hlo)) for op in GPT2_COMPILED} == GPT2_COMPILED


def test_worst_request_ratio_by_hand():
    """Two requests of two judged tokens: the first as far from the
    reference as the stated arithmetic, the second twice as far; the
    pooled ratio reads 1.5, the worst request's 2."""
    valid = np.array([[True, True, False], [True, True, False]])
    program = {"gap": np.array([1.0, 1.0, 4.0, 0.0])}
    stated = {"gap": np.array([0.5, 1.5, 1.0, 1.0])}
    assert lfm2.gap_ratio(program, stated) == pytest.approx(1.5)
    assert lfm2.worst_request_ratio(program, stated, valid) == pytest.approx(2.0)


@pytest.mark.parametrize("k, coarser", [(lfm2.CHUNK, False), (6 * lfm2.CHUNK, True)])
def test_the_bfloat16_sums_control_is_coarser_only_where_a_sum_runs(k, coarser):
    """``_mm`` under ``bf16_sums``: a contraction of one chunk is the
    stated arithmetic's own product; over six chunks its running sum is
    rounded five times more and lies farther from float64."""
    rs = np.random.RandomState(k)
    a, w = rs.randn(16, k).astype(np.float32), rs.randn(k, 32).astype(np.float32)
    a16, w16 = jnp.asarray(a, jnp.bfloat16), jnp.asarray(w, jnp.bfloat16)
    exact = np.asarray(a16, np.float64) @ np.asarray(w16, np.float64)
    err = lambda got: float(np.abs(np.asarray(got, np.float64) - exact).mean())  # noqa: E731
    stated = lfm2._mm(a16, w16, {"dtype": jnp.bfloat16})
    control = lfm2._mm(a16, w16, {"dtype": jnp.bfloat16, "bf16_sums": True})
    assert stated.dtype == control.dtype == jnp.bfloat16
    if coarser:
        assert err(control) > 1.5 * err(stated)
    else:
        np.testing.assert_array_equal(np.asarray(control, np.float32), np.asarray(stated, np.float32))
