"""Block diffusion (SDAR-30B-A3B-Chat's generation rule) at tiny sizes on
the CPU: the plain reference (benchmark/reference/sdar.py) against
itself, the program's forwards against the reference (logits through the
cache at every denoising forward, the committed K/V), and the served
path (tokens AND the forward that fixed each) against the reference's
plain loop; what a block-diffusion engine refuses, counts and reports.
"""
import json
import pathlib
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import spec
from benchmark.reference import sdar
from flexflow_tpu.generation import (
    BlockDiffusion, ContinuousBatchingScheduler, GenerationEngine, SamplingParams, SpeculationConfig, decoder,
)
from flexflow_tpu.ops import attention
from flexflow_tpu.ops.expert_product import expert_form
from flexflow_tpu.ops.kernels.flash_attention import reference_prefill_stream_attention
from flexflow_tpu.serving.resilience import ShuttingDownError

ROOT = pathlib.Path(__file__).resolve().parents[1]
B = 4  # the block length


@pytest.fixture(scope="module")
def config():
    c = json.loads((ROOT / "benchmark/configs/sdar-30b-a3b-chat.json").read_text())
    return spec._merge(c, c["rehearsal"])


@pytest.fixture(scope="module")
def params(config):
    return sdar.cast_params(sdar.init_params(3, config), jnp.float32)


@pytest.fixture(scope="module")
def sharp(params):
    """The same weights with a head 60 x as large: confidences that pass
    a threshold, which seeded weights' near-uniform softmax never does."""
    return dict(params, lm_head=params["lm_head"] * 60.0)


def make_engine(params, config, slots=3, steps=2, cache_config=None, **rule):
    return GenerationEngine(
        params, sdar.engine_config(config, 64), cache_config, max_batch_slots=slots, block_size=8, max_seq_len=64,
        prompt_buckets=[16, 32], diffusion=sdar.diffusion_rule(config, denoising_steps=steps, **rule),
    )


def prompts_of(seed, lengths, vocab=512):
    rs = np.random.RandomState(seed)
    return [[int(t) for t in rs.randint(0, vocab - 1, size=n)] for n in lengths]


def serve(engine, prompts, budgets, overlap=None, **sampling):
    sched = ContinuousBatchingScheduler(engine, overlap=overlap)
    handles = [sched.submit(p, SamplingParams(max_new_tokens=n, **sampling)) for p, n in zip(prompts, budgets)]
    for _ in range(2000):
        if all(h.done() for h in handles) or not sched.step():
            break
    return handles, sched


# ------------------------------------------------------------- the reference
def test_a_block_moves_with_its_own_rows_and_not_with_later_blocks(params, config):
    tokens = np.array(prompts_of(0, [16])[0])[None]
    none = np.zeros(tokens.shape, bool)
    base = np.asarray(sdar.forward(params, tokens, none, config))
    later = tokens.copy()
    later[0, 12:] = (later[0, 12:] + 7) % 500  # blocks after block 2
    assert np.array_equal(np.asarray(sdar.forward(params, later, none, config))[:, :12], base[:, :12])
    own = tokens.copy()
    own[0, 7] = (own[0, 7] + 7) % 500  # the LAST row of block 1: its earlier rows see it
    moved = np.abs(np.asarray(sdar.forward(params, own, none, config)) - base).max(axis=-1)[0]
    assert np.all(moved[:4] == 0) and np.all(moved[4:8] > 0)
    masked = none.copy()
    masked[0, 6] = True  # a masked row is embedded as the mask token
    assert np.abs(np.asarray(sdar.forward(params, tokens, masked, config)) - base).max(axis=-1)[0, 4] > 0


def test_block_logits_equals_the_whole_forward_at_the_blocks_rows(params, config):
    tokens = np.array(prompts_of(1, [24])[0])
    _, kvs = sdar.hidden_kv(params, tokens[None], np.zeros((1, 24), bool), config)
    bases = np.array([8, 16, 20])
    blocks = np.stack([tokens[b : b + B] for b in bases])
    masked = np.array([[0, 1, 1, 0], [1, 1, 1, 1], [0, 0, 0, 0]], bool)
    got = np.asarray(sdar.block_logits(params, config, kvs, blocks, masked, bases))
    for m, base in enumerate(bases):
        hidden = np.zeros((1, base + B), bool)
        hidden[0, base:] = masked[m]
        want = np.asarray(sdar.forward(params, tokens[None, : base + B], hidden, config))[0, base:]
        np.testing.assert_allclose(got[m], want, atol=2e-5)


def test_the_parameter_count_is_the_familys(config):
    full = sdar.parameter_count(dict(config, **json.loads((ROOT / "benchmark/configs/sdar-30b-a3b-chat.json").read_text())), 48)
    assert round(full["total"] / 1e9, 1) == 30.5 and round(full["active"] / 1e9, 2) == 3.35


# ------------------------------------------------ the program's forwards
def test_forward_full_under_the_block_mask_is_the_references(params, config):
    cfg = sdar.engine_config(config, 64)
    tokens = np.array(prompts_of(2, [20]))
    masked = np.zeros(tokens.shape, bool)
    masked[0, [5, 17, 18]] = True
    got = decoder.forward_full(params, jnp.where(masked, cfg.vocab_size - 1, tokens), cfg=cfg)
    np.testing.assert_allclose(np.asarray(got), np.asarray(sdar.forward(params, tokens, masked, config)), atol=3e-5)


def test_prefill_then_block_steps_through_the_cache_give_the_references_logits_and_kv(params, config):
    """The prompt's whole blocks prefilled, then every denoising forward
    of two blocks through the cache: the reference's logits at the
    block's rows each time, and after each commit the cache holds the
    K/V of the reference's whole forward over the final tokens."""
    engine = make_engine(params, config, slots=2)
    cfg, mask_token = engine.dcfg, engine.diffusion.mask_token_id
    seq = prompts_of(3, [19])[0]
    head, table = seq[:16], engine.allocator.allocate(4)
    engine.prefill_one(head, table, SamplingParams(), jax.random.key(0))
    tables = np.zeros((2, engine.max_blocks_per_seq), np.int32)
    tables[1, :4] = table
    final = list(head)
    for base, given in ((16, seq[16:]), (20, [])):
        tokens = np.zeros((2, B), np.int32)
        tokens[1, : len(given)] = given
        fixed = np.zeros((2, B), bool)
        fixed[1, : len(given)] = True
        while True:
            logits, k, v = decoder.block_step(
                params, jnp.asarray(tokens), jnp.asarray(fixed), jnp.asarray([0, base]), jnp.asarray([0, 1]), mask_token,
                engine.cache.k, engine.cache.v, jnp.asarray(tables), cfg=cfg,
            )
            engine.cache.update(k, v)
            whole = np.array(final + list(tokens[1]))[None]
            hidden = np.concatenate([np.zeros(len(final), bool), ~fixed[1]])[None]
            want = np.asarray(sdar.forward(params, whole, hidden, config))[0, base:]
            np.testing.assert_allclose(np.asarray(logits)[1], want, atol=3e-5)
            if fixed[1].all():
                break  # that forward was the commit
            token, _, chosen, _ = sdar.rule(jnp.asarray(want[None]), jnp.asarray(~fixed[1][None]), 2, 2.0, mask_token)
            tokens[1] = np.where(np.asarray(chosen[0]), np.asarray(token[0]), tokens[1])
            fixed[1] |= np.asarray(chosen[0])
        final += [int(t) for t in tokens[1]]
        _, kvs = sdar.hidden_kv(params, np.array(final)[None], np.zeros((1, len(final)), bool), config)
        stored = engine._logical(engine.cache.k)  # [L, blocks, block size, Hkv, D]
        for layer, (ref_k, _) in enumerate(kvs):
            got = np.stack([np.asarray(stored[layer, table[p // 8], p % 8]) for p in range(len(final))])
            np.testing.assert_allclose(got, np.asarray(ref_k[0]), atol=2e-5)


def test_verify_step_without_a_bound_is_what_it_was(params, config):
    """``attend_positions`` None is the window's own positions: bit for bit."""
    engine = make_engine(params, config, slots=2)
    causal = sdar.engine_config(config, 64)
    causal.block_mask = 0
    tokens = jnp.asarray(prompts_of(4, [4, 4]), jnp.int32)
    positions = jnp.asarray([[0, 1, 2, 3], [0, 1, 2, -1]], jnp.int32)
    tables = jnp.asarray(np.arange(1, 1 + 2 * engine.max_blocks_per_seq).reshape(2, -1), jnp.int32)
    args = (params, tokens, positions, engine.cache.k, engine.cache.v, tables)
    a = decoder.verify_step(*args, cfg=causal)
    b = decoder.verify_step(*args, cfg=causal, attend_positions=positions)
    assert all(np.array_equal(np.asarray(x), np.asarray(y)) for x, y in zip(a, b))


# ----------------------------------------------------- prefill_attention
def test_prefill_attention_under_the_block_mask():
    rs = np.random.RandomState(0)
    q, k, v = (jnp.asarray(rs.randn(2, 16, h, 8), jnp.float32) for h in (4, 2, 2))
    lengths = jnp.asarray([16, 11])
    got = attention.masked_attention(q, k, v, lengths, block=B)
    # against plain softmax attention under the mask written out
    i, j = np.arange(16)[:, None], np.arange(16)[None, :]
    seen = (j // B <= i // B)[None] & (j < np.asarray(lengths)[:, None, None])
    s = np.einsum("bqhgd,bkhd->bhgqk", np.asarray(q).reshape(2, 16, 2, 2, 8), np.asarray(k)) * 8 ** -0.5
    p = np.where(seen[:, None, None], np.exp(s - s.max(-1, keepdims=True)), 0.0)
    want = np.einsum("bhgqk,bkhd->bqhgd", p / p.sum(-1, keepdims=True), np.asarray(v)).reshape(2, 16, 4, 8)
    np.testing.assert_allclose(np.asarray(got)[0], want[0], atol=1e-5)
    np.testing.assert_allclose(np.asarray(got)[1, :11], want[1, :11], atol=1e-5)
    streamed = reference_prefill_stream_attention(q, k, v, lengths, chunk=8, block=B)
    np.testing.assert_allclose(np.asarray(streamed)[1, :11], np.asarray(got)[1, :11], atol=1e-5)
    # block 0 is the causal mask, bit for bit
    assert np.array_equal(np.asarray(attention.masked_attention(q, k, v, lengths)),
                          np.asarray(attention.masked_attention(q, k, v, lengths, block=0)))
    assert np.array_equal(np.asarray(attention._seen(5, 9, 0)), np.tril(np.ones((5, 9), bool), k=4))


def test_the_streamed_kernel_refuses_a_block_mask_by_name(monkeypatch):
    monkeypatch.setattr(attention, "on_tpu", lambda: True)
    shape = (1, 8192, 64, 128)  # a group of 16 query heads: a whole tile, which the kernel takes
    assert attention.prefill_call_lowering(shape, (1, 8192, 4, 128), 2)["kernel"] == "prefill_stream_attention"
    low = attention.prefill_call_lowering(shape, (1, 8192, 4, 128), 2, block=B)
    assert low["form"] == "streamed" and low["kernel"] == "xla_chunks" and "block mask of 4" in low["refused"]
    # the cell's prompts stay under the score bound: materialised, whatever the mask
    assert attention.prefill_call_lowering((1, 1024, 32, 128), (1, 1024, 4, 128), 2, block=B)["form"] == "materialised"


# ------------------------------------------------------- the served path
OVERLAP = pytest.mark.parametrize("overlap", [True, False], ids=["pipelined", "sequential"])


@OVERLAP
@pytest.mark.parametrize("steps", [1, 2, 4])
def test_served_tokens_and_fixed_at_are_the_plain_loops(params, config, steps, overlap):
    """Prompt remainders ``P mod 4`` in 0..3, budgets that end inside a
    block, more requests than slots (so slots sit at different phases of
    their blocks in one step): tokens and ``fixed_at`` equal
    ``reference.generate``; the mask token is never emitted. With the
    step in the overlap pipeline and without: the same bytes."""
    engine = make_engine(params, config, steps=steps)
    lengths, budgets = (8, 9, 10, 11, 3), (9, 8, 6, 12, 5)
    prompts = prompts_of(steps, lengths)
    seen = []
    real = engine.consume_block  # (every block step's result comes through it, pipelined or not)
    engine.consume_block = lambda *a: seen.append(real(*a)) or seen[-1]
    handles, sched = serve(engine, prompts, budgets, overlap=overlap)
    assert (sched.pipe_dispatches > 0) == overlap
    for p, n, h in zip(prompts, budgets, handles):
        tokens, fixed_at = sdar.generate(params, config, p, n, steps=steps)
        assert h.result(0) == tokens and h._request.fixed_at == fixed_at
        assert engine.diffusion.mask_token_id not in tokens and len(tokens) == n
    if steps == 4:  # (at 1 step every slot admitted together alternates in lockstep)
        assert any(r["commit"].any() and r["chosen"].any() for r in seen), "no step had one slot commit while another denoised"
    # every block ran whole and was committed once: the counters' identities under the static rule
    blocks = sum(-(-(len(p) + n) // B) - len(p) // B for p, n in zip(prompts, budgets))
    c = engine.diffusion_stats()
    assert c["blocks_committed_total"] == c["commit_forwards_total"] == blocks
    assert c["tokens_fixed_total"] == sum(budgets) + sum(-(len(p) + n) % B for p, n in zip(prompts, budgets))
    masked_rows = sum(B * (-(-(len(p) + n) // B) - len(p) // B) - len(p) % B for p, n in zip(prompts, budgets))
    assert c["tokens_fixed_total"] == masked_rows
    if all(len(p) % B == 0 for p in prompts[:1]) and steps == 1:
        assert c["fixed_per_forward_histogram"][B] > 0
    assert sum(c["fixed_per_forward_histogram"]) + c["commit_forwards_total"] == c["slot_forwards_total"]
    assert engine.trace_counts["block_step"] == 1 and "decode" not in engine.trace_counts


def test_slot_forwards_are_blocks_times_steps_plus_one(params, config):
    engine = make_engine(params, config, steps=2)
    prompts, budgets = prompts_of(9, (8, 12, 16)), (8, 12, 4)  # whole blocks only
    serve(engine, prompts, budgets)
    c = engine.diffusion_stats()
    assert c["slot_forwards_total"] == c["blocks_committed_total"] * (2 + 1) == (2 + 3 + 1) * 3
    assert c["fixed_per_forward_histogram"] == [0, 0, 12, 0, 0]


@OVERLAP
def test_the_dynamic_rule_takes_fewer_forwards_and_is_the_plain_loops(sharp, config, overlap):
    static = make_engine(sharp, config, steps=4)
    dynamic = make_engine(sharp, config, steps=4, remasking="low_confidence_dynamic", threshold=0.5)
    prompts, budgets = prompts_of(5, (9, 12, 6)), (11, 8, 13)
    serve(static, prompts, budgets, overlap=overlap)
    handles, sched = serve(dynamic, prompts, budgets, overlap=overlap)
    assert (sched.pipe_dispatches > 0) == overlap
    for p, n, h in zip(prompts, budgets, handles):
        tokens, fixed_at = sdar.generate(sharp, config, p, n, steps=4, remasking="low_confidence_dynamic", threshold=0.5)
        assert h.result(0) == tokens and h._request.fixed_at == fixed_at
    assert dynamic.diffusion_stats()["slot_forwards_total"] < static.diffusion_stats()["slot_forwards_total"]
    assert sum(dynamic.diffusion_stats()["fixed_per_forward_histogram"][2:]) > 0  # several rows passed at once
    # a request's own rule over the engine's default
    handles, _ = serve(static, prompts[:1], budgets[:1], overlap=overlap, remasking="low_confidence_dynamic", threshold=0.5)
    assert handles[0].result(0) == sdar.generate(sharp, config, prompts[0], budgets[0], steps=4,
                                                 remasking="low_confidence_dynamic", threshold=0.5)[0]


def test_a_prompt_holding_the_mask_id_is_read_as_a_token(params, config):
    engine = make_engine(params, config)
    mask_token = engine.diffusion.mask_token_id
    prompt = prompts_of(6, [10])[0]
    prompt[3], prompt[9] = mask_token, mask_token  # one in a prefilled block, one in the first block's fixed rows
    handles, _ = serve(engine, [prompt], [7])
    tokens, fixed_at = sdar.generate(params, config, prompt, 7, steps=2)
    assert handles[0].result(0) == tokens and handles[0]._request.fixed_at == fixed_at and mask_token not in tokens


def test_sampling_is_seeded_and_never_draws_the_mask_token(params, config):
    engine = make_engine(params, config)
    prompts = prompts_of(7, (9, 6))
    a = [h.result(0) for h in serve(engine, prompts, (12, 12), temperature=1.5, seed=5)[0]]
    b = [h.result(0) for h in serve(engine, prompts, (12, 12), temperature=1.5, seed=5)[0]]
    c = [h.result(0) for h in serve(engine, prompts, (12, 12), temperature=1.5, seed=6)[0]]
    assert a == b and a != c
    assert all(0 <= t < 511 for stream in a + c for t in stream)


def test_an_end_inside_a_block_ends_the_request_with_what_has_left(params, config):
    engine = make_engine(params, config)
    prompt = prompts_of(8, [9])[0]
    full, _ = sdar.generate(params, config, prompt, 12, steps=2)
    eos = full[5]
    at = full.index(eos)
    handles, _ = serve(engine, [prompt], [12], eos_id=eos)
    assert handles[0].result(0) == full[: at + 1] == sdar.generate(params, config, prompt, 12, steps=2, eos_id=eos)[0]


@OVERLAP
def test_cancel_and_preemption_inside_a_block(params, config, overlap):
    """A request preempted with a row fixed out of order resumes with it
    (and gives the unpreempted stream, ``fixed_at`` too); one cancelled
    there ends with an error after a prefix of its stream. With a step
    in flight both drain it first."""
    prompts, budgets = prompts_of(10, (9, 14)), (14, 10)
    want = [sdar.generate(params, config, p, n, steps=4) for p, n in zip(prompts, budgets)]
    engine = make_engine(params, config, steps=4)
    sched = ContinuousBatchingScheduler(engine, overlap=overlap)
    handles = [sched.submit(p, SamplingParams(max_new_tokens=n)) for p, n in zip(prompts, budgets)]

    def inside():
        return [s for s in sched._running.values() if 0 < s.blk.fixed.sum() < B and s.blk.forwards > 0]

    preempted = in_flight = 0
    for _ in range(400):
        if inside() and preempted < 3 and (sched._pipe is not None) == overlap:
            in_flight += sched._pipe is not None
            sched._drain_frontier("pressure")  # (what the loop does itself before it preempts: never with a step in flight)
            if inside():
                assert sched._preempt_youngest()
                preempted += 1
        if all(h.done() for h in handles) or not sched.step():
            break
    assert preempted == 3 and sum(h._request.preemptions for h in handles) == 3 and in_flight == 3 * overlap
    assert [(h.result(0), h._request.fixed_at) for h in handles] == want
    # cancel
    sched = ContinuousBatchingScheduler(make_engine(params, config, steps=4), overlap=overlap)
    h = sched.submit(prompts[0], SamplingParams(max_new_tokens=14))
    while h._request.n_generated < 3:
        sched.step()
    assert (sched._pipe is not None) == overlap
    h.cancel()
    sched.step()
    with pytest.raises(ShuttingDownError):
        h.result(0)
    got = list(h._request.generated)
    assert got == want[0][0][: len(got)] and not sched._running and len(sched._free_slots) == 3


@pytest.mark.parametrize("site,nth", [("generation.decode_step", (4, 5)), ("generation.async_readback", (3,))])
@OVERLAP
def test_a_journal_replay_restarts_the_block_from_what_had_left(params, config, overlap, site, nth):
    """A crash mid-stream: the engine is reset and every stream replayed
    from its journal; the block in flight restarts from the emitted
    tokens as fixed rows, and the streams complete at their budgets.
    Pipelined, the crash meets a step in flight: at the dispatch of its
    successor (the step in flight is consumed first) or at its own
    consume (its successor is discarded)."""
    from flexflow_tpu.generation import RecoveryPolicy
    from flexflow_tpu.runtime.faults import FaultInjected, FaultPlan

    if site == "generation.async_readback" and not overlap:
        pytest.skip("the sequential loop has no consume apart from its dispatch")
    prompts, budgets = prompts_of(11, (9, 6)), (10, 9)
    engine = make_engine(params, config)
    sched = ContinuousBatchingScheduler(engine, overlap=overlap, recovery=RecoveryPolicy(sleep=lambda _s: None))
    plan = FaultPlan(seed=0)
    if site == "generation.async_readback":
        # the consume is lost and the step's sequential re-run crashes twice: an engine-level fault
        plan.on(site, mode="error", error=FaultInjected("readback lost"), nth=nth)
        plan.on("generation.decode_step", mode="error", error=RuntimeError("device crash"), nth=(5, 6))
    else:
        plan.on(site, mode="error", error=RuntimeError("device crash"), nth=nth)
    with plan.active():
        handles = [sched.submit(p, SamplingParams(max_new_tokens=n)) for p, n in zip(prompts, budgets)]
        for _ in range(400):
            if all(h.done() for h in handles) or not sched.step():
                break
    assert sched.recovery_stats.recoveries == 1 and engine.resets == 1
    for p, n, h in zip(prompts, budgets, handles):
        tokens = h.result(0)
        assert len(tokens) == n == len(h._request.fixed_at) and h._request.replays == 1
        # what had left before the crash is the plain loop's; the rest a valid continuation of it
        assert tokens[:2] == sdar.generate(params, config, p, n, steps=2)[0][:2]


def test_the_durable_journal_takes_a_step_of_several_tokens(params, config, tmp_path):
    from flexflow_tpu.serving.durable import Durability, DurabilityConfig

    engine = make_engine(params, config, steps=1)  # 4 tokens a denoising forward
    sched = ContinuousBatchingScheduler(engine)
    durable = Durability(sched, DurabilityConfig(wal_dir=str(tmp_path), fsync=False))
    prompt = prompts_of(12, [8])[0]
    h = sched.submit(prompt, SamplingParams(max_new_tokens=8))
    while not h.done():
        sched.step()
    state, done = durable.lookup(h._request.durable_id)
    assert state == "done" and list(done["tokens"]) == h.result(0) == sdar.generate(params, config, prompt, 8, steps=1)[0]


# ------------------------------------------- the block step in the overlap pipeline
def counters(engine):
    c = engine.diffusion_stats()
    return {k: c[k] for k in ("slot_forwards_total", "commit_forwards_total", "blocks_committed_total",
                              "tokens_fixed_total", "fixed_per_forward_histogram")}


@pytest.mark.parametrize("rule", ["static", "dynamic"])
def test_an_end_of_sequence_token_with_a_successor_in_flight_is_counted_as_the_sequential_loop_counts(sharp, config, rule):
    """The successor was dispatched with the slot that then ended on
    its end-of-sequence token: that slot's rows in it are neither
    emitted nor counted, so streams, ``fixed_at`` AND the rule's
    counters are the sequential loop's."""
    kw = dict(steps=4, remasking="low_confidence_dynamic", threshold=0.5) if rule == "dynamic" else dict(steps=2)
    prompts, budgets = prompts_of(16, (9, 12, 6)), (13, 11, 12)
    full = serve(make_engine(sharp, config, **kw), prompts, budgets, overlap=False)[0][0].result(0)
    eos = full[5]  # inside the first request's second block; the others run to their budgets or meet it too
    got = {}
    for overlap in (False, True):
        engine = make_engine(sharp, config, **kw)
        handles, sched = serve(engine, prompts, budgets, overlap=overlap, eos_id=eos)
        got[overlap] = ([(h.result(0), h._request.fixed_at) for h in handles], counters(engine))
        if overlap:
            assert sched.pipe_drains["finish"] >= 1 and sched.pipe_dispatches > sched.pipe_drains["finish"]
    assert got[True] == got[False]
    assert got[True][0][0][0] == full[: full.index(eos) + 1] and len(full) > full.index(eos) + 1
    # the forwards the engine RAN are more: the successor's rows for the ended slot
    assert engine.step_counts["block_step"] >= 1


def test_a_spent_budget_leaves_the_slot_out_of_the_successor_and_drains_nothing_before_its_commit(params, config):
    """A request whose budget is spent still awaits its block's commit:
    no drain on the steps before it (as ``finished()`` would ask on
    every request's last block), and with that commit in flight the
    next dispatch leaves the slot out."""
    engine = make_engine(params, config, steps=4)
    prompts, budgets = prompts_of(17, (8, 12)), (5, 14)  # the first ends a row into its second block
    sched = ContinuousBatchingScheduler(engine)
    handles = [sched.submit(p, SamplingParams(max_new_tokens=n)) for p, n in zip(prompts, budgets)]
    left_out, awaiting, real = [], 0, sched._dispatch
    sched._dispatch = lambda live, prev: left_out.extend(
        (s.req, prev is not None) for s in sched._running.values() if s not in live) or real(live, prev)
    for _ in range(400):
        if all(h.done() for h in handles) or not sched.step():
            break
        spent = [s for s in sched._running.values() if s.req.finished()]
        awaiting += bool(spent)
        if spent:
            assert sched._pipe is not None and sched.pipe_drains["nonsteady"] == 0
    assert awaiting >= 3, "the budget-spent request never waited for its block's last rows and commit"
    assert [r for r, _ in left_out] == [handles[0]._request]  # (the second's commit is consumed with nothing left to dispatch)
    assert all(in_flight and r.n_generated == r.max_new for r, in_flight in left_out)
    assert sched.pipe_drains == {"nonsteady": 0, "finish": 1, "pressure": 0, "idle": 0}  # (the last end has no successor)
    for p, n, h in zip(prompts, budgets, handles):
        assert (h.result(0), h._request.fixed_at) == sdar.generate(params, config, p, n, steps=4)
    c = engine.diffusion_stats()
    assert c["blocks_committed_total"] == c["commit_forwards_total"] == sum(
        -(-(len(p) + n) // B) - len(p) // B for p, n in zip(prompts, budgets))


def test_pool_pressure_drains_the_step_in_flight_and_preempts(params, config):
    """A pool with nothing left to evict: the pipeline drains for
    ``pressure`` and the sequential body preempts; the streams are the
    plain loop's."""
    import dataclasses

    prompts, budgets = prompts_of(18, (9, 14, 11)), (22, 18, 20)
    roomy = make_engine(params, config, steps=2)
    tight = dataclasses.replace(roomy.cache_config, num_blocks=9)  # three sequences of up to 4 blocks, and the scratch block
    got = {}
    for overlap in (False, True):
        engine = make_engine(params, config, steps=2, cache_config=tight)
        handles, sched = serve(engine, prompts, budgets, overlap=overlap)
        got[overlap] = [(h.result(0), h._request.fixed_at) for h in handles]
        assert sched.preemptions >= 1
        assert (sched.pipe_drains["pressure"] >= 1) == overlap
    assert got[True] == got[False] == [sdar.generate(params, config, p, n, steps=2) for p, n in zip(prompts, budgets)]


def test_the_pipeline_section_counts_block_steps(params, config):
    engine = make_engine(params, config)
    prompts, budgets = prompts_of(19, (8, 9, 10, 11, 3)), (9, 8, 6, 12, 5)  # five requests over three slots
    sched = ContinuousBatchingScheduler(engine)
    handles = [sched.submit(p, SamplingParams(max_new_tokens=n)) for p, n in zip(prompts[:2], budgets[:2])]
    while sched._pipe is None:
        sched.step()
    # a slot is free and a step in flight: the admissions drain it first
    handles += [sched.submit(p, SamplingParams(max_new_tokens=n)) for p, n in zip(prompts[2:], budgets[2:])]
    while not all(h.done() for h in handles):
        assert sched.step()
    p = sched.pipeline_stats()
    assert p["block_steps_total"] == engine.step_counts["block_step"] > 0 and p["decode_steps_total"] == 0
    assert 0 < p["pipelined_steps_total"] < p["block_steps_total"]
    assert p["drains_total"]["finish"] >= 1 and p["drains_total"]["nonsteady"] == 1 and p["emits_pending"] == 0
    assert sched.stats.snapshot()["pipeline"] == p
    assert [h.result(0) for h in handles] == [sdar.generate(params, config, q, n, steps=2)[0] for q, n in zip(prompts, budgets)]
    _, off = serve(make_engine(params, config), prompts, budgets, overlap=False)
    q = off.pipeline_stats()
    assert q["pipelined_steps_total"] == 0 and not any(q["drains_total"].values()) and q["block_steps_total"] > 0


# ------------------------------------------------------------ refusals
def test_the_refused_paths_are_refused_by_name(params, config):
    engine = make_engine(params, config)
    assert set(engine.unsupported) >= {"speculation", "constrained_decoding", "kv_handoff", "tensor_parallel"}
    sched = ContinuousBatchingScheduler(engine)
    with pytest.raises(NotImplementedError, match="speculative verification"):
        sched.submit([1, 2, 3], SamplingParams(), speculation=SpeculationConfig(enabled=True, k=2))
    with pytest.raises(NotImplementedError, match="left-to-right automaton"):
        sched.submit([1, 2, 3], SamplingParams(), grammar=object())
    with pytest.raises(NotImplementedError, match="disaggregation wire"):
        engine.pack_kv_blocks([1], 8)
    with pytest.raises(NotImplementedError, match="tp_degree > 1"):
        GenerationEngine(params, sdar.engine_config(config, 64), tp_degree=2, diffusion=sdar.diffusion_rule(config))
    with pytest.raises(ValueError, match="do not divide"):
        sched.submit([1, 2, 3], SamplingParams(denoising_steps=3))
    with pytest.raises(ValueError, match="remasking"):
        sched.submit([1, 2, 3], SamplingParams(remasking="random"))


def test_the_geometry_a_block_length_asks_of_an_engine(params, config):
    cfg = sdar.engine_config(config, 64)
    with pytest.raises(ValueError, match="block diffusion over blocks of that length"):
        GenerationEngine(params, cfg)  # a block mask and no rule
    with pytest.raises(ValueError, match="multiples of it"):
        GenerationEngine(params, cfg, block_size=6, max_seq_len=64, prompt_buckets=[16], diffusion=sdar.diffusion_rule(config))
    with pytest.raises(ValueError, match="attention layers"):
        decoder.DecoderConfig(num_layers=2, hidden_size=32, num_heads=4, ff_size=64, seq_length=64, vocab_size=50,
                              layer_types=("attention", "conv"), block_mask=4)
    with pytest.raises(ValueError, match="divide"):
        BlockDiffusion(block_length=4, denoising_steps=3)


def test_a_prefix_hit_is_whole_cache_blocks_and_gives_the_same_stream(params, config):
    engine = make_engine(params, config)
    prompt = prompts_of(13, [21])[0]
    first = serve(engine, [prompt], [6])[0][0].result(0)
    again = serve(engine, [prompt[:19] + [7, 8]], [6])[0][0]
    assert engine.prefix_cache.hits == 1 and engine.prefix_cache.tokens_reused_total == 16  # two cache blocks of 8
    assert serve(engine, [prompt], [6])[0][0].result(0) == first == sdar.generate(params, config, prompt, 6, steps=2)[0]
    assert again.result(0) == sdar.generate(params, config, prompt[:19] + [7, 8], 6, steps=2)[0]


# ------------------------------------------------- the server and its stats
def test_sse_events_come_in_order_and_the_response_carries_fixed_at(params, config):
    from flexflow_tpu.serving import InferenceServer
    from flexflow_tpu.serving.generation import GenerationModel

    engine = make_engine(params, config)
    prompt = prompts_of(14, [10])[0]
    want = sdar.generate(params, config, prompt, 9, steps=2)
    one_step = sdar.generate(params, config, prompt, 9, steps=1)
    server = InferenceServer(port=0)
    server.register_generation(GenerationModel(engine, name="lm"))

    def post(body):
        req = urllib.request.Request(f"http://127.0.0.1:{server.port}/v2/models/lm/generate", json.dumps(body).encode(),
                                     {"Content-Type": "application/json"})
        return urllib.request.urlopen(req, timeout=60).read().decode()

    with server:
        events = [json.loads(line[5:]) for line in post({"prompt": prompt, "max_new_tokens": 9, "stream": True}).splitlines()
                  if line.startswith("data:")]
        assert [e["index"] for e in events[:-1]] == list(range(9))
        assert [e["token"] for e in events[:-1]] == events[-1]["tokens"] == want[0] and events[-1]["done"]
        body = json.loads(post({"prompt": prompt, "max_new_tokens": 9}))
        assert body["tokens"] == want[0] and body["fixed_at"] == want[1]
        body = json.loads(post({"prompt": prompt, "max_new_tokens": 9, "parameters": {"denoising_steps": 1}}))
        assert body["tokens"] == one_step[0] and body["fixed_at"] == [0] * 9
        lm = json.loads(urllib.request.urlopen(f"http://127.0.0.1:{server.port}/v2/stats", timeout=60).read())["generation"]["lm"]
    d = lm["diffusion"]
    assert d["block_length"] == 4 and d["denoising_steps"] == 2 and d["remasking"] == "low_confidence_static"
    assert d["tokens_fixed_total"] >= 27 and len(d["fixed_per_forward_histogram"]) == 5
    assert lm["kernels"]["block_step"]["group"] == 2 and "block_step" in lm["experts"]["forms"]
    assert lm["prefill_attention"]["programs"]["prefill[16]"]["form"] == "materialised"
    phases = {k for k in lm["step_phases"] if k.startswith("block_step.")}
    assert {"block_step.dispatch", "block_step.execute", "block_step.readback", "block_step.dispatch.upload",
            "block_step.dispatch.call"} <= phases
    assert len(lm["experts"]["tokens_total"]) == 8
    trace = [t for t in json.loads(json.dumps(server.debug_traces(n=8)))["traces"] if t.get("fixed_at")]
    assert trace and all(len(t["fixed_at"]) == t["n_generated"] for t in trace)


def test_a_steady_composition_uploads_nothing(params, config):
    engine = make_engine(params, config, slots=2)
    prompts = prompts_of(15, (8, 12))
    sched = ContinuousBatchingScheduler(engine)
    handles = [sched.submit(p, SamplingParams(max_new_tokens=16)) for p in prompts]
    for _ in range(4):
        sched.step()
    before = dict(engine.uploads)
    for _ in range(5):
        sched.step()
    # the blocks, their flags, bases and forwards pass from step to step on the device; what goes up is a
    # table that grew by a cache block (every second block of 4 at cache blocks of 8)
    assert engine.uploads["carried_hits_total"] == before["carried_hits_total"] + 5
    assert engine.uploads["carried_misses_total"] == before["carried_misses_total"]
    assert engine.uploads["uploads_total"] - before["uploads_total"] <= 2
    # a pipelined steady step takes its state from the predecessor's device outputs: the same arrays, and
    # no host array is compared for them (7 staged look-ups a step: the table, active and the five vectors)
    calls, real = [], engine._block_jit
    engine._block_jit = lambda params, *a: calls.append((a[:4], real(params, *a))) or calls[-1][1]
    before = dict(engine.uploads)
    for _ in range(3):
        sched.step()
    assert sched.pipe_dispatches >= 8 and len(calls) == 3
    for (state, _), (_, results) in zip(calls[1:], calls):
        assert all(x is y for x, y in zip(state, results[-4:]))
    looked = sum(engine.uploads[k] - before[k] for k in ("staged_hits_total", "staged_misses_total"))
    assert looked == 3 * 7
    engine._block_jit = real
    while not all(h.done() for h in handles):
        sched.step()


# ------------------------------------------------------- expert_form, flops
ACCEPTED_SHAPES = [
    # (rows, held, k, outputs) the accepted cells' step programs ask, and the form each keeps
    ((64, 32, 4, 32), "dense"), ((512, 32, 4, 32), "dense"),  # lfm2-8b-a1b.gen-batch: decode, prefill[512]
    ((48, 64, 8, 64), "dense"), ((1536, 64, 8, 64), "grouped"), ((2048, 64, 8, 64), "grouped"),  # mellum2-12b.code-gen
    ((48, 16, 8, 256), "dense"), ((2048, 16, 8, 256), "dense"),  # joyai-llm-flash.long-gen
    ((16, 16, 8, 128), "dense"), ((5120, 16, 8, 128), "grouped"), ((6144, 16, 8, 128), "grouped"),  # command-a-plus.long-doc
    ((32, 16, 12, 768), "grouped"), ((2048, 16, 12, 768), "grouped"),  # longcat-flash-chat.agent-turns
]


@pytest.mark.parametrize("shape,form", ACCEPTED_SHAPES)
def test_every_shape_an_accepted_cell_asks_keeps_its_form(shape, form):
    assert expert_form(*shape) == form


def test_the_block_steps_rows_keep_the_dense_form():
    assert [expert_form(rows, 128, 8, 128) for rows in (128, 192, 256)] == ["dense"] * 3
    assert expert_form(1024, 128, 8, 128) == "grouped" and expert_form(512, 128, 8, 128) == "dense"


def test_serving_flops_of_a_block_forward(config):
    from flexflow_tpu.obs.capacity import ServingFlops

    cfg = sdar.engine_config(config, 64)
    flops = ServingFlops.from_config(cfg, dtype=cfg.dtype)
    slots, ctx = 3, 3 * 24
    assert flops.block_flops(slots, ctx, B) == flops.verify_flops(slots * B, ctx * B)
    # K/V of a slot's context is read once for its B rows, the block's rows written every forward
    assert flops.block_bytes(slots, ctx, B) < flops.verify_bytes(slots * B, ctx * B)
    assert flops.block_bytes(slots, ctx, B) > flops.decode_bytes(slots, ctx)
