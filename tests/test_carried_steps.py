"""A decode step's positions and counts stay on the device (ISSUE 40, tier-1).

The decode program returns the positions and counts it was given,
advanced by one in every active slot, and the engine keeps them in its
staging beside the host's same sums; a step of the same composition
finds its host vectors equal and uploads nothing. Held here, over a
plain, a convolution, a window and a latent configuration at rehearsal
widths:
  * streams are the same token for token with the pipeline on, off and
    through ``generate``, greedy and sampled (``counts`` drives the keys)
  * a steady step hits, a step after an admission or a finish misses
  * a bisection probe that clears a live slot misses and uploads
  * a fault at the decode step and a ``reset()`` drop the carried
    entries, and the retried or replayed stream is byte-exact
"""
import jax
import numpy as np
import pytest

from flexflow_tpu.generation import (
    ContinuousBatchingScheduler,
    GenerationEngine,
    RecoveryPolicy,
    SamplingParams,
    init_decoder_params,
)
from flexflow_tpu.generation.engine import CARRIED
from flexflow_tpu.models.transformer import TransformerConfig
from flexflow_tpu.runtime import faults
from flexflow_tpu.runtime.faults import FaultPlan, TransientDeviceError
from flexflow_tpu.serving.resilience import RetryPolicy
from tests.test_expert_product import rehearsal_model

pytestmark = pytest.mark.generation

PLAIN = TransformerConfig(num_layers=2, hidden_size=32, num_heads=4, ff_size=64, seq_length=64, vocab_size=512, causal=True)
# a plain, a convolution, a window and a latent configuration
CONFIGS = ["plain", "lfm2-8b-a1b", "mellum2-12b", "joyai-llm-flash"]
PROMPTS = [[5, 9, 2, 77, 13], [301, 17, 4], [64, 65, 66, 67, 68, 69, 70, 71, 72]]
GREEDY = SamplingParams(max_new_tokens=14)
SAMPLED = SamplingParams(max_new_tokens=14, temperature=0.9, seed=11)
NO_SLEEP = RecoveryPolicy(sleep=lambda _s: None)

_models = {}


def make_engine(name, slots=3):
    if name not in _models:
        _models[name] = (PLAIN, init_decoder_params(jax.random.key(0), PLAIN)) if name == "plain" else rehearsal_model(name)
    cfg, params = _models[name]
    return GenerationEngine(params, cfg, max_batch_slots=slots, max_seq_len=64, block_size=8, prompt_buckets=(16, 32, 64),
                            prefix_cache=False)


@pytest.fixture(scope="module", params=CONFIGS)
def engine(request):
    """One engine a configuration for the whole file: every test leaves
    it with no stream running, and what a test before left in the
    staging is a miss to the next."""
    return make_engine(request.param)


def serve(eng, prompts=PROMPTS, sampling=GREEDY, plan=None, **kw):
    sched = ContinuousBatchingScheduler(eng, **kw)
    if plan is not None:
        plan.install()
    try:
        handles = [sched.submit(list(p), sampling) for p in prompts]
        steps = 0
        while any(not h.done() for h in handles) and sched.step():
            steps += 1
            assert steps < 2000
    finally:
        if plan is not None:
            plan.remove()
    return handles, sched


def streams(handles):
    return [h.result(timeout=0) for h in handles]


def grown(eng, before):
    return {k: v - before[k] for k, v in eng.uploads.items()}


@pytest.mark.parametrize("sampling", [GREEDY, SAMPLED], ids=["greedy", "sampled"])
def test_streams_are_the_same_with_the_pipeline_on_off_and_through_generate(engine, sampling):
    before = dict(engine.uploads)
    on = streams(serve(engine, sampling=sampling, overlap=True)[0])
    hits = grown(engine, before)["carried_hits_total"]
    off = streams(serve(engine, sampling=sampling, overlap=False)[0])
    assert on == off == engine.generate(PROMPTS, sampling)
    assert all(len(s) == sampling.max_new_tokens for s in on)
    assert hits >= sampling.max_new_tokens - 4  # the steady steps carried
    # and a sampled stream is not the greedy one: the counts reached the keys
    if sampling is SAMPLED:
        assert on != streams(serve(engine, overlap=True)[0])


def test_a_steady_step_hits_and_a_step_after_an_admission_or_a_finish_misses(engine):
    """Step by step through two requests of unequal length, pipelined:
    every step reports a hit or a miss; a hit uploaded nothing of its
    positions and counts (what it did upload, staging missed, a table
    that grew, or was the host token array of a pipeline's first step),
    a miss uploaded both; the step after the admissions and the one
    after the first finish are misses."""
    sched = ContinuousBatchingScheduler(engine, overlap=True)
    handles = [sched.submit(p, SamplingParams(max_new_tokens=n)) for p, n in (([1, 2, 3, 4], 6), ([9, 8, 7], 14))]
    steps = []
    while any(not h.done() for h in handles):
        before, had = dict(engine.uploads), sched._pipe is not None
        decodes = engine.step_counts["decode"]
        sched.step()
        if engine.step_counts["decode"] > decodes:
            steps.append((len(sched._running), had and sched._pipe is not None, grown(engine, before)))
    # one stream left, the other's slot inactive: from the iteration that finished it and ran the next step sequentially
    after_finish = [g for live, _, g in steps if live == 1]
    assert all(g["carried_hits_total"] + g["carried_misses_total"] == 1 for _, _, g in steps)
    hits = [(chained, g) for _, chained, g in steps if g["carried_hits_total"]]
    assert len(hits) >= 8
    for chained, g in hits:
        # a step dispatched on the token array of the one in flight uploads what staging missed (a table that grew, a
        # window that moved), and nothing else
        assert g["uploads_total"] - g["staged_misses_total"] == (0 if chained else 1) and g["staged_misses_total"] <= 3
    assert sum(g["uploads_total"] == 0 and g["upload_bytes_total"] == 0 for _, g in hits) >= 4
    misses = [g for _, _, g in steps if g["carried_misses_total"]]
    assert all(g["uploads_total"] >= 2 for g in misses)
    assert steps[0][2]["carried_misses_total"] == 1  # the first step after the admissions
    assert after_finish[0]["carried_misses_total"] == 1 and after_finish[0]["staged_misses_total"] >= 3  # + active, tables
    assert all(g["carried_misses_total"] == 0 for g in after_finish[1:])


def test_a_probe_that_clears_a_live_slot_misses_and_uploads(engine):
    """A crash keyed on one request's token: the step fails twice, the
    supervisor bisects, and every probe that runs (a subset without the
    poisoned request, its live neighbours cleared) finds no carried
    entry it could take, uploads its positions, counts, mask and tables,
    and leaves the survivors' streams byte-identical."""
    ref = streams(serve(engine, overlap=False)[0])
    others = {t for s in (ref[0], ref[2]) for t in s}
    tok = next(t for t in ref[1][2:-1] if t not in others)
    plan = FaultPlan(seed=0)
    plan.on(faults.GENERATION_DECODE_STEP, mode="error", error=RuntimeError("poisoned-input crash"),
            when=lambda v: bool((np.asarray(v[0]) == tok).any()))
    probes, real = [], engine.decode

    def spy(tokens, positions, tables, active, *rest):
        before, carried = dict(engine.uploads), [name in engine._staged for name in CARRIED]
        try:
            return real(tokens, positions, tables, active, *rest)
        finally:
            probes.append((int(active.sum()), carried, grown(engine, before)))

    engine.decode = spy
    try:
        handles, sched = serve(engine, overlap=False, plan=plan, recovery=NO_SLEEP)
    finally:
        del engine.decode
    with pytest.raises(RuntimeError, match="poisoned-input crash"):
        handles[1].result(timeout=0)
    # (over convolution layers too: a probe puts the slots' state back, which it shifts where K/V is rewritten)
    assert [handles[0].result(timeout=0), handles[2].result(timeout=0)] == [ref[0], ref[2]]
    assert sched.recovery_stats.quarantined == 1 and engine.resets == 0
    cleared = [(carried, g) for n, carried, g in probes if n < 3 and g["carried_misses_total"]]
    assert cleared, probes
    assert cleared[0][0] == [False, False]  # the failed step dropped them; a later probe finds the first one's, and they differ
    for _, g in cleared:
        assert g["uploads_total"] >= 4 and g["carried_hits_total"] == 0


@pytest.mark.parametrize("overlap", [False, True], ids=["sequential", "pipelined"])
def test_a_fault_at_the_decode_step_drops_the_carried_entries_and_the_retry_is_exact(engine, overlap):
    ref = streams(serve(engine, overlap=overlap)[0])
    seen = []
    plan = FaultPlan(seed=0)
    plan.on(faults.GENERATION_DECODE_STEP, mode="error", error=TransientDeviceError, nth=(5,))
    real = engine._decode_args

    def spy(*args, **kw):
        seen.append([name in engine._staged for name in CARRIED])
        return real(*args, **kw)

    engine._decode_args = spy
    try:
        before = engine.resets
        handles, sched = serve(engine, overlap=overlap, plan=plan, retry=RetryPolicy(max_attempts=3, sleep=lambda _s: None))
    finally:
        del engine._decode_args
    assert plan.fired(faults.GENERATION_DECODE_STEP) == 1
    assert streams(handles) == ref and engine.resets == before
    # the call that raised assembled nothing; the one that followed it, and no other, found nothing carried
    assert seen.count([False, False]) == 1 and seen.count([True, True]) == len(seen) - 1
    assert 0 < seen.index([False, False]) < len(seen) - 1


def test_a_reset_drops_the_carried_entries_and_the_replayed_stream_is_exact(engine):
    ref = streams(serve(engine, overlap=True)[0])
    assert all(name in engine._staged for name in CARRIED)
    uploaded = {name: engine._staged[name][1] for name in engine._staged if name not in CARRIED}
    engine.reset()
    assert not any(name in engine._staged for name in CARRIED)
    assert all(engine._staged[name][1] is dev for name, dev in uploaded.items())  # an upload is no program's result: it stays
    # a hard crash twice: retry, bisection blames everyone, reset and journal replay
    plan = FaultPlan(seed=0)
    plan.on(faults.GENERATION_DECODE_STEP, mode="error", error=RuntimeError("device crash"), nth=(4, 5))
    before = engine.resets
    handles, sched = serve(engine, overlap=True, plan=plan, recovery=NO_SLEEP)
    assert streams(handles) == ref
    assert engine.resets > before and sched.recovery_stats.recoveries >= 1


def test_the_blocking_call_and_its_two_halves_are_one_step(engine):
    """``decode(x)`` is ``consume_decode(decode_async(x))`` with the
    dispatched hook between: from the same state the same tokens, blame
    vector, cache contents and phases (contiguous in the blocking call:
    their sum is the whole call), and a fault raised at either leaves
    no carried entry behind."""
    sched = ContinuousBatchingScheduler(engine, overlap=False)
    handles = [sched.submit(list(p), GREEDY) for p in PROMPTS]
    for _ in range(3):
        sched.step()
    x = sched._collect_slots(sorted(sched._running.values(), key=lambda s: s.slot))
    cache = engine.cache
    k, v, state, counts = cache.k, cache.v, dict(cache.state), engine.expert_counts

    def run(call):
        cache.update(k, v, **state)  # the engine donates nothing: the step's inputs are whole
        engine.expert_counts = counts
        engine._drop_carried()
        phases = dict(engine.phase_time_s["decode"])
        tokens = call()
        assert all(name in engine._staged for name in CARRIED)
        grew = {name: s - phases[name] for name, s in engine.phase_time_s["decode"].items()}
        return tokens, engine.last_finite, [np.asarray(a) for a in (cache.k, cache.v, *cache.state.values())], grew

    hooked = []
    hook, engine.on_dispatched = engine.on_dispatched, lambda: hooked.append(engine.step_counts["decode"])
    try:
        before = engine.step_counts["decode"]
        blocking = run(lambda: engine.decode(*x))
        spans = {name: (t0, t1) for name, t0, t1 in engine.last_step_spans}
        halves = run(lambda: engine.consume_decode(engine.decode_async(*x)))
    finally:
        engine.on_dispatched = hook
    assert hooked == [before + 1]  # the blocking call's dispatch, and not the other's
    for a, b in zip(blocking[:2], halves[:2]):
        assert np.array_equal(a, b)
    assert len(blocking[2]) == len(halves[2]) >= 2 and all(np.array_equal(a, b) for a, b in zip(blocking[2], halves[2]))
    assert set(blocking[3]) == set(halves[3]) == {"dispatch", "execute", "readback"}
    assert all(s > 0 for s in blocking[3].values()) and all(s > 0 for s in halves[3].values())
    assert set(spans) == {"dispatch", "post", "block", "execute", "readback", "account"} and spans["block"] == spans["execute"]
    assert sum(blocking[3].values()) == pytest.approx(spans["readback"][1] - spans["dispatch"][0])
    assert [name for name, _, _ in engine.last_step_children] == []  # a handle's went with it
    plan = FaultPlan(seed=0).on(faults.GENERATION_DECODE_STEP, mode="error", error=TransientDeviceError)
    for call in (engine.decode, engine.decode_async):
        run(lambda: engine.decode(*x))
        plan.install()
        try:
            with pytest.raises(TransientDeviceError):
                call(*x)
        finally:
            plan.remove()
        assert not any(name in engine._staged for name in CARRIED)
    # the streams go on from the state the step found, as if nothing had run
    cache.update(k, v, **state)
    engine.expert_counts = counts
    while any(not h.done() for h in handles):
        assert sched.step()
    assert streams(handles) == streams(serve(engine, overlap=False)[0])
