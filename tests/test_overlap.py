"""Overlapped decode (ISSUE 13): the two-deep host/device software
pipeline with double-buffered readback, in-jit sampling keys, and
deterministic frontier drain.

Coverage (the ISSUE acceptance matrix):
  * exactness matrix — greedy / seeded temperature / speculative token
    streams are byte-identical with the pipeline on vs off, across
    block and bucket boundaries
  * pipeline drain — EOS/finish, preemption pressure, quarantine, and
    expiry all drain the frontier deterministically; final state is
    sequential-identical
  * crash mid-flight — an injected failure on a pipelined step (at
    dispatch or at the async readback) recovers through the supervisor
    with byte-identical streams; whole-batch NaN journal-replays exactly
  * watchdog heartbeat semantics — dispatch AND completion stamps: a
    one-step-deep pipeline at long execute times never trips the
    watchdog, while a genuinely wedged in-flight step still does
  * device-resident staging — zero added retraces with the pipeline on
    (decode compiles exactly once; ProgramRegistry-blamed retraces
    stay zero), and the cache-donating engine configuration stays exact
  * steptrace lanes — pipelined captures genuinely diverge: an execute
    span may begin before its iteration (it started during the previous
    one), the sequential block==execute mirror is broken
  * a full pool is not pressure (ISSUE 30) — with the prefix cache on
    and the free list empty, the pipeline's block growth evicts an
    unreferenced entry with a step in flight (dropping it, or reading
    it out to a host tier with room) and stays pipelined; only a pool
    with nothing left to evict drains for pressure; the ``pipeline``
    section of /v2/stats counts all of it
  * the streams are woken where the thread parks (ISSUE 38) — a step
    consumed with its successor in flight is bookkept at once and its
    tokens are put on their streams' queues when the frontier is next
    consumed or discarded: every stream reads the sequential
    scheduler's tokens, in order and whole, before its end or its
    error; nothing is pending when nothing is in flight (a drain, a
    crash with reset and replay, a shutdown); the deferral and the
    drop's seconds are counted
"""
import contextlib
import dataclasses

import jax
import numpy as np
import pytest

from flexflow_tpu.generation import (
    ContinuousBatchingScheduler,
    GenerationEngine,
    SamplingParams,
    SpeculationConfig,
    init_decoder_params,
)
from flexflow_tpu.generation.cache import CacheConfig
from flexflow_tpu.generation.recovery import (
    PoisonedRequestError,
    RecoveryPolicy,
    WatchdogPolicy,
)
from flexflow_tpu.models.transformer import TransformerConfig
from flexflow_tpu.runtime.faults import FaultInjected, FaultPlan, TransientDeviceError
from flexflow_tpu.serving.resilience import RetryPolicy

pytestmark = pytest.mark.generation

CFG = TransformerConfig(
    num_layers=1, hidden_size=32, num_heads=2, ff_size=128,
    seq_length=64, vocab_size=64, causal=True,
)
BLOCK = 8
BUCKETS = (8, 16, 32, 64)


@pytest.fixture(scope="module")
def decoder_params():
    return init_decoder_params(jax.random.key(0), CFG)


class FakeClock:
    def __init__(self):
        self.t = 1000.0

    def __call__(self):
        return self.t

    def advance(self, s):
        self.t += s


def make_engine(decoder_params, num_blocks=40, slots=3, **kw):
    cache = CacheConfig(
        num_layers=CFG.num_layers, num_heads=CFG.num_heads,
        head_dim=CFG.hidden_size // CFG.num_heads,
        block_size=BLOCK, num_blocks=num_blocks,
    )
    kw.setdefault("prefix_cache", False)
    return GenerationEngine(
        decoder_params, CFG, cache_config=cache, max_batch_slots=slots,
        prompt_buckets=BUCKETS, **kw,
    )


def run_stream(decoder_params, prompts, sampling, *, overlap, spec=None,
               num_blocks=40, slots=3, plan=None, engine_kw=None,
               sched_kw=None):
    eng = make_engine(decoder_params, num_blocks=num_blocks, slots=slots,
                      **(engine_kw or {}))
    sched = ContinuousBatchingScheduler(eng, overlap=overlap, **(sched_kw or {}))
    ctx = plan.active() if plan is not None else contextlib.nullcontext()
    with ctx:
        handles = [sched.submit(p, sampling, speculation=spec) for p in prompts]
        steps = 0
        while any(not h.done() for h in handles):
            if not sched.step():
                break
            steps += 1
            assert steps < 5000, "scheduler failed to converge"
    return [h.result(timeout=0) for h in handles], eng, sched


# ------------------------------------------------------ exactness matrix
# prompts straddle bucket boundaries (7/8, 15/16/17) and max_new crosses
# block boundaries (cached_len passes multiples of BLOCK mid-stream)
MATRIX_PROMPTS = [
    [1, 2, 3, 4, 5, 6, 7],            # bucket edge (8)
    [9, 8, 7, 6, 5, 4, 3, 2],         # exactly one bucket
    list(range(11, 26)),              # 15: just under bucket 16
    list(range(30, 47)),              # 17: just over bucket 16
]


@pytest.mark.parametrize(
    "sampling",
    [
        SamplingParams(max_new_tokens=14),                                # greedy
        SamplingParams(max_new_tokens=14, temperature=0.8, top_k=8, seed=7),
        SamplingParams(max_new_tokens=11, temperature=0.5, seed=123),
    ],
    ids=["greedy", "temp_topk", "temp"],
)
def test_overlap_exactness_matrix(decoder_params, sampling):
    off, eng_off, _ = run_stream(
        decoder_params, MATRIX_PROMPTS, sampling, overlap=False
    )
    on, eng_on, sched_on = run_stream(
        decoder_params, MATRIX_PROMPTS, sampling, overlap=True
    )
    assert on == off
    assert sched_on.pipe_dispatches > 0, "pipeline never engaged"
    # staging + carry added zero retraces: ONE decode compile, and the
    # registry blamed nothing
    assert eng_on.trace_counts["decode"] == 1
    assert eng_on.recompiles() == {}
    assert eng_on.programs.total_retraces() == 0


def test_overlap_exactness_speculative(decoder_params):
    """Speculative streams are byte-identical with overlap on/off (the
    verify path is sequential by design — drafting is host-data-
    dependent — so the pipeline must drain before any verify step)."""
    spec = SpeculationConfig(k=3, method="ngram")
    prompts = [[1, 2, 3] * 6, [4, 5] * 8, [7, 8, 9, 7, 8, 9, 7, 8, 9]]
    sampling = SamplingParams(max_new_tokens=18)
    off, _, _ = run_stream(decoder_params, prompts, sampling, overlap=False,
                           spec=spec)
    on, eng_on, sched_on = run_stream(decoder_params, prompts, sampling,
                                      overlap=True, spec=spec)
    assert on == off
    assert eng_on.trace_counts["verify"] == 1


@pytest.mark.slow
def test_overlap_mixed_plain_and_speculative(decoder_params):
    """A batch mixing plain and speculating requests stays exact: the
    speculating request forces the sequential verify path for everyone
    (nonsteady drain), plain-only phases pipeline again after it
    finishes."""
    spec = SpeculationConfig(k=3, method="ngram")
    sampling = SamplingParams(max_new_tokens=16)

    def run(overlap):
        eng = make_engine(decoder_params)
        sched = ContinuousBatchingScheduler(eng, overlap=overlap)
        h1 = sched.submit([1, 2, 3] * 5, SamplingParams(max_new_tokens=6),
                          speculation=spec)
        h2 = sched.submit([11, 12, 13, 14], sampling)
        steps = 0
        while not (h1.done() and h2.done()):
            if not sched.step():
                break
            steps += 1
            assert steps < 2000
        return [h1.result(0), h2.result(0)], sched

    off, _ = run(False)
    on, sched_on = run(True)
    assert on == off
    # after the speculating stream finished, the plain one pipelined
    assert sched_on.pipe_dispatches > 0


# ---------------------------------------------------------------- drains
def test_pipeline_drains_on_eos(decoder_params):
    sampling = SamplingParams(max_new_tokens=24)
    base, _, _ = run_stream(decoder_params, MATRIX_PROMPTS, sampling,
                            overlap=False)
    # pick an EOS token that occurs mid-stream (index >= 3) but never
    # in any stream's first tokens: it must fire while the pipeline is
    # live, not at an admission prefill (the streams depend on jax PRNG
    # config, so the choice is made in-environment, not hardcoded)
    early = {t for o in base for t in o[:3]}
    cands = [t for o in base for t in o[3:] if t not in early]
    assert cands, "no usable mid-stream EOS token; widen the stream"
    eos = int(cands[0])
    samp = SamplingParams(max_new_tokens=24, eos_id=eos)
    off, _, _ = run_stream(decoder_params, MATRIX_PROMPTS, samp, overlap=False)
    on, _, sched_on = run_stream(decoder_params, MATRIX_PROMPTS, samp,
                                 overlap=True)
    assert on == off
    assert any(len(o) < 24 for o in on), "EOS never fired; test is vacuous"
    assert sched_on.pipe_drains.get("finish", 0) + sched_on.pipe_drains.get(
        "nonsteady", 0
    ) >= 1


def test_pipeline_drains_on_preempt(decoder_params):
    """Tight cache: growth fails mid-stream, the frontier drains on
    pressure, preempt-by-recompute resumes streams exactly."""
    sampling = SamplingParams(max_new_tokens=30)
    prompts = [[1, 2, 3, 4, 5, 6, 7, 8], [9, 10, 11, 12, 13, 14], [20, 21, 22, 23]]
    off, _, sched_off = run_stream(decoder_params, prompts, sampling,
                                   overlap=False, num_blocks=14)
    on, _, sched_on = run_stream(decoder_params, prompts, sampling,
                                 overlap=True, num_blocks=14)
    assert on == off
    assert sched_on.preemptions >= 1, "preemption never exercised"
    assert sched_on.pipe_drains.get("pressure", 0) >= 1


def test_pipeline_drains_on_quarantine(decoder_params):
    """Per-slot NaN poison with the pipeline on: the blamed request is
    quarantined alone, survivors keep byte-identical streams, and the
    tainted frontier is discarded."""
    sampling = SamplingParams(max_new_tokens=10)
    prompts = [[1, 2, 3, 4], [7, 8, 9], [11, 12, 13, 14, 15]]

    def run_collect(overlap):
        plan = FaultPlan(seed=0)
        plan.on(
            "generation.decode_step", mode="nan", nth=(3,),
            select=lambda v: np.asarray([True, False, False]),
        )
        eng = make_engine(decoder_params)
        sched = ContinuousBatchingScheduler(eng, overlap=overlap)
        with plan.active():
            handles = [sched.submit(p, sampling) for p in prompts]
            steps = 0
            while any(not h.done() for h in handles):
                if not sched.step():
                    break
                steps += 1
                assert steps < 2000
        outs = []
        for h in handles:
            try:
                outs.append(h.result(timeout=0))
            except PoisonedRequestError:
                outs.append("quarantined")
        return outs, sched

    off, _ = run_collect(False)
    on, sched_on = run_collect(True)
    assert on == off
    assert "quarantined" in on  # the poison really landed on one stream
    assert sched_on.recovery_stats.quarantined >= 1


@pytest.mark.slow
def test_pipeline_drain_on_cancel_and_deadline(decoder_params):
    """Cancel mid-stream with the pipeline live: the frontier drains on
    the nonsteady sweep and the remaining streams finish exactly."""
    sampling = SamplingParams(max_new_tokens=20)
    eng = make_engine(decoder_params)
    sched = ContinuousBatchingScheduler(eng, overlap=True)
    h1 = sched.submit([1, 2, 3, 4, 5], sampling)
    h2 = sched.submit([9, 8, 7], sampling)
    for _ in range(6):
        sched.step()
    h1.cancel()
    steps = 0
    while not (h1.done() and h2.done()):
        if not sched.step():
            break
        steps += 1
        assert steps < 2000
    with pytest.raises(Exception):
        h1.result(timeout=0)
    ref, _, _ = run_stream(decoder_params, [[9, 8, 7]], sampling, overlap=False)
    assert h2.result(timeout=0) == ref[0]
    assert eng.allocator.num_free == eng.allocator.num_total


# ----------------------------------------------------- crash mid-flight
def test_pipelined_transient_fault_is_invisible(decoder_params):
    sampling = SamplingParams(max_new_tokens=12)
    off, _, _ = run_stream(decoder_params, MATRIX_PROMPTS, sampling,
                           overlap=False)
    plan = FaultPlan(seed=0)
    plan.on("generation.decode_step", mode="error",
            error=TransientDeviceError, nth=(5,))
    on, eng, sched = run_stream(
        decoder_params, MATRIX_PROMPTS, sampling, overlap=True, plan=plan,
        sched_kw={"retry": RetryPolicy(max_attempts=3, sleep=lambda _s: None)},
    )
    assert plan.fired("generation.decode_step") == 1
    assert on == off
    assert eng.resets == 0  # absorbed without an engine restart


def test_pipelined_hard_crash_journal_replays_exactly(decoder_params):
    sampling = SamplingParams(max_new_tokens=12)
    off, _, _ = run_stream(decoder_params, MATRIX_PROMPTS, sampling,
                           overlap=False)
    plan = FaultPlan(seed=0)
    plan.on("generation.decode_step", mode="error",
            error=RuntimeError("device crash"), nth=(4, 5))
    on, eng, sched = run_stream(
        decoder_params, MATRIX_PROMPTS, sampling, overlap=True, plan=plan,
        sched_kw={"recovery": RecoveryPolicy(sleep=lambda _s: None)},
    )
    assert on == off
    assert eng.resets >= 1
    assert sched.recovery_stats.recoveries >= 1


def test_async_readback_fault_recovers_exactly(decoder_params):
    """The new generation.async_readback site: an error at the pipeline
    consume discards the frontier and re-runs the step sequentially
    under the supervisor — byte-exact, quarantining nothing."""
    sampling = SamplingParams(max_new_tokens=12)
    off, _, _ = run_stream(decoder_params, MATRIX_PROMPTS, sampling,
                           overlap=False)
    plan = FaultPlan(seed=0)
    plan.on("generation.async_readback", mode="error",
            error=FaultInjected("readback lost"), nth=(2,))
    on, eng, sched = run_stream(
        decoder_params, MATRIX_PROMPTS, sampling, overlap=True, plan=plan,
        sched_kw={"recovery": RecoveryPolicy(sleep=lambda _s: None)},
    )
    assert plan.fired("generation.async_readback") == 1
    assert on == off
    assert sched.recovery_stats.quarantined == 0


def test_pipelined_whole_batch_nan_restarts_and_replays(decoder_params):
    sampling = SamplingParams(max_new_tokens=10)
    off, _, _ = run_stream(decoder_params, MATRIX_PROMPTS, sampling,
                           overlap=False)
    plan = FaultPlan(seed=0)
    plan.on("generation.decode_step", mode="nan", nth=(3,))
    on, eng, sched = run_stream(
        decoder_params, MATRIX_PROMPTS, sampling, overlap=True, plan=plan,
        sched_kw={"recovery": RecoveryPolicy(sleep=lambda _s: None)},
    )
    assert on == off
    assert eng.resets >= 1


# ------------------------------------------- watchdog heartbeat semantics
def test_watchdog_not_tripped_by_long_pipelined_steps(decoder_params):
    """Satellite 2 regression: the heartbeat is stamped at dispatch AND
    at completion, so an in-flight step's age is its OWN device time —
    a pipeline whose per-step execute approaches the stall timeout, run
    for many steps, must never trip (under the old stamp-once scheme
    the cumulative in-flight window would)."""
    clock = FakeClock()
    eng = make_engine(decoder_params)
    sched = ContinuousBatchingScheduler(
        eng, overlap=True, clock=clock,
        watchdog=WatchdogPolicy(enabled=True, stall_timeout_s=10.0),
    )
    sampling = SamplingParams(max_new_tokens=16)
    h = sched.submit([1, 2, 3, 4, 5], sampling)
    steps = 0
    while not h.done():
        if not sched.step():
            break
        # each step's device window stays under the timeout, but the
        # cumulative in-flight time across the stream far exceeds it
        clock.advance(6.0)
        sched.watchdog.check()
        steps += 1
        assert steps < 2000
    assert sched.recovery_stats.watchdog_trips == 0
    ref, _, _ = run_stream(decoder_params, [[1, 2, 3, 4, 5]], sampling,
                           overlap=False)
    assert h.result(timeout=0) == ref[0]


def test_watchdog_still_trips_on_wedged_inflight_step(decoder_params):
    """A genuinely outstanding in-flight step older than the stall
    timeout trips the watchdog; the late result is discarded and the
    stream journal-replays byte-exactly."""
    clock = FakeClock()
    eng = make_engine(decoder_params)
    sched = ContinuousBatchingScheduler(
        eng, overlap=True, clock=clock,
        watchdog=WatchdogPolicy(enabled=True, stall_timeout_s=10.0),
        recovery=RecoveryPolicy(sleep=lambda _s: None),
    )
    sampling = SamplingParams(max_new_tokens=12)
    h = sched.submit([1, 2, 3, 4, 5], sampling)
    # admit + warm the pipeline so a frontier is genuinely in flight
    for _ in range(3):
        sched.step()
    assert sched._pipe is not None, "pipeline did not engage"
    # the device never completes (from the watchdog's point of view):
    # the in-flight dispatch stamp ages past the stall timeout
    clock.advance(11.0)
    assert sched.watchdog.check() is True
    assert sched.recovery_stats.watchdog_trips == 1
    # the loop's next consume sees the stall flag, discards the late
    # result, and restarts + journal-replays
    steps = 0
    while not h.done():
        if not sched.step():
            break
        steps += 1
        assert steps < 2000
    assert eng.resets >= 1
    ref, _, _ = run_stream(decoder_params, [[1, 2, 3, 4, 5]], sampling,
                           overlap=False)
    assert h.result(timeout=0) == ref[0]


# ------------------------------------------------- staging and donation
def test_zero_added_retraces_and_staging_reuse(decoder_params):
    """Device-resident staging: a long pipelined stream compiles decode
    exactly once (ProgramRegistry retraces zero), and slot-constant
    args (tables/sampling) are re-uploaded only on composition change."""
    sampling = SamplingParams(max_new_tokens=24)
    on, eng, sched = run_stream(decoder_params, MATRIX_PROMPTS, sampling,
                                overlap=True)
    assert eng.trace_counts["decode"] == 1
    assert eng.programs.total_retraces() == 0
    assert eng.recompiles() == {}
    # staged entries exist for the slot-constant decode args
    assert {"decode.tables", "decode.temps", "decode.top_ks", "decode.seeds"} <= set(
        eng._staged
    )


def test_donating_engine_is_exact_and_stage_safe(decoder_params):
    """donate_cache=True (the accelerator default; opt-in on CPU): the
    decode/verify jits consume their cache inputs in place. Fault-free
    streams must be byte-identical to the non-donating engine, with
    zero added retraces."""
    sampling = SamplingParams(max_new_tokens=16)
    off, _, _ = run_stream(decoder_params, MATRIX_PROMPTS, sampling,
                           overlap=False)
    on, eng, _ = run_stream(
        decoder_params, MATRIX_PROMPTS, sampling, overlap=True,
        engine_kw={"donate_cache": True},
    )
    assert eng.donate is True
    assert on == off
    assert eng.trace_counts["decode"] == 1
    # speculative + donation (verify jit donates too)
    spec = SpeculationConfig(k=3, method="ngram")
    prompts = [[1, 2, 3] * 6, [4, 5] * 8]
    s_off, _, _ = run_stream(decoder_params, prompts, sampling, overlap=False,
                             spec=spec)
    s_on, eng2, _ = run_stream(
        decoder_params, prompts, sampling, overlap=True, spec=spec,
        engine_kw={"donate_cache": True},
    )
    assert s_on == s_off


# --------------------------------------------------- steptrace divergence
def test_pipelined_lanes_genuinely_diverge(decoder_params):
    """Under overlap the captured two-lane timeline stops mirroring:
    some decode capture holds an execute span that BEGAN before the
    iteration's own window (it was dispatched in the previous
    iteration), which the sequential shape (block == execute, both
    inside the step) never produces."""
    eng = make_engine(decoder_params)
    sched = ContinuousBatchingScheduler(eng, overlap=True)
    sched.anatomy.arm_capture(64)
    sampling = SamplingParams(max_new_tokens=16)
    handles = [sched.submit(p, sampling) for p in MATRIX_PROMPTS[:2]]
    steps = 0
    while any(not h.done() for h in handles):
        if not sched.step():
            break
        steps += 1
        assert steps < 2000
    caps = [c for c in sched.anatomy.captured_steps() if c["kind"] == "decode"]
    assert caps
    diverged = False
    for cap in caps:
        block = sorted(s[1:] for s in cap["spans"] if s[0] == "block")
        execute = sorted(s[1:] for s in cap["spans"] if s[0] == "execute")
        if execute and (execute != block or any(
            s0 < cap["t_start"] - 1e-9 for s0, _ in execute
        )):
            diverged = True
    assert diverged, "pipelined captures still mirror block==execute"


# ------------------------------------------- a full pool is not pressure
# 24 unshared requests over 6 slots, prompts 9-29 and replies 12-29 (so
# streams cross block boundaries and finish out of step), prefix cache
# ON: every finished stream's full blocks stay in the index, the free
# list empties, and every further block is an eviction
_POOL_RNG = np.random.default_rng(5)
POOL_PROMPTS = [
    [int(x) for x in _POOL_RNG.integers(1, 64, size=int(n))]
    for n in _POOL_RNG.integers(9, 30, size=24)
]
POOL_NEW = [int(n) for n in _POOL_RNG.integers(12, 30, size=24)]
POOL_SLOTS = 6
HOST_FULL = 0      # host tier at its budget: a victim is dropped, nothing read
HOST_ROOM = None   # the default budget: a victim's content is read out


def run_full_pool(decoder_params, *, overlap, num_blocks, host_cache_bytes,
                  temperature=0.0, sched_kw=None, each_step=None):
    """Drive POOL_PROMPTS to completion; returns (streams, engine,
    scheduler, reclaim log). The log has one row per
    ``engine.reclaim_cached`` call: (the heartbeat sequence of the step
    in flight or None, the heartbeat before, the heartbeat after, blocks
    freed)."""
    eng = make_engine(decoder_params, num_blocks=num_blocks, slots=POOL_SLOTS,
                      prefix_cache=True, host_cache_bytes=host_cache_bytes)
    sched = ContinuousBatchingScheduler(eng, overlap=overlap, **(sched_kw or {}))
    log = []
    reclaim = eng.reclaim_cached

    def logged(n):
        f, before = sched._pipe, sched._heartbeat
        freed = reclaim(n)
        log.append((f and f.hb_seq, before, sched._heartbeat, freed))
        return freed

    eng.reclaim_cached = logged
    handles = [
        sched.submit(p, SamplingParams(
            max_new_tokens=n, temperature=temperature,
            top_k=8 if temperature else 0, seed=7 + i,
        ))
        for i, (p, n) in enumerate(zip(POOL_PROMPTS, POOL_NEW))
    ]
    steps = 0
    while any(not h.done() for h in handles):
        if not sched.step():
            break
        if each_step is not None:
            each_step(sched)
        steps += 1
        assert steps < 5000, "scheduler failed to converge"
    return [h.result(timeout=0) for h in handles], eng, sched, log


@pytest.mark.parametrize("temperature", [0.0, 0.8], ids=["greedy", "temp_topk"])
def test_full_pool_with_evictable_entries_stays_pipelined(decoder_params, temperature):
    """(a) The free list empties, evictable entries never run out: no
    drain for pressure, most decode steps pipelined, streams exact."""
    kw = dict(num_blocks=32, host_cache_bytes=HOST_FULL, temperature=temperature)
    off, _, _, _ = run_full_pool(decoder_params, overlap=False, **kw)
    seen = []
    on, eng, sched, log = run_full_pool(
        decoder_params, overlap=True, each_step=lambda sched: staged_as_the_sequential_loop_would(sched, seen), **kw)
    assert on == off
    assert len(set(seen)) >= 10 and len(seen) - len(set(seen)) >= 10  # compositions that changed, and steps that bumped in place
    # the index never ran dry: every reclaim came back with a block
    assert sched.preemptions == 0 and all(freed >= 1 for *_, freed in log)
    assert sched.pipe_drains["pressure"] == 0
    assert sched.pipe_reclaims >= 10, "the pipeline's growth never had to evict"
    assert any(seq is not None for seq, *_ in log), "no eviction with a step in flight"
    assert 2 * sched.pipe_dispatches >= eng.step_counts["decode"]
    assert eng.prefix_cache.swaps_out_total == 0  # tier at its budget: dropped
    assert eng.trace_counts["decode"] == 1 and eng.recompiles() == {}


def test_full_pool_reads_a_victim_out_behind_the_step_in_flight(decoder_params):
    """(b) Host tier with room: the eviction READS the victim's block
    with a step in flight, queued behind it on the step's own heartbeat
    stamp: that stamp is neither cleared nor re-sequenced (``_stamped``
    would do both), the watchdog sees no stall, nothing recovers."""
    clock = FakeClock()

    def tick(sched):
        # under the stall timeout a step, far over it across the run
        clock.advance(6.0)
        sched.watchdog.check()

    kw = dict(num_blocks=32, host_cache_bytes=HOST_ROOM)
    off, _, _, _ = run_full_pool(decoder_params, overlap=False, **kw)
    on, eng, sched, log = run_full_pool(
        decoder_params, overlap=True, each_step=tick, sched_kw=dict(
            clock=clock, watchdog=WatchdogPolicy(enabled=True, stall_timeout_s=10.0),
        ), **kw)
    assert on == off
    behind = [row for row in log if row[0] is not None]
    assert behind, "no eviction with a step in flight"
    assert all(before == after and before[0] == seq for seq, before, after, _ in behind)
    # without a step in flight a reclaim runs under a stamp of its own
    assert all(before is not None for seq, before, _, _ in log if seq is None)
    assert eng.prefix_cache.swaps_out_total == sum(freed for *_, freed in log) > 0  # every victim read out
    assert sched.pipe_drains["pressure"] == 0 and sched.pipe_reclaims >= len(behind)
    rs = sched.recovery_stats
    assert rs.watchdog_trips == 0 and rs.recoveries == 0 and rs.step_retries == 0
    assert eng.resets == 0


def test_full_pool_with_nothing_left_to_evict_drains_and_preempts(decoder_params):
    """(c) A pool so small that the evictable entries run out
    mid-stream: the pipeline evicts while it can, then drains for
    pressure and the sequential body preempts by recompute. Exact."""
    kw = dict(num_blocks=28, host_cache_bytes=HOST_FULL)
    off, _, sched_off, _ = run_full_pool(decoder_params, overlap=False, **kw)
    on, eng, sched, log = run_full_pool(decoder_params, overlap=True, **kw)
    assert on == off
    assert sched_off.preemptions >= 1 and sched.preemptions >= 1
    assert sched.pipe_reclaims >= 1
    assert sched.pipe_drains["pressure"] >= 1
    assert any(freed == 0 for *_, freed in log), "the index never ran dry"


def test_pipeline_section_of_stats_counts_the_loop_s_decisions(decoder_params):
    """(d) ``/v2/stats`` ``pipeline``: monotone totals, pipelined steps
    a part of all decode steps, the reasons adding up to the drains."""
    drained, snaps = [0], []

    def each_step(sched):
        if not snaps:  # after the first step (an admission: nothing in flight yet)
            drain = sched._drain_frontier

            def counted(reason):  # an independent count of the real drains
                drained[0] += sched._pipe is not None
                drain(reason)

            sched._drain_frontier = counted
        snaps.append(sched.stats.snapshot()["pipeline"])

    _, eng, sched, log = run_full_pool(
        decoder_params, overlap=True, num_blocks=28, host_cache_bytes=HOST_FULL,
        each_step=each_step)
    flat = lambda p: [p["decode_steps_total"], p["pipelined_steps_total"], p["reclaims_total"],
                      *(p["drains_total"][r] for r in ("nonsteady", "finish", "pressure", "idle"))]  # noqa: E731
    assert all(a <= b for p, q in zip(snaps, snaps[1:]) for a, b in zip(flat(p), flat(q)))
    assert all(0 <= p["pipelined_steps_total"] <= p["decode_steps_total"] for p in snaps)
    last = sched.stats.snapshot()["pipeline"]
    assert set(last) == {"decode_steps_total", "block_steps_total", "pipelined_steps_total", "reclaims_total", "drains_total",
                         "emits_deferred_total", "emits_pending", "release_wait_total_s"}
    assert last["block_steps_total"] == 0  # (a block-diffusion engine's: tests/test_sdar.py)
    assert set(last["drains_total"]) == {"nonsteady", "finish", "pressure", "idle"}
    assert sum(last["drains_total"].values()) == drained[0] > 0
    assert last["drains_total"]["pressure"] >= 1 and last["drains_total"]["finish"] >= 1
    assert last["decode_steps_total"] == eng.step_counts["decode"]
    assert 0 < last["pipelined_steps_total"] == sched.pipe_dispatches
    assert 0 < last["reclaims_total"] == sched.pipe_reclaims <= sum(freed for *_, freed in log)
    # a sequential scheduler has the section too, and pipelines nothing
    _, eng_off, sched_off, _ = run_full_pool(
        decoder_params, overlap=False, num_blocks=28, host_cache_bytes=HOST_FULL)
    off = sched_off.stats.snapshot()["pipeline"]
    assert off["decode_steps_total"] == eng_off.step_counts["decode"] > 0
    assert off["pipelined_steps_total"] == off["reclaims_total"] == 0 == sum(off["drains_total"].values())


# ------------------- one staging and one block growth for both loops (ISSUE 44)
def staged_as_the_sequential_loop_would(sched, seen):
    """(An ``each_step`` of ``run_full_pool``.) After an iteration that left a step in flight, the step before it
    has been bookkept: the arrays the pipeline staged for the one in
    flight (over that covered set, rebuilt or bumped in place) are what
    the sequential staging gives now, one step later."""
    f = sched._pipe
    if f is None:
        return
    _last, *sequential = sched._collect_slots(f.states)
    assert len(f.slots) == len(sequential) == 7
    assert all(np.array_equal(a, b) and a.dtype == b.dtype for a, b in zip(sequential, f.slots))
    seen.append(f.sig)


def grow_from_a_full_pool(decoder_params, overlap):
    """One stream grows by a block out of a pool whose free list is
    empty and whose index holds ONE unreferenced prefix; returns the
    block it took, the reclaim's heartbeat row, the pipeline's count and
    the stream."""
    eng = make_engine(decoder_params, num_blocks=16, slots=2, prefix_cache=True, host_cache_bytes=HOST_FULL)
    sched = ContinuousBatchingScheduler(eng, overlap=overlap)
    first = sched.submit(POOL_PROMPTS[0], SamplingParams(max_new_tokens=4))
    while not first.done():
        sched.step()
    h = sched.submit([3, 1, 4, 1, 5], SamplingParams(max_new_tokens=12))
    while not sched._running or (overlap and sched._pipe is None):
        sched.step()
    state, = sched._running.values()
    assert len(state.blocks) == 1 and eng.prefix_cache.evictable_blocks >= 1
    held = eng.allocator.allocate(eng.allocator.num_free)
    while eng.prefix_cache.evictable_blocks > 1:
        held += eng.allocator.allocate(eng.reclaim_cached(1))
    log, reclaim = [], eng.reclaim_cached

    def logged(n):
        f, before = sched._pipe, sched._heartbeat
        freed = reclaim(n)
        log.append((f is not None, before is not None and (before[0], sched._heartbeat)))
        return freed

    eng.reclaim_cached = logged
    while len(state.blocks) == 1:
        sched.step()
    block = state.blocks[1]
    assert eng.prefix_cache.evictable_blocks == 0 and eng.allocator.num_free == 0
    eng.allocator.free(held)
    while not h.done():
        sched.step()
    return block, log, sched.pipe_reclaims, h.result(timeout=0)


def test_both_loops_take_the_same_block_from_a_full_pool_each_under_its_own_stamp(decoder_params):
    block, (row,), reclaims, stream = grow_from_a_full_pool(decoder_params, overlap=False)
    assert row[0] is False and row[1] and row[1][1] is not None and reclaims == 0  # a stamp of its own, and not the pipeline's count
    block_on, (row,), reclaims, stream_on = grow_from_a_full_pool(decoder_params, overlap=True)
    # behind the step in flight, on ITS stamp, which stands after the reclaim as before it
    assert row[0] is True and row[1] and row[1][1][0] == row[1][0] and reclaims == 1
    assert block_on == block and stream_on == stream


# ------------------------------------- the streams are woken late (ISSUE 38)
LATE_PROMPTS = [[(7 * i + j) % 60 + 1 for j in range(3 + i % 6)] for i in range(18)]


def streamed(handle):
    """What a stream's reader gets, read after the fact: the tokens on
    the handle's queue up to its end; its error ends the list."""
    got = []
    try:
        for tok in handle.tokens(timeout=0):
            got.append(tok)
    except Exception as e:  # noqa: BLE001 - the stream's own failure, kept as its last item
        got.append(type(e).__name__)
    return got


def submit_late(sched, sampling, prompts=LATE_PROMPTS):
    """Budgets of 0-9 fewer tokens, so that streams finish beside running ones."""
    return [sched.submit(p, dataclasses.replace(sampling, max_new_tokens=sampling.max_new_tokens - 3 * (i % 4)))
            for i, p in enumerate(prompts)]


def run_late(decoder_params, *, overlap, sampling, plan=None, sched_kw=None, each_step=None):
    eng = make_engine(decoder_params, num_blocks=60, slots=3)
    sched = ContinuousBatchingScheduler(eng, overlap=overlap, **(sched_kw or {}))
    with (plan.active() if plan is not None else contextlib.nullcontext()):
        handles = submit_late(sched, sampling)
        steps = 0
        while any(not h.done() for h in handles):
            if not sched.step():
                break
            steps += 1
            if each_step is not None:
                each_step(sched)
            assert steps < 5000
    return handles, eng, sched


@pytest.mark.parametrize("sampling", [
    SamplingParams(max_new_tokens=46),
    SamplingParams(max_new_tokens=43, temperature=0.8, top_k=8, seed=11),
], ids=["greedy", "temp_topk"])
def test_streams_read_the_sequential_tokens_with_the_emission_deferred(decoder_params, sampling):
    """Eighteen requests through three slots, 34-46 tokens each: over 200
    pipelined steps, streams finishing beside running ones, admissions.
    Every stream's reader gets what the sequential scheduler's gets,
    token for token, and what ``result()`` holds."""
    pending = []

    def each_step(sched):
        p = sched.stats.snapshot()["pipeline"]["emits_pending"]
        # held only while a dispatch is sure to follow: a step in flight, or streams still running
        assert p == len(sched._late) and (p == 0 or sched._pipe is not None or sched._running)
        pending.append(p)

    off, _, sched_off = run_late(decoder_params, overlap=False, sampling=sampling)
    on, eng, sched = run_late(decoder_params, overlap=True, sampling=sampling, each_step=each_step)
    assert [streamed(h) for h in on] == [streamed(h) for h in off] == [h.result(timeout=0) for h in off]
    assert [h.result(timeout=0) for h in on] == [h.result(timeout=0) for h in off]
    stats = sched.stats.snapshot()["pipeline"]
    assert stats["pipelined_steps_total"] >= 200 and sum(stats["drains_total"].values()) >= 6
    assert max(pending) > 0 and pending[-1] == 0 == stats["emits_pending"]
    # a prefill's first token goes out at once, every decode step's tokens late: where the thread parks
    # (counted), or with the stream's end when that comes first (its last token at most)
    total = sum(len(h.result(timeout=0)) for h in on)
    assert total - 2 * len(on) <= stats["emits_deferred_total"] <= total - len(on)
    assert stats["release_wait_total_s"] > 0.0
    # the sequential scheduler wakes its streams late too (after the next step's dispatch), and drops no handle
    quiet = sched_off.stats.snapshot()["pipeline"]
    assert total - 2 * len(on) <= quiet["emits_deferred_total"] <= total - len(on) and quiet["emits_pending"] == 0
    assert quiet["release_wait_total_s"] == 0.0


@pytest.mark.parametrize("fault", ["crash", "readback", "whole_batch_nan", "one_slot_nan"])
def test_a_failure_in_flight_owes_no_stream_a_token(decoder_params, fault):
    """The step before a failed one was bookkept: its tokens reach their
    streams before the restart, the re-run or the quarantine, and after
    the recovery nothing is pending. Each stream reads what its
    ``result()`` holds; the quarantined one its tokens, then its error."""
    plan = FaultPlan(seed=0)
    if fault == "crash":
        plan.on("generation.decode_step", mode="error", error=RuntimeError("device crash"), nth=(6, 7))
    elif fault == "readback":
        plan.on("generation.async_readback", mode="error", error=FaultInjected("readback lost"), nth=(4,))
    elif fault == "whole_batch_nan":
        plan.on("generation.decode_step", mode="nan", nth=(5,))
    else:
        plan.on("generation.decode_step", mode="nan", nth=(5,), select=lambda v: np.asarray([True, False, False]))
    sampling = SamplingParams(max_new_tokens=16)
    seen = []

    def each_step(sched):
        seen.append((len(sched._late), sched._pipe is not None or bool(sched._running)))

    handles, eng, sched = run_late(
        decoder_params, overlap=True, sampling=sampling, plan=plan, each_step=each_step,
        sched_kw={"recovery": RecoveryPolicy(sleep=lambda _s: None)})
    assert all(more_to_come or not late for late, more_to_come in seen)
    assert sched.stats.snapshot()["pipeline"]["emits_pending"] == 0 and sched._pipe is None
    assert sched.stats.snapshot()["pipeline"]["emits_deferred_total"] > 0
    off, _, _ = run_late(decoder_params, overlap=False, sampling=sampling, plan=None)
    poisoned = 0
    for h, ref in zip(handles, off):
        got = streamed(h)
        if got and got[-1] == "PoisonedRequestError":
            poisoned += 1
            assert got[:-1] == ref.result(timeout=0)[: len(got) - 1]  # its tokens first, then its error
        else:
            assert got == h.result(timeout=0) == ref.result(timeout=0)
    assert poisoned == (1 if fault == "one_slot_nan" else 0)
    if fault in ("crash", "whole_batch_nan"):
        assert eng.resets >= 1


def test_nothing_is_pending_after_a_shutdown_and_no_thread_is_left(decoder_params):
    """The threaded loop: streams read as they are served; a graceful
    stop ends with nothing in flight, nothing pending and the loop's
    thread gone; a hard stop with work in flight still gives each
    stream the tokens that were bookkept before its error."""
    import threading

    sampling = SamplingParams(max_new_tokens=30)
    ref, _, _ = run_late(decoder_params, overlap=False, sampling=sampling)
    before = {t.ident for t in threading.enumerate()}
    eng = make_engine(decoder_params, num_blocks=60, slots=3)
    eng.generate([LATE_PROMPTS[0]], SamplingParams(max_new_tokens=2))  # warm: no compile on the loop's thread
    eng.reset()
    sched = ContinuousBatchingScheduler(eng, overlap=True)
    sched.start()
    handles = submit_late(sched, sampling)
    got = [list(h.tokens(timeout=60)) for h in handles]  # read live, as a handler thread does
    sched.stop()
    assert got == [h.result(timeout=0) for h in ref]
    p = sched.stats.snapshot()["pipeline"]
    assert p["emits_pending"] == 0 and sched._pipe is None and p["emits_deferred_total"] > 0
    assert {t.ident for t in threading.enumerate() if t.is_alive()} <= before

    sched = ContinuousBatchingScheduler(make_engine(decoder_params, num_blocks=60, slots=3), overlap=True)
    handles = submit_late(sched, sampling, LATE_PROMPTS[:3])
    while sched._pipe is None or not sched._late:
        assert sched.step()
    owed = {id(h): h._held[-1] for h in sched._late}
    assert owed
    sched.stop(drain=False)
    assert sched._pipe is None and not sched._late
    for h in handles:
        got = streamed(h)
        assert got[-1] == "ShuttingDownError" and got[:-1] == h._request.generated
        assert id(h) not in owed or got[-2] == owed[id(h)]


@pytest.mark.parametrize("settle", ["finish", "fail", "emit"])
def test_a_handle_gives_its_held_tokens_before_whatever_comes_next(settle):
    """``_emit_later`` keeps a token off the queue; whoever settles the
    handle (the loop, the watchdog's thread) or emits at once after it
    puts the held ones out first, in order."""
    from flexflow_tpu.generation.scheduler import Request

    h = Request([1, 2, 3], SamplingParams(max_new_tokens=4)).handle
    h._emit(7)
    h._emit_later(8)
    h._emit_later(9)
    assert h._tokens.qsize() == 1 and h._held == [8, 9]
    if settle == "finish":
        h._finish([7, 8, 9])
        assert streamed(h) == [7, 8, 9] and h.result(timeout=0) == [7, 8, 9]
    elif settle == "fail":
        assert h._fail(TimeoutError("deadline"))
        assert streamed(h) == [7, 8, 9, "TimeoutError"]
    else:
        h._emit(10)
        assert h._held == [] and h._release_held() == 0
        h._finish([7, 8, 9, 10])
        assert streamed(h) == [7, 8, 9, 10]


def test_the_engine_reports_a_blocking_call_s_dispatch_and_not_a_pipelined_one(decoder_params):
    """``on_dispatched`` fires once a blocking call, after its program
    went to the device and before the wait (the scheduler wakes the
    streams there); ``decode_async`` leaves the moment to its caller."""
    def serve(overlap):
        eng = make_engine(decoder_params)
        sched = ContinuousBatchingScheduler(eng, overlap=overlap)
        assert eng.on_dispatched == sched._emit_late  # the scheduler that serves from the engine takes the hook
        seen = []

        def hook():
            seen.append(dict(eng.step_counts))
            sched._emit_late()

        eng.on_dispatched = hook
        h = sched.submit([5, 6, 7], SamplingParams(max_new_tokens=12))
        while not h.done():
            assert sched.step()
        assert streamed(h) == h.result(timeout=0) and len(h.result(timeout=0)) == 12
        return seen, eng, sched

    seen, eng, _ = serve(overlap=False)
    assert len(seen) == eng.step_counts["prefill"] + eng.step_counts["decode"] == 1 + 11
    assert seen[0]["prefill"] == 1 and seen[0]["decode"] == 0  # the count is the dispatched call's own
    seen, eng, sched = serve(overlap=True)
    # the admission's prefill reported; of the 11 decode steps only the sequential ones did
    assert sched.pipe_dispatches > 0 and len(seen) == 1 + eng.step_counts["decode"] - sched.pipe_dispatches
