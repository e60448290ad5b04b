"""Step-anatomy profiler tests (ISSUE 12, tier-1).

Acceptance criteria covered:
  * span nesting + conservation: a steady-state decode step's host
    spans are disjoint and sum (plus the gap) to the step wall within
    epsilon, with the device execute span mirroring the host block span
  * bubble-ratio / classification / overlap-headroom math is exact on
    synthetic timelines (virtual stamps — no clock involved)
  * capture-K bounds, re-arming, and ring eviction
  * the two-lane chrome trace schema (host tid 1 / device tid 2, real
    offsets)
  * anatomy disabled (observability=False) is inert AND the token
    streams are byte-identical
  * the engine's device_time_s split: dispatch/execute/readback accrue
    per kind, the old total is the derived sum, MFU divides by
    execute-only seconds, and the prometheus family renders
"""
import math

import jax
import pytest

from flexflow_tpu.generation import (
    ContinuousBatchingScheduler,
    GenerationEngine,
    SamplingParams,
    init_decoder_params,
)
from flexflow_tpu.models.transformer import TransformerConfig
from flexflow_tpu.obs import StepAnatomy, render_prometheus, validate_exposition
from flexflow_tpu.obs.steptrace import DEVICE_PHASES
from flexflow_tpu.serving.stats import ServingStats

pytestmark = pytest.mark.observability

CFG = TransformerConfig(
    num_layers=2, hidden_size=32, num_heads=4, ff_size=64,
    seq_length=64, vocab_size=50, causal=True,
)


@pytest.fixture(scope="module")
def decoder_params():
    return init_decoder_params(jax.random.key(0), CFG)


@pytest.fixture(scope="module")
def engine(decoder_params):
    return GenerationEngine(
        decoder_params, CFG, max_batch_slots=3, block_size=8,
        prompt_buckets=(8, 16, 32, 64),
    )


def _drive(sched, prompts, max_new=6):
    handles = [sched.submit(p, SamplingParams(max_new_tokens=max_new))
               for p in prompts]
    while any(not h.done() for h in handles):
        if not sched.step():
            break
    return [h.result(timeout=0) for h in handles]


# ------------------------------------------------------- synthetic math
def _step(an, kind="decode", dispatch=0.25, execute=1.0, host_extra=0.5,
          t0=0.0, tokens=1):
    """One synthetic step: dispatch, block/execute, then host_extra of
    bookkeeping — wall is exactly the sum (gap-free)."""
    spans = [
        ("dispatch", t0, t0 + dispatch),
        ("block", t0 + dispatch, t0 + dispatch + execute),
        ("execute", t0 + dispatch, t0 + dispatch + execute),
        ("bookkeep", t0 + dispatch + execute,
         t0 + dispatch + execute + host_extra),
    ]
    an.observe_step(kind, spans, t0, t0 + dispatch + execute + host_extra,
                    tokens=tokens)


def test_bubble_ratio_and_headroom_math_exact():
    an = StepAnatomy(enabled=True, min_steps=2)
    assert an.device_bubble_ratio() is None
    assert an.classification() == "unknown"
    # two identical steps: wall 2.0, execute 1.0 -> bubble exactly 0.5
    _step(an, dispatch=0.25, execute=1.0, host_extra=0.75, t0=0.0)
    _step(an, dispatch=0.25, execute=1.0, host_extra=0.75, t0=10.0)
    assert an.device_bubble_ratio() == pytest.approx(0.5)
    # threshold is >= 0.5 -> host_bound at exactly the boundary
    assert an.classification() == "host_bound"
    hr = an.overlap_headroom()
    # projected wall per step = max(execute, dispatch) = 1.0 vs 2.0
    assert hr["steps"] == 2 and hr["tokens"] == 2
    assert hr["measured_tokens_per_s"] == pytest.approx(2 / 4.0)
    assert hr["projected_tokens_per_s"] == pytest.approx(2 / 2.0)
    assert hr["projected_speedup"] == pytest.approx(2.0)
    assert hr["hidden_host_s"] == pytest.approx(2.0)
    # the bubble ratio without its clamp: hidden host s / step
    assert hr["host_s_per_hot_step"] == pytest.approx(1.0)


def test_device_bound_classification_and_dispatch_floor():
    an = StepAnatomy(enabled=True, min_steps=1)
    # device dominates: wall 4.5, execute 4.0 -> bubble 1/9, device-bound
    _step(an, dispatch=0.25, execute=4.0, host_extra=0.25)
    assert an.device_bubble_ratio() == pytest.approx(1 / 9)
    assert an.classification() == "device_bound"
    # fully host-bound window (execute ~ 0): projection floors at the
    # dispatch residue, not infinity
    an2 = StepAnatomy(enabled=True, min_steps=1)
    _step(an2, dispatch=0.5, execute=0.0, host_extra=0.5)
    hr = an2.overlap_headroom()
    assert an2.classification() == "host_bound"
    assert hr["projected_speedup"] == pytest.approx(2.0)  # 1.0 / 0.5
    assert math.isfinite(hr["projected_tokens_per_s"])


def test_handled_failure_steps_stay_out_of_hot_window():
    """A supervisor-handled failure iteration (hot=False) has no
    execute span and a retry-inflated wall: it must not poison the
    bubble/headroom window, though histograms still record it."""
    an = StepAnatomy(enabled=True, min_steps=1)
    _step(an, dispatch=0.25, execute=1.0, host_extra=0.25)  # healthy
    an.observe_step(
        "decode", [("dispatch", 0.0, 5.0)], 0.0, 5.0, tokens=0, hot=False
    )
    # window math unchanged by the failure sample
    assert an.device_bubble_ratio() == pytest.approx(1 - 1.0 / 1.5)
    assert an.overlap_headroom()["steps"] == 1
    # but the histograms saw both iterations
    assert an.phases_summary()["decode"]["dispatch"]["count"] == 2


def test_admit_only_iterations_are_excluded_from_hot_window():
    an = StepAnatomy(enabled=True, min_steps=1)
    an.observe_step("admit", [("admit", 0.0, 1.0)], 0.0, 1.0, tokens=1)
    assert an.device_bubble_ratio() is None  # no hot-path step yet
    assert an.steps_observed() == 1  # but the histograms saw it
    assert an.phases_summary()["admit"]["admit"]["count"] == 1


def test_capture_bounds_rearm_and_ring_eviction():
    an = StepAnatomy(enabled=True, capture_capacity=4)
    # bounds: arming beyond the ring capacity clamps
    assert an.arm_capture(100) == 4
    for i in range(6):  # only the armed 4 are retained
        _step(an, t0=float(i * 10))
    st = an.capture_state()
    assert st["remaining"] == 0 and st["captured"] == 4
    assert st["captured_total"] == 4
    first_batch = [c["t_start"] for c in an.captured_steps()]
    assert first_batch == [0.0, 10.0, 20.0, 30.0]
    # re-arm: new captures evict the oldest from the bounded ring
    assert an.arm_capture(2) == 2
    _step(an, t0=100.0)
    _step(an, t0=110.0)
    kept = [c["t_start"] for c in an.captured_steps()]
    assert kept == [20.0, 30.0, 100.0, 110.0]  # ring of 4, oldest gone
    assert an.capture_state()["captured_total"] == 6


def test_chrome_trace_two_lane_schema():
    an = StepAnatomy(enabled=True)
    an.arm_capture(2)
    _step(an, dispatch=0.25, execute=1.0, host_extra=0.5, t0=5.0)
    _step(an, dispatch=0.25, execute=1.0, host_extra=0.5, t0=7.0)
    trace = an.to_chrome_trace()
    events = trace["traceEvents"]
    names = {e["name"]: e for e in events if e["ph"] == "M" and "tid" in e}
    assert names["thread_name"]["args"]["name"] in ("host", "device")
    lanes = {e["args"]["name"] for e in events
             if e["ph"] == "M" and e["name"] == "thread_name"}
    assert lanes == {"host", "device"}
    xs = [e for e in events if e["ph"] == "X"]
    assert all(e["tid"] == (2 if e["name"] in DEVICE_PHASES else 1)
               for e in xs)
    # real offsets: the second step's dispatch starts 2s (=2e6us) after
    # the first step's — not a synthetic back-to-back layout
    disp = sorted(e["ts"] for e in xs if e["name"] == "dispatch")
    assert disp[0] == pytest.approx(0.0) and disp[1] == pytest.approx(2e6)
    exe = [e for e in xs if e["name"] == "execute"]
    assert all(e["dur"] == pytest.approx(1e6) for e in exe)
    import json

    json.dumps(trace)  # chrome requires valid JSON


# ------------------------------------------------- real-engine invariants
def test_decode_span_conservation_on_real_steps(engine):
    """SEQUENTIAL steady-state decode (overlap off): host spans are
    disjoint and host-sum + gap == step wall; the device execute span
    mirrors the host block span; the flight record still carries the
    conflated device phase next to the new execute_s field. (The
    overlapped pipeline's diverging-lanes shape is asserted in
    tests/test_overlap.py.)"""
    sched = ContinuousBatchingScheduler(engine, overlap=False)
    assert sched.anatomy.arm_capture(64) == 64
    _drive(sched, [[1, 2, 3, 4], [9, 8, 7]], max_new=8)
    caps = [c for c in sched.anatomy.captured_steps() if c["kind"] == "decode"]
    assert caps, "no decode steps captured"
    for cap in caps:
        wall = cap["t_end"] - cap["t_start"]
        host = sorted(
            (s for s in cap["spans"] if s[0] not in DEVICE_PHASES),
            key=lambda s: s[1],
        )
        # spans sit inside the step window
        assert all(cap["t_start"] - 1e-9 <= s0 and s1 <= cap["t_end"] + 1e-9
                   for _, s0, s1 in host)
        # host spans are disjoint (nesting would double-count)
        for a, b in zip(host, host[1:]):
            assert a[2] <= b[1] + 1e-9, f"overlap: {a} vs {b}"
        host_sum = sum(s1 - s0 for _, s0, s1 in host)
        gap = wall - host_sum
        assert gap >= -1e-9  # conservation: spans never exceed the wall
        assert host_sum + gap == pytest.approx(wall)
        # the device lane mirrors the host block interval, one pair per
        # engine call in the iteration (admission prefills + the decode
        # step); they diverge only once the overlap refactor lands
        block = sorted(s[1:] for s in cap["spans"] if s[0] == "block")
        execute = sorted(s[1:] for s in cap["spans"] if s[0] == "execute")
        assert len(block) >= 1 and block == execute
    # steady-state decode kinds own every first-class phase (the old
    # host "sample" phase no longer exists: keys derive in-jit)
    phases = sched.anatomy.phases_summary()["decode"]
    for p in ("schedule", "dispatch", "block", "execute",
              "readback", "bookkeep"):
        assert phases[p]["count"] >= 1, f"missing phase {p}"
    assert "sample" not in phases
    # flight compatibility: decode records keep the conflated device
    # phase and gain execute_s
    rec = next(r for r in sched.flight.snapshot() if r["kind"] == "decode")
    assert "device" in rec["phases"] and rec["phases"]["device"] >= 0
    assert "execute_s" in rec and rec["execute_s"] >= 0
    assert rec["execute_s"] <= rec["phases"]["device"] + 1e-9


def test_prefix_plan_is_first_class_in_admissions(engine):
    sched = ContinuousBatchingScheduler(engine)
    sched.anatomy.arm_capture(8)
    _drive(sched, [[5, 6, 7, 8]], max_new=2)
    # the admission's radix planning surfaces as its own phase, not
    # hidden inside admit
    summary = sched.anatomy.phases_summary()
    kinds_with_plan = [k for k, ph in summary.items() if "prefix_plan" in ph]
    assert kinds_with_plan, f"prefix_plan not a first-class phase: {summary}"
    # and the admission's flight record carries it next to device
    rec = next(r for r in sched.flight.snapshot() if r["kind"] == "prefill")
    assert "prefix_plan" in rec["phases"]


def test_engine_device_time_split(engine):
    """device_time_s is the derived dispatch+execute+readback sum per
    kind, and MFU divides by execute-only seconds."""
    before = {k: dict(v) for k, v in engine.phase_time_s.items()}
    # overlap off: this test pins the engine's SEQUENTIAL span shape
    # (last_step_spans with block == execute); the pipelined shape is
    # covered by tests/test_overlap.py
    engine.generate([[1, 2, 3]], SamplingParams(max_new_tokens=3), overlap=False)
    after = engine.phase_time_s
    for kind in ("prefill", "decode"):
        for phase in ("dispatch", "execute", "readback"):
            assert after[kind][phase] >= before[kind][phase]
        assert after[kind]["dispatch"] > before[kind]["dispatch"]
    assert engine.device_time_s == {
        k: pytest.approx(sum(v.values())) for k, v in after.items()
    }
    assert engine.total_execute_time_s() == pytest.approx(
        sum(v["execute"] for v in after.values())
    )
    if engine.total_execute_time_s() > 0:
        assert engine.mfu() == pytest.approx(
            engine.total_flops() / engine.total_execute_time_s()
            / engine.flops_model.peak_flops
        )
    # the engine published real spans for the last step
    spans = dict((n, (s0, s1)) for n, s0, s1 in engine.last_step_spans)
    assert set(spans) == {"dispatch", "block", "execute", "readback"}
    assert spans["block"] == spans["execute"]


# ------------------------------------------------------------- disabled
def test_anatomy_disabled_is_inert_and_exact(engine):
    on = ContinuousBatchingScheduler(engine, observability=True)
    off = ContinuousBatchingScheduler(engine, observability=False)
    assert off.anatomy.enabled is False
    assert off.anatomy.arm_capture(8) == 0  # arming a disabled anatomy: no-op
    prompts = [[1, 2, 3], [7, 6, 5, 4]]
    outs_on = _drive(on, prompts)
    outs_off = _drive(off, prompts)
    assert outs_on == outs_off  # anatomy never changes the stream
    assert off.anatomy.steps_observed() == 0
    assert off.anatomy.captured_steps() == []
    assert off.anatomy.device_bubble_ratio() is None
    assert off.anatomy.report()["enabled"] is False
    # disabled gauges emit nothing: None values are skipped by the
    # exposition, so a disabled engine shows no step_* series at all
    gv = off.stats.gauge_values()
    assert gv["step_device_bubble_ratio"] is None
    assert gv["step_anatomy_steps_observed"] is None
    assert on.anatomy.steps_observed() > 0
    # a real run's report is not empty: the bubble ratio is a share
    assert 0.0 <= on.anatomy.device_bubble_ratio() <= 1.0


# ------------------------------------------------------------ exposition
def test_step_phase_family_renders_and_validates():
    an = StepAnatomy(enabled=True)
    _step(an, dispatch=0.25, execute=1.0, host_extra=0.5)
    s = ServingStats()
    s.incr("admitted")
    an.register_gauges(s)
    text = render_prometheus({"lm": s}, anatomy={"lm": an.prom_snapshot()})
    assert not validate_exposition(text)
    assert "# TYPE flexflow_serving_step_phase_seconds histogram" in text
    assert ('flexflow_serving_step_phase_seconds_count'
            '{model="lm",kind="decode",phase="execute"} 1') in text
    assert 'flexflow_serving_step_device_bubble_ratio{model="lm"}' in text
