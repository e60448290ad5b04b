"""Step-anatomy profiler tests (ISSUE 12, tier-1).

Acceptance criteria covered:
  * span nesting + conservation: a steady-state decode step's host
    spans are disjoint and sum (plus the gap) to the step wall within
    epsilon, with the device execute span mirroring the host block span
  * the conserved account (ISSUE 37): a dispatch's children lie inside
    it, host-lane spans stay disjoint with stage / post / observe among
    them, ``working = phases + unspanned`` and the ``loop`` identity to
    1 %, every new total monotone across scrapes from another thread,
    empty iterations counted, uploads counted exactly, and nothing of it
    (no section, no CPU-clock read) with observability off
  * capture-K bounds, re-arming, and ring eviction
  * the two-lane chrome trace schema (host tid 1 / device tid 2, real
    offsets)
  * anatomy disabled (observability=False) is inert AND the token
    streams are byte-identical
  * the engine's device_time_s split: dispatch/execute/readback accrue
    per kind, the old total is the derived sum, MFU divides by
    execute-only seconds, and the prometheus family renders
"""
import threading
import time

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
    assert set(spans) == {"dispatch", "post", "block", "execute", "readback", "account"}  # the last call was a decode step
    assert spans["readback"][1] <= spans["account"][0]
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
    assert off.anatomy.report()["enabled"] is False and not any(off.anatomy.report()["loop"].values())
    # disabled gauges emit nothing: None values are skipped by the
    # exposition, so a disabled engine shows no step_* series at all
    gv = off.stats.gauge_values()
    assert gv["step_anatomy_steps_observed"] is None
    assert on.anatomy.steps_observed() > 0
    # a real run's report is not empty: the account holds its iterations
    assert on.anatomy.report()["loop"]["working_iterations_total"] == on.anatomy.steps_observed()


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
    assert 'flexflow_serving_step_anatomy_steps_observed{model="lm"} 1' in text
    assert "bubble" not in text and "overlap_projected" not in text


# ------------------------------------------- the conserved account (ISSUE 37)
HOST_ONLY = {"wall_total_s", "idle_wait_total_s"}  # what only the scheduler's own loop can count


def _lane_seconds(phases):
    """Host-lane seconds of a ``step_phases`` snapshot: every key that is
    no device-lane span and no child of a dispatch, ``unspanned`` among them."""
    return sum(v["total_s"] for k, v in phases.items()
               if k.split(".", 1)[1] not in DEVICE_PHASES and ".dispatch." not in k)


def test_unspanned_is_the_wall_less_the_union_and_children_are_in_no_sum():
    an = StepAnatomy(enabled=True)
    spans = [("schedule", 0.0, 1.0), ("dispatch", 1.5, 3.0), ("execute", 0.0, 4.0),
             ("bookkeep", 2.5, 3.5)]  # bookkeep overlaps dispatch by 0.5: the union counts it once
    children = [("args", 1.5, 1.75), ("upload", 1.75, 2.25), ("call", 2.25, 3.0)]
    an.observe_step("decode", spans, 0.0, 4.0, tokens=1, children=children, carried_s=0.25, cpu_s=2.0)
    got = {k: v["total_s"] for k, v in an.cumulative().items()}
    assert got["decode.unspanned"] == pytest.approx(4.0 - (1.0 + 2.0))  # [0,1] + [1.5,3.5]
    assert (got["decode.dispatch.args"], got["decode.dispatch.upload"], got["decode.dispatch.call"]) == (0.25, 0.5, 0.75)
    assert got["decode.observe"] == 0.25  # what the observation before cost, handed on
    loop = an.loop()
    assert loop["working_total_s"] == 4.25 and loop["working_iterations_total"] == 1
    assert (loop["cpu_total_s"], loop["cpu_wall_total_s"]) == (2.0, 4.0)  # a sampled iteration: its CPU beside its wall
    assert not HOST_ONLY & set(loop)  # nobody said a loop of the scheduler's own runs
    # with disjoint spans, working = host-lane phases + unspanned exactly
    an2 = StepAnatomy(enabled=True)
    an2.observe_step("decode", spans[:3], 0.0, 4.0, children=children, carried_s=0.25)
    assert an2.loop()["working_total_s"] == pytest.approx(_lane_seconds(an2.cumulative()))
    # the capture keeps the children beside the spans, and the timeline nests them
    an.arm_capture(1)
    an.observe_step("decode", spans[:3], 10.0, 14.0, children=children)
    names = [e["name"] for e in an.to_chrome_trace()["traceEvents"] if e["ph"] == "X"]
    assert names == ["schedule", "dispatch", "execute", "dispatch.args", "dispatch.upload", "dispatch.call"]


def test_the_loop_account_marks_wall_at_every_iteration_and_wait():
    an = StepAnatomy(enabled=True)
    an.observe_empty(0.0, 0.5)
    assert an.loop()["empty_iterations_total"] == 1 and "wall_total_s" not in an.loop()
    an.loop_started(10.0)
    an.observe_step("decode", [("dispatch", 10.25, 11.0)], 10.25, 11.0)  # 0.25 of the loop's own before it
    an.observe_empty(11.0, 11.5)
    an.observe_wait(11.5, 13.5)
    loop = an.loop()
    assert (loop["wall_total_s"], loop["working_total_s"], loop["empty_total_s"], loop["idle_wait_total_s"]) == (3.5, 0.75, 1.0, 2.0)
    an.loop_stopped()
    an.observe_wait(20.0, 30.0)  # no loop runs: nothing to count
    an.observe_empty(30.0, 30.5)
    assert an.loop()["wall_total_s"] == 3.5 and an.loop()["idle_wait_total_s"] == 2.0


def test_phase_reads_the_cpu_clock_only_when_asked(monkeypatch):
    from flexflow_tpu.obs import steptrace

    with steptrace.phase("engine.decode.dispatch", cpu=True) as p:
        sum(range(20000))
    assert p.c1 >= p.c0 and 0.0 <= p.cpu_seconds <= p.seconds + 1e-3
    monkeypatch.setattr(steptrace, "thread_time", lambda: pytest.fail("the CPU clock was read"))
    with steptrace.phase("engine.decode.dispatch") as q:
        pass
    assert q.c0 is None and q.cpu_seconds is None


@pytest.mark.parametrize("overlap", [False, True], ids=["sequential", "pipelined"])
def test_a_dispatch_s_children_lie_inside_it_and_sum_within_it(engine, overlap):
    sched = ContinuousBatchingScheduler(engine, overlap=overlap)
    sched.anatomy.arm_capture(64)
    _drive(sched, [[1, 2, 3, 4], [9, 8, 7]], max_new=10)
    n_dispatch = 0
    for cap in sched.anatomy.captured_steps():
        parents = [s for s in cap["spans"] if s[0] == "dispatch"]
        n_dispatch += len(parents)
        # args, upload, call: one of each a dispatch, in that order, back to back
        assert [c[0] for c in cap["children"]] == ["args", "upload", "call"] * len(parents)
        for _, p0, p1 in parents:
            mine = [c for c in cap["children"] if p0 - 1e-9 <= c[1] and c[2] <= p1 + 1e-9]
            assert [c[0] for c in mine] == ["args", "upload", "call"]
            assert all(a[2] <= b[1] + 1e-9 for a, b in zip(mine, mine[1:]))
            assert sum(c[2] - c[1] for c in mine) <= (p1 - p0) + 1e-9
    assert n_dispatch >= 10
    phases = sched.anatomy.cumulative()
    parts = sum(phases[f"decode.dispatch.{part}"]["total_s"] for part in ("args", "upload", "call"))
    assert 0.5 * phases["decode.dispatch"]["total_s"] <= parts <= phases["decode.dispatch"]["total_s"]
    assert phases["decode.dispatch.call"]["count"] == phases["decode.dispatch"]["count"]


def test_host_lane_spans_stay_disjoint_with_stage_post_and_observe_among_them(engine):
    sched = ContinuousBatchingScheduler(engine, overlap=True)
    sched.anatomy.arm_capture(64)
    _drive(sched, [[1, 2, 3, 4], [9, 8, 7]], max_new=10)
    seen = set()
    for cap in sched.anatomy.captured_steps():
        host = sorted((s for s in cap["spans"] if s[0] not in DEVICE_PHASES), key=lambda s: s[1])
        seen |= {s[0] for s in host}
        assert all(cap["t_start"] - 1e-9 <= s0 and s1 <= cap["t_end"] + 1e-9 for _, s0, s1 in host)
        for a, b in zip(host, host[1:]):
            assert a[2] <= b[1] + 1e-9, f"overlap: {a} vs {b}"
        # a pipelined dispatch is followed by its post, with nothing between
        order = [s[0] for s in host]
        assert all(order[i + 1] == "post" for i, n in enumerate(order) if n == "dispatch" and "stage" in order)
    assert {"stage", "dispatch", "post", "block", "readback", "account", "bookkeep", "release", "observe", "housekeep"} <= seen
    # the account of the same run: what the spans leave is counted, not lost
    loop, phases = sched.anatomy.loop(), sched.anatomy.cumulative()
    assert loop["working_total_s"] == pytest.approx(_lane_seconds(phases), rel=1e-9)
    assert phases["decode.observe"]["total_s"] > 0 and phases["decode.unspanned"]["count"] == loop["working_iterations_total"]


def _served(engine, **kw):
    """A scheduler on its own loop thread that has served two requests."""
    sched = ContinuousBatchingScheduler(engine, **kw)
    sched.start()
    handles = [sched.submit(p, SamplingParams(max_new_tokens=40)) for p in ([1, 2, 3, 4], [9, 8, 7])]
    for h in handles:
        h.result(timeout=120)
    return sched


def test_the_thread_s_seconds_are_conserved_to_a_hundredth_on_a_real_engine(engine):
    before = engine.decode_dispatch_clock[0]  # the engine's total, which other schedulers of this module fed
    sched = _served(engine)
    time.sleep(0.05)  # some empty iterations and waits behind the work
    sched.stop()
    loop, phases = sched.stats.snapshot()["loop"], sched.stats.snapshot()["step_phases"]
    assert loop["working_iterations_total"] >= 12 and loop["empty_iterations_total"] >= 1
    assert loop["working_total_s"] == pytest.approx(_lane_seconds(phases), rel=0.01)
    parts = loop["working_total_s"] + loop["empty_total_s"] + loop["idle_wait_total_s"]
    assert parts <= loop["wall_total_s"] * (1 + 1e-9)
    assert parts == pytest.approx(loop["wall_total_s"], rel=0.01)  # the rest is the loop's own few lines
    # the CPU clock is read on one iteration in CPU_CLOCK_EVERY, at four places: the sampled iterations' CPU
    # beside their wall (one thread cannot use more CPU than wall), and their decode dispatches' likewise
    assert 0 < loop["cpu_wall_total_s"] < loop["working_total_s"] and 0 <= loop["cpu_total_s"] <= loop["cpu_wall_total_s"] * 1.05
    wall, cpu = loop["decode_dispatch_wall_total_s"], loop["decode_dispatch_cpu_total_s"]
    assert 0 <= cpu <= wall * 1.05 and wall == pytest.approx(engine.decode_dispatch_clock[0])
    dispatches = sched.stats.snapshot()["step_phases"]["decode.dispatch"]
    assert 0 < wall - before < dispatches["total_s"]  # some of this scheduler's dispatches, not all


def test_the_cpu_clock_is_read_on_one_iteration_in_sixteen_at_four_places(engine, monkeypatch):
    from flexflow_tpu.generation import scheduler as sched_mod
    from flexflow_tpu.obs import steptrace

    reads = {"iteration": 0, "dispatch": 0}
    clock = time.thread_time
    monkeypatch.setattr(sched_mod.time, "thread_time", lambda: reads.__setitem__("iteration", reads["iteration"] + 1) or clock())
    monkeypatch.setattr(steptrace, "thread_time", lambda: reads.__setitem__("dispatch", reads["dispatch"] + 1) or clock())
    sched = ContinuousBatchingScheduler(engine, overlap=True)
    handles = [sched.submit(p, SamplingParams(max_new_tokens=40)) for p in ([1, 2, 3, 4], [9, 8, 7])]
    iterations = 0
    while any(not h.done() for h in handles):
        iterations += 1
        sched.step()
    sampled = -(-iterations // sched_mod.CPU_CLOCK_EVERY)
    assert iterations >= 2 * sched_mod.CPU_CLOCK_EVERY
    assert reads["iteration"] == 2 * sampled  # the two ends of a sampled iteration
    assert 0 < reads["dispatch"] <= 2 * sampled  # and of its decode dispatch, where it made one
    assert engine.cpu_stamps is False or iterations % sched_mod.CPU_CLOCK_EVERY == 1


def _flat(snapshot):
    """Every new monotone total of one ``/v2/stats`` snapshot, by name."""
    out = {f"loop.{k}": v for k, v in snapshot["loop"].items()}
    out.update({f"uploads.{k}": v for k, v in snapshot["uploads"].items()})
    for key, v in snapshot["step_phases"].items():
        out[f"{key}.count"], out[f"{key}.total_s"] = v["count"], v["total_s"]
    return out


def test_every_new_total_is_monotone_across_scrapes_from_another_thread(engine):
    sched = ContinuousBatchingScheduler(engine)
    sched.start()
    scrapes, done = [], threading.Event()

    def scrape():
        while not done.is_set():
            scrapes.append(_flat(sched.stats.snapshot()))
            time.sleep(0.001)

    t = threading.Thread(target=scrape)
    t.start()
    try:
        handles = [sched.submit([1 + i, 2, 3], SamplingParams(max_new_tokens=12)) for i in range(4)]
        for h in handles:
            h.result(timeout=120)
    finally:
        done.set()
        t.join()
        sched.stop()
    scrapes.append(_flat(sched.stats.snapshot()))
    assert len(scrapes) >= 3
    for a, b in zip(scrapes, scrapes[1:]):
        assert set(a) <= set(b)  # a key, once there, stays
        assert all(b[k] >= v for k, v in a.items()), [k for k, v in a.items() if b[k] < v]
        # and each scrape is a whole number of iterations: the identity holds in it, not only at rest
        spent = b["loop.working_total_s"] + b["loop.empty_total_s"] + b["loop.idle_wait_total_s"]
        assert spent <= b["loop.wall_total_s"] + 1e-9
    last = scrapes[-1]
    assert last["decode.dispatch.upload.count"] > 0 and last["loop.wall_total_s"] > 0 and last["uploads.uploads_total"] > 0


def test_an_empty_iteration_is_counted_and_a_working_one_is_counted_once(engine):
    sched = ContinuousBatchingScheduler(engine)
    assert sched.step() is False and sched.step() is False
    loop = sched.anatomy.loop()
    assert (loop["empty_iterations_total"], loop["working_iterations_total"]) == (2, 0)
    assert loop["empty_total_s"] > 0 and sched.anatomy.steps_observed() == 0
    assert not HOST_ONLY & set(loop)  # this test drives step(): no loop thread to speak for
    h = sched.submit([1, 2, 3], SamplingParams(max_new_tokens=3))
    worked = 0
    while not h.done():
        worked += bool(sched.step())
    loop = sched.anatomy.loop()
    assert (loop["empty_iterations_total"], loop["working_iterations_total"]) == (2, worked)
    assert sched.anatomy.steps_observed() == worked
    assert sched.step() is False
    assert sched.anatomy.loop()["empty_iterations_total"] == 3


def test_a_carried_steady_step_uploads_exactly_its_three_fresh_vectors(engine):
    """(The name is PR 37's: the three vectors were ``safe_pos``,
    ``context_lens`` and ``counts``, uploaded every step. Since ISSUE 40
    the decode program returns the positions and counts advanced, the
    engine keeps them staged, and a steady step uploads NOTHING.)"""
    sched = ContinuousBatchingScheduler(engine, overlap=True)
    handles = [sched.submit(p, SamplingParams(max_new_tokens=n)) for p, n in (([1, 2, 3, 4], 14), ([9, 8, 7], 9))]
    carried, after_finish = [], None
    while any(not h.done() for h in handles):
        before, had, live = dict(engine.uploads), sched._pipe, len(sched._running)
        sched.step()
        grew = {k: engine.uploads[k] - v for k, v in before.items()}
        if had is not None and sched._pipe is not None and sched._pipe.handle.children:
            carried.append(grew)  # a step dispatched on the token array of the one in flight
        elif live == 2 and len(sched._running) == 1:
            after_finish = grew  # the iteration that finished a stream and dispatched the other's next step
    assert len(carried) >= 6
    for grew in carried:
        # positions and counts are what the step in flight returned; whatever staging missed (a block table that
        # grew) goes up; the tokens never
        assert grew["carried_hits_total"] == 1 and grew["carried_misses_total"] == 0
        assert grew["uploads_total"] == grew["staged_misses_total"]
        # positions, counts; tables, active, temps, top_ks, seeds
        assert grew["staged_hits_total"] + grew["staged_misses_total"] == 7
    steady = [g for g in carried if g["staged_misses_total"] == 0]
    assert steady and all(g["uploads_total"] == 0 and g["upload_bytes_total"] == 0 for g in steady)
    # a finish: the freed slot's position, count, mask and table row change, and each vector goes up once
    assert after_finish["carried_misses_total"] == 1 and after_finish["carried_hits_total"] == 0
    assert after_finish["staged_misses_total"] == after_finish["uploads_total"] == 4
    assert after_finish["upload_bytes_total"] == 3 * 4 * engine.max_batch_slots + engine._staged["decode.tables"][0].nbytes
    assert sched.stats.snapshot()["uploads"]["carried_hits_total"] == engine.uploads["carried_hits_total"] >= len(carried)


def test_with_observability_off_the_sections_are_absent_and_no_cpu_clock_is_read(decoder_params, monkeypatch):
    from flexflow_tpu.generation import scheduler as sched_mod
    from flexflow_tpu.obs import steptrace

    fresh = GenerationEngine(decoder_params, CFG, max_batch_slots=3, block_size=8, prompt_buckets=(8, 16, 32, 64))
    monkeypatch.setattr(steptrace, "thread_time", lambda: pytest.fail("phase read the CPU clock"))
    monkeypatch.setattr(sched_mod.time, "thread_time", lambda: pytest.fail("the scheduler read the CPU clock"))
    sched = _served(fresh, observability=False)
    sched.stop()
    snapshot = sched.stats.snapshot()
    assert not {"loop", "uploads", "step_phases"} & set(snapshot)
    assert fresh.cpu_stamps is False and fresh.decode_dispatch_clock == (0.0, 0.0)
    assert sched.anatomy.loop()["working_iterations_total"] == 0 and sched.anatomy.cumulative() == {}
