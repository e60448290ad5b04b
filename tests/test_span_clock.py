"""Spans on two clocks, cumulative counters, program names (ISSUE 23).

Acceptance criteria covered:
  * ``obs/steptrace.phase`` is one span on two clocks: under
    ``jax.profiler.start_trace`` a tiny engine's and a tiny trainer's
    ``ff.*`` spans are events on ``/host:CPU`` of the profile, one per
    ``perf_counter`` span of an armed StepAnatomy capture and of the same
    length to within a millisecond; with no trace running the same code
    path records the same StepAnatomy spans
  * ``/v2/stats`` windows carry ``count_total`` / ``sum_total_s``:
    monotone, growing past the rolling window's 512, and the delta between
    two snapshots is exactly what was observed between them; the step
    anatomy's cumulative phase sums ride along as ``step_phases``
  * ``admit_stall`` is observed once per admission that found a running
    stream, and never otherwise
  * ``ProgramRegistry.instrument`` names the wrapper after the program, so
    the lowered module is ``jit_train_step``
Each test has a time limit of its own (SIGALRM; none off the main thread).
"""
import collections
import functools
import glob
import itertools
import signal
import threading
import time

import jax
import numpy as np
import pytest

from flexflow_tpu import ActiMode, FFConfig, FFModel, LossType, SGDOptimizer
from flexflow_tpu.generation import (
    ContinuousBatchingScheduler,
    GenerationEngine,
    SamplingParams,
    init_decoder_params,
)
from flexflow_tpu.models.transformer import TransformerConfig
from flexflow_tpu.obs import StepAnatomy
from flexflow_tpu.obs.capacity import ProgramRegistry
from flexflow_tpu.obs.steptrace import DEVICE_PHASES, phase
from flexflow_tpu.serving.stats import ServingStats

pytestmark = pytest.mark.observability

CFG = TransformerConfig(
    num_layers=2, hidden_size=32, num_heads=4, ff_size=64,
    seq_length=64, vocab_size=50, causal=True,
)


def time_limit(seconds):
    """Fail the test, instead of hanging the run, after ``seconds``."""
    def wrap(fn):
        @functools.wraps(fn)
        def limited(*args, **kwargs):
            if threading.current_thread() is not threading.main_thread():
                return fn(*args, **kwargs)

            def on_alarm(signum, frame):
                raise TimeoutError(f"{fn.__name__} ran past its {seconds} s limit")

            before = signal.signal(signal.SIGALRM, on_alarm)
            signal.alarm(seconds)
            try:
                return fn(*args, **kwargs)
            finally:
                signal.alarm(0)
                signal.signal(signal.SIGALRM, before)
        return limited
    return wrap


@pytest.fixture(scope="module")
def engine():
    return GenerationEngine(
        init_decoder_params(jax.random.key(0), CFG), CFG, max_batch_slots=3,
        block_size=8, prompt_buckets=(8, 16, 32, 64),
    )


def _drive(sched, prompts, max_new=6):
    handles = [sched.submit(p, SamplingParams(max_new_tokens=max_new)) for p in prompts]
    while any(not h.done() for h in handles):
        if not sched.step():
            break
    return [h.result(timeout=0) for h in handles]


def _profile(tmp_path, body):
    """Run ``body`` under the profiler; the ``ff.*`` events of the
    profile's ``/host:CPU`` plane as {name: [duration in seconds]}."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        body()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "plugins/profile/*/*.xplane.pb"))
    found = collections.defaultdict(list)
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("ff."):
                    found[e.name].append(e.duration_ns / 1e9)
    return found


def _host_spans(sched):
    """Captured host-lane spans by phase name, in order: [seconds]."""
    out = collections.defaultdict(list)
    for cap in sched.anatomy.captured_steps():
        for name, t0, t1 in cap["spans"]:
            if name not in DEVICE_PHASES:
                out[name].append(t1 - t0)
    return out


# ----------------------------------------------------------- the two clocks
@time_limit(120)
def test_phase_hands_back_stamps_and_feeds_a_list():
    sink = []
    with phase("sched.admit", into=sink, request=7) as p:
        pass
    assert p.t1 >= p.t0 and p.seconds == p.t1 - p.t0
    assert sink == [("admit", p.t0, p.t1)] and p.span == sink[0]
    # early exits leave their span too
    with pytest.raises(KeyError):
        with phase("engine.decode.block", into=sink):
            raise KeyError("x")
    assert [s[0] for s in sink] == ["admit", "block"]


@time_limit(300)
def test_serving_spans_are_on_the_profilers_clock(engine, tmp_path):
    sched = ContinuousBatchingScheduler(engine, overlap=False)
    _drive(sched, [[1, 2, 3], [4, 5, 6, 7]])  # compile outside the trace

    def body():
        assert sched.anatomy.arm_capture(128) == 128
        _drive(sched, [[1, 2, 3, 9], [4, 5, 6, 7, 8]])

    events = _profile(tmp_path, body)
    spans = _host_spans(sched)
    assert {"schedule", "admit", "prefix_plan", "dispatch", "block", "readback",
            "bookkeep", "housekeep"} <= set(spans)
    # one event per perf_counter span, phase by phase, the same length
    # to a millisecond (the annotation encloses the stamps)
    by_phase = collections.defaultdict(list)
    for name, durations in events.items():
        layer = name.split(".")[1]
        if layer in ("sched", "engine"):
            by_phase[name.rsplit(".", 1)[1]] += durations
    off = []
    for name, want in spans.items():
        got = by_phase[name]
        if name == "observe":
            # beside its spans, one event an iteration that is in no list:
            # the observation itself, handed to the next one as seconds
            assert len(got) == len(want) + len(sched.anatomy.captured_steps())
            continue
        assert len(got) == len(want), (name, len(got), len(want))
        off += [(name, g, w) for g, w in zip(sorted(got), sorted(want)) if abs(g - w) >= 1e-3]
    # (one span in the run may lose the CPU between its two clock reads)
    assert len(off) <= 1, off
    assert any(n.startswith("ff.engine.decode.") for n in events)
    assert any(n.startswith("ff.engine.prefill.") for n in events)
    # a dispatch's parts are events of their own, one of each a dispatch
    for kind in ("decode", "prefill"):
        parent = len(events[f"ff.engine.{kind}.dispatch"])
        assert [len(events[f"ff.engine.{kind}.dispatch.{part}"]) for part in ("args", "upload", "call")] == [parent] * 3


@time_limit(300)
@pytest.mark.parametrize("overlap", [False, True])
def test_the_same_path_records_the_same_spans_with_no_trace_running(engine, tmp_path, overlap):
    def shape():
        sched = ContinuousBatchingScheduler(engine, overlap=overlap)
        sched.anatomy.arm_capture(128)
        _drive(sched, [[1, 2, 3, 9], [4, 5, 6, 7, 8]])
        return [(c["kind"], [s[0] for s in c["spans"]]) for c in sched.anatomy.captured_steps()]

    untraced = shape()
    traced = []
    _profile(tmp_path, lambda: traced.extend(shape()))
    assert untraced == traced
    names = {n for _, spans in untraced for n in spans}
    assert {"schedule", "admit", "prefix_plan", "dispatch", "block", "execute",
            "readback", "bookkeep", "housekeep"} <= names


@time_limit(300)
def test_training_and_loader_spans_are_on_the_profilers_clock(tmp_path):
    model = FFModel(FFConfig(batch_size=16, epochs=1))
    x = model.create_tensor((16, 8))
    model.softmax(model.dense(model.dense(x, 16, ActiMode.RELU), 4))
    model.compile(optimizer=SGDOptimizer(lr=0.1),
                  loss_type=LossType.SPARSE_CATEGORICAL_CROSSENTROPY)
    rs = np.random.RandomState(0)
    xs = rs.randn(16 * 6, 8).astype(np.float32)
    ys = rs.randint(0, 4, size=(16 * 6,)).astype(np.int32)
    model.fit(xs, ys, verbose=False)  # compile outside the trace
    loader = model.create_data_loader(xs, ys, shuffle=False)

    def body():
        model.fit(xs, ys, verbose=False)
        for i, (bx, by) in enumerate(loader.epoch()):
            model.executor.train_batch(bx, by, jax.random.key(i))

    events = _profile(tmp_path, body)
    # 6 steps through fit, 6 through the loader
    assert len(events["ff.train.step"]) == 6
    assert len(events["ff.train.dispatch"]) == 12
    assert len(events["ff.train.shard_inputs"]) == 12
    assert len(events["ff.data.produce"]) == 6
    assert len(events["ff.data.wait"]) == 7  # 6 batches and the end of the epoch
    # measured calls (the first four of a program, then every 8th) drain
    # the device twice, and say so
    assert len(events["ff.train.truth_sync"]) % 2 == 0
    assert len(events["ff.train.truth_sync"]) >= 2


# ------------------------------------------------------ cumulative counters
@time_limit(120)
def test_cumulative_counters_outgrow_the_rolling_window():
    stats = ServingStats(latency_window=512)
    seen = []
    total = 0.0
    for i in range(700):
        stats.observe("queue_time", 0.001 * (i % 5))
        total += 0.001 * (i % 5)
        if i % 100 == 99:
            seen.append(stats.snapshot()["queue_time"])
    counts = [s["count_total"] for s in seen]
    assert counts == [100, 200, 300, 400, 500, 600, 700]  # monotone, past 512
    sums = [s["sum_total_s"] for s in seen]
    assert sums == sorted(sums) and sums[-1] == pytest.approx(total)
    # the delta between two snapshots is what was observed between them
    a = stats.snapshot()["queue_time"]
    for v in (0.25, 0.5, 1.0):
        stats.observe("queue_time", v)
    b = stats.snapshot()["queue_time"]
    assert b["count_total"] - a["count_total"] == 3
    assert b["sum_total_s"] - a["sum_total_s"] == pytest.approx(1.75)
    # the rolling percentiles are still there for the limiter and the SLOs
    assert {"p50_s", "p95_s", "p99_s"} <= set(b)


@time_limit(120)
def test_step_phases_in_the_snapshot_are_cumulative():
    stats = ServingStats()
    an = StepAnatomy()
    an.register_gauges(stats)
    an.observe_step("decode", [("dispatch", 0.0, 0.25), ("block", 0.25, 1.0),
                               ("execute", 0.25, 1.0)], 0.0, 1.5, tokens=1)
    a = stats.snapshot()["step_phases"]
    assert a["decode.dispatch"] == {"count": 1, "total_s": 0.25}
    assert a["decode.block"] == {"count": 1, "total_s": 0.75}
    an.observe_step("decode", [("dispatch", 2.0, 2.5)], 2.0, 3.0)
    an.observe_step("admit", [("admit", 3.0, 3.125)], 3.0, 3.25)
    b = stats.snapshot()["step_phases"]
    assert b["decode.dispatch"]["total_s"] - a["decode.dispatch"]["total_s"] == 0.5
    assert b["decode.dispatch"]["count"] == 2 and b["decode.block"] == a["decode.block"]
    assert b["admit.admit"] == {"count": 1, "total_s": 0.125}
    # a disabled anatomy adds no entry
    quiet = ServingStats()
    StepAnatomy(enabled=False).register_gauges(quiet)
    assert "step_phases" not in quiet.snapshot()


# ---------------------------------------------------------------- admit_stall
def _stalls(sched):
    return sched.stats.snapshot().get("admit_stall", {"count_total": 0})["count_total"]


@time_limit(300)
@pytest.mark.parametrize("overlap", [False, True])
def test_admit_stall_once_per_admission_that_found_a_running_stream(engine, overlap):
    sched = ContinuousBatchingScheduler(engine, overlap=overlap)
    # alone: nothing is held, nothing is observed
    _drive(sched, [[1, 2, 3]], max_new=4)
    assert _stalls(sched) == 0
    # two at once, into an idle scheduler: the second finds the first in
    # a slot, but no stream is decoding yet
    _drive(sched, [[1, 2, 3], [4, 5, 6]], max_new=4)
    assert _stalls(sched) == 0
    # one running and decoding, then two more join it: two observations
    first = sched.submit([7, 8, 9], SamplingParams(max_new_tokens=12))
    for _ in range(3):
        sched.step()
    assert not first.done() and _stalls(sched) == 0
    later = [sched.submit(p, SamplingParams(max_new_tokens=3)) for p in ([1, 2], [3, 4])]
    while not all(h.done() for h in [first] + later):
        assert sched.step()
    snap = sched.stats.snapshot()["admit_stall"]
    assert snap["count_total"] == 2
    # held for at least the prefill and the decode step that followed
    assert snap["sum_total_s"] > 0.0
    # and once the batch has drained, a lone request again observes nothing
    _drive(sched, [[5, 5, 5]], max_new=3)
    assert _stalls(sched) == 2


# ------------------------------------------ queue_time and the flight record
@time_limit(300)
def test_queue_time_ends_at_the_queue_pop_not_after_the_prefill(engine, monkeypatch):
    ticks = itertools.count()
    clock = lambda: float(next(ticks))  # noqa: E731 - every read is a second later
    sched = ContinuousBatchingScheduler(engine, overlap=False, clock=clock)
    at_prefill = []
    prefill = engine.prefill_one
    monkeypatch.setattr(
        engine, "prefill_one", lambda *a, **k: (at_prefill.append(clock()), prefill(*a, **k))[1]
    )
    handle = sched.submit([1, 2, 3], SamplingParams(max_new_tokens=2))
    submitted = handle._request.submitted_at
    while not handle.done():
        assert sched.step()
    snap = sched.stats.snapshot()
    queued, first_token = snap["queue_time"]["sum_total_s"], snap["ttft"]["sum_total_s"]
    assert snap["queue_time"]["count_total"] == snap["ttft"]["count_total"] == 1
    # the wait ends before the prefill starts, the first token after it
    assert submitted + queued < at_prefill[0] < submitted + first_token


@time_limit(300)
def test_flight_record_device_is_the_wall_of_the_supervised_step(engine, monkeypatch):
    """``device`` holds what the supervisor spent around the engine's
    step (retries, bisection), not only the attempt that succeeded; and
    the record is cut before housekeep runs."""
    sched = ContinuousBatchingScheduler(engine, overlap=False)
    _drive(sched, [[1, 2, 3]], max_new=3)  # compile first
    seen = len(sched.flight.snapshot())
    run_step = sched.supervisor.run_step

    def slow(*a, **k):
        time.sleep(0.05)  # a failed attempt's worth of time
        return run_step(*a, **k)

    monkeypatch.setattr(sched.supervisor, "run_step", slow)
    sched.anatomy.arm_capture(64)
    _drive(sched, [[1, 2, 3]], max_new=3)
    records = sched.flight.snapshot()[seen:]
    decodes = [r for r in records if r.get("kind") == "decode"]
    assert decodes and all(r["phases"]["device"] >= 0.05 for r in decodes)
    assert all("housekeep" not in r["phases"] and "dispatch" not in r["phases"] for r in decodes)
    # the anatomy's engine spans are the successful attempt alone
    for cap in sched.anatomy.captured_steps():
        if cap["kind"] == "decode":
            engine_s = sum(t1 - t0 for n, t0, t1 in cap["spans"] if n in ("dispatch", "block", "readback"))
            assert engine_s < 0.05
    prefill = [r for r in records if r.get("kind") == "prefill"]
    assert prefill and set(prefill[0]["phases"]) == {"prefix_plan", "device"}


@time_limit(300)
def test_flight_record_of_a_pipelined_step_keeps_dispatch_beside_device(engine):
    sched = ContinuousBatchingScheduler(engine, overlap=True)
    _drive(sched, [[1, 2, 3]], max_new=8)
    piped = [r for r in sched.flight.snapshot()
             if r.get("kind") == "decode" and "dispatch" in r["phases"]]
    # (the iteration that fills the pipeline dispatches and consumes nothing)
    assert any(r["phases"].get("device", 0.0) > 0.0 for r in piped)
    assert all("block" not in r["phases"] and "readback" not in r["phases"] for r in piped)


# -------------------------------------------------------------- program names
@time_limit(120)
def test_instrumented_programs_carry_their_own_name():
    reg = ProgramRegistry()

    def f(x):
        return x * 2.0

    wrapped = reg.instrument("ns.train_step", f)
    assert wrapped.__name__ == "train_step"
    lowered = jax.jit(wrapped).lower(jax.numpy.ones((4,)))
    assert "jit_train_step" in lowered.as_text()[:200]
    assert reg.trace_count("ns.train_step") == 1  # and it still registers
    assert reg.instrument("executor[3].train_window[16]", f).__name__ == "train_window_16"
    assert reg.instrument("executor[0].forward", f).__name__ == "forward"
