"""Overload control (ISSUE 14): priority-aware admission, the AIMD
adaptive concurrency limiter, the graceful-degradation ladder, roofline
infeasibility fast-fail, fleet spill-then-shed, and the
Retry-After / gRPC retry-metadata round trips — all on virtual clocks.
"""
import json
import urllib.error
import urllib.request

import jax
import pytest

from flexflow_tpu.generation import (
    ContinuousBatchingScheduler,
    GenerationEngine,
    SamplingParams,
    init_decoder_params,
)
from flexflow_tpu.generation.speculative import SpeculationConfig
from flexflow_tpu.models.transformer import TransformerConfig
from flexflow_tpu.runtime import faults
from flexflow_tpu.runtime.faults import FaultPlan
from flexflow_tpu.serving.fleet import Fleet
from flexflow_tpu.serving.overload import (
    AdaptiveLimiter,
    AutoscaleAdvisor,
    DegradeLadder,
    OverloadConfig,
    Priority,
)
from flexflow_tpu.serving.resilience import (
    InfeasibleError,
    OverloadedError,
    QueueFullError,
)

pytestmark = pytest.mark.overload

CFG = TransformerConfig(
    num_layers=1, hidden_size=16, num_heads=2, ff_size=32,
    seq_length=64, vocab_size=40, causal=True,
)
BUCKETS = (8, 32, 64)

from conftest import FakeClock  # noqa: E402


@pytest.fixture(scope="module")
def decoder_params():
    return init_decoder_params(jax.random.key(0), CFG)


@pytest.fixture(scope="module")
def engine(decoder_params):
    return GenerationEngine(
        decoder_params, CFG, max_batch_slots=3, block_size=8,
        prompt_buckets=BUCKETS,
    )


@pytest.fixture(autouse=True)
def _no_leaked_plan():
    yield
    assert faults.active_plan() is None, "a test leaked an installed FaultPlan"


def make_sched(engine, clock=None, **kw):
    clock = clock or FakeClock()
    kw.setdefault("max_queue", 8)
    return ContinuousBatchingScheduler(engine, clock=clock, **kw), clock


def drain(sched, handles, steps=500):
    for _ in range(steps):
        if all(h.done() for h in handles):
            return
        sched.step()


# ---------------------------------------------------------------------------
# priority plumbing
# ---------------------------------------------------------------------------


def test_priority_parse():
    assert Priority.parse(None) == "standard"
    assert Priority.parse("Interactive") == "interactive"
    assert Priority.parse("best-effort") == "best_effort"
    assert Priority.parse("BEST_EFFORT") == "best_effort"
    with pytest.raises(ValueError):
        Priority.parse("urgent")


def test_priority_ordered_admission(engine):
    """Queued requests admit priority-first, FIFO within a class —
    regardless of submit order."""
    sched, _ = make_sched(engine)
    sampling = SamplingParams(max_new_tokens=2)
    order = []

    def tag(h, name):
        h.future.add_done_callback(lambda f: order.append(name))
        return h

    # 3 slots: the first three submits admit immediately whatever their
    # class; the rest queue and must reorder by priority
    running = [sched.submit([1, 2, 3], sampling, priority="best_effort")
               for _ in range(3)]
    b = sched.submit([4, 5, 6], sampling, priority="best_effort")
    s = sched.submit([4, 5, 7], sampling, priority="standard")
    i = sched.submit([4, 5, 8], sampling, priority="interactive")
    queued = [r.priority for r in sched._queue]
    # the 3 fillers are still queued too (admission happens at step);
    # the newcomers sorted ahead of every fresh lower-class request
    assert queued == ["interactive", "standard"] + ["best_effort"] * 4
    drain(sched, running + [b, s, i])
    assert all(h.done() for h in (b, s, i))


def test_queue_full_sheds_lowest_priority(engine):
    """A full queue sheds the youngest queued best-effort request to
    admit an interactive one; an incoming best-effort request is
    rejected outright — and the accounting splits per reason AND per
    class. The typed error subclasses QueueFullError (compat)."""
    sched, _ = make_sched(engine, max_queue=2)
    sampling = SamplingParams(max_new_tokens=2)
    running = []
    for _ in range(3):  # fill the 3 slots, admitting each before the next
        running.append(sched.submit([1, 2, 3], sampling))
        sched.step()
    q1 = sched.submit([4, 4, 4], sampling, priority="best_effort")
    q2 = sched.submit([5, 5, 5], sampling, priority="best_effort")
    # queue full: best-effort newcomer bounces (nothing outranked)
    with pytest.raises(OverloadedError) as ei:
        sched.submit([6, 6, 6], sampling, priority="best_effort")
    assert ei.value.reason == "queue_full"
    assert ei.value.priority == "best_effort"
    assert ei.value.retry_after_s is not None
    assert isinstance(ei.value, QueueFullError)
    # interactive newcomer displaces the YOUNGEST best-effort victim
    hi = sched.submit([7, 7, 7], sampling, priority="interactive")
    with pytest.raises(OverloadedError) as ev:
        q2.result(timeout=0)
    assert ev.value.reason == "queue_full"
    assert ev.value.priority == "best_effort"
    assert not q1.done()
    counts = sched.stats.counters()
    assert counts["rejected_queue_full"] == 2
    assert counts["rejected_best_effort"] == 2
    assert sched.overload.activations()["sheds"] == 1
    drain(sched, running + [q1, hi])
    assert hi.result(timeout=0)


def test_preemption_victim_is_lowest_priority(engine, decoder_params):
    """Under cache pressure the recompute victim is the youngest member
    of the LOWEST class present — an older best-effort stream is evicted
    before a younger interactive one."""
    # a tiny dedicated cache so pressure is easy to provoke
    from flexflow_tpu.generation.cache import CacheConfig

    eng = GenerationEngine(
        decoder_params, CFG,
        CacheConfig(num_layers=1, num_heads=2, head_dim=8,
                    num_blocks=6, block_size=8),
        max_batch_slots=2, prompt_buckets=BUCKETS,
    )
    sched, _ = make_sched(eng)
    sampling = SamplingParams(max_new_tokens=24)
    hb = sched.submit([1] * 6, sampling, priority="best_effort")
    hi = sched.submit([2] * 6, sampling, priority="interactive")
    drain(sched, [hb, hi], steps=800)
    assert hb.result(timeout=0) and hi.result(timeout=0)
    # the best-effort stream absorbed every preemption
    assert hi._request.preemptions == 0
    assert sched.preemptions == 0 or hb._request.preemptions > 0


# ---------------------------------------------------------------------------
# AdaptiveLimiter
# ---------------------------------------------------------------------------


def _limiter(clock, *, queue_depth=lambda: 0, queue_p95=lambda: 0.0,
             ttft_p95=lambda: 0.0, cache_pressure=lambda: False, **cfg_kw):
    cfg = OverloadConfig(**cfg_kw)
    return AdaptiveLimiter(
        cfg, clock=clock, slots=4, max_queue=32,
        queue_depth=queue_depth, queue_p95=queue_p95, ttft_p95=ttft_p95,
        cache_pressure=cache_pressure,
    )


def test_limiter_aimd_convergence():
    """Sustained overload cuts the limit multiplicatively to the floor;
    recovery raises it additively back to the ceiling."""
    clock = FakeClock()
    hot = {"on": True}
    lim = _limiter(
        clock,
        queue_depth=lambda: 32 if hot["on"] else 0,
        queue_p95=lambda: 9.9 if hot["on"] else 0.0,
        limiter_interval_s=1.0, min_limit=4,
    )
    assert lim.limit == lim.max_limit == 36
    lim.tick()  # arms the interval
    cuts = 0
    for _ in range(12):
        clock.advance(1.0)
        if lim.tick() == "cut":
            cuts += 1
    assert lim.limit == 4  # converged to the floor, multiplicatively
    assert cuts >= 3
    hot["on"] = False
    for _ in range(40):
        clock.advance(1.0)
        lim.tick()
    assert lim.limit == 36  # additive recovery to the ceiling
    snap = lim.snapshot()
    assert snap["cuts_total"] == cuts and snap["raises_total"] >= 30


def test_limiter_occupancy_floor_blocks_benign_cuts():
    """Latency symptoms with an (almost) empty queue never cut — the
    limiter's half of the inertness property
    (test_overload_machinery_inert_off_pressure_path holds the whole)."""
    clock = FakeClock()
    lim = _limiter(
        clock, queue_depth=lambda: 1, queue_p95=lambda: 99.0,
        limiter_interval_s=1.0,
    )
    lim.tick()
    for _ in range(10):
        clock.advance(1.0)
        lim.tick()
    assert lim.snapshot()["cuts_total"] == 0


@pytest.mark.parametrize("depth, cuts", [(64, False), (65, True)])
def test_limiter_floor_scales_with_slots(depth, cuts):
    """A queue as deep as the slots is one batch waiting its turn (a
    closed loop of 2 x slots clients), whatever share of ``max_queue``
    that is: 64 slots against the default 256 sit AT ``min_queue_frac``.
    One request more is a queue forming."""
    clock = FakeClock()
    lim = AdaptiveLimiter(
        OverloadConfig(limiter_interval_s=1.0), clock=clock, slots=64, max_queue=256,
        queue_depth=lambda: depth, queue_p95=lambda: 9.9, ttft_p95=lambda: 0.0,
        cache_pressure=lambda: False,
    )
    lim.tick()
    for _ in range(5):
        clock.advance(1.0)
        lim.tick()
    assert (lim.snapshot()["cuts_total"] > 0) == cuts


def test_limiter_priority_headroom():
    """Best-effort hits the limit first; interactive keeps a reserve."""
    clock = FakeClock()
    lim = _limiter(clock, min_limit=10, max_limit=10)
    for _ in range(9):
        assert lim.try_acquire("best_effort")   # 8 < 0.85*10 admits the 9th
    assert not lim.try_acquire("best_effort")   # 9 >= 8.5
    assert lim.try_acquire("standard")          # 9 < 10
    assert not lim.try_acquire("standard")      # 10 >= 10
    assert lim.try_acquire("interactive")       # 10 < 1.1*10
    assert not lim.try_acquire("interactive")   # 11 >= 11
    for _ in range(11):
        lim.release()
    assert lim.inflight == 0


# ---------------------------------------------------------------------------
# DegradeLadder
# ---------------------------------------------------------------------------


def test_ladder_hysteresis_and_levels():
    clock = FakeClock()
    transitions = []
    cfg = OverloadConfig(up_hold_s=1.0, down_hold_s=3.0)
    ladder = DegradeLadder(
        cfg, clock=clock,
        on_transition=lambda o, n, p: transitions.append((o, n)),
    )
    assert ladder.spec_cap() is None and ladder.max_new_cap("standard") is None
    # sustained high pressure climbs one level per hold window
    for _ in range(10):
        ladder.update(1.0)
        clock.advance(0.5)
    assert ladder.level == 4
    assert ladder.shed_best_effort()
    assert ladder.max_new_cap("best_effort") == cfg.max_new_caps["best_effort"]
    assert ladder.max_new_cap("interactive") is None
    # a mid-band blip resets BOTH timers: no flapping
    ladder.update(0.5)
    clock.advance(10.0)
    ladder.update(0.5)
    assert ladder.level == 4
    # sustained low pressure descends one level per (longer) hold
    steps_to_zero = 0
    for _ in range(40):
        if ladder.level == 0:
            break
        ladder.update(0.0)
        clock.advance(1.0)
        steps_to_zero += 1
    assert ladder.level == 0
    assert steps_to_zero >= 12  # 4 levels x 3s holds on a 1s tick
    # monotone up then down, one level at a time
    ups = [t for t in transitions if t[1] > t[0]]
    downs = [t for t in transitions if t[1] < t[0]]
    assert [t[1] for t in ups] == [1, 2, 3, 4]
    assert [t[1] for t in downs] == [3, 2, 1, 0]
    assert all(abs(n - o) == 1 for o, n in transitions)


def test_ladder_spec_caps():
    clock = FakeClock()
    ladder = DegradeLadder(OverloadConfig(up_hold_s=0.0), clock=clock)
    ladder.update(1.0)
    clock.advance(1.0)
    ladder.update(1.0)
    assert ladder.level == 1 and ladder.spec_cap() == 1
    clock.advance(1.0)
    ladder.update(1.0)
    assert ladder.level == 2 and ladder.spec_cap() == 0


def test_spec_cap_mid_stream_is_byte_exact(engine):
    """A speculative greedy stream whose window is capped (then
    disabled) mid-stream emits exactly the never-speculating stream —
    the ladder's levels 1-2 cannot corrupt surviving streams."""
    sampling = SamplingParams(max_new_tokens=16)
    prompt = [7, 8, 9, 7, 8, 9, 7, 8]
    ref = engine.generate([list(prompt)], sampling)[0]

    sched, clock = make_sched(engine)
    spec = SpeculationConfig(enabled=True, k=3, adaptive=False)
    h = sched.submit(prompt, sampling, speculation=spec)
    # force the ladder up as the stream decodes: level 1 after a few
    # steps, level 2 a few steps later
    ladder = sched.overload.ladder
    steps = 0
    while not h.done() and steps < 500:
        if steps == 3:
            ladder._level = 1  # cap k
        elif steps == 6:
            ladder._level = 2  # disable drafting
        sched.step()
        steps += 1
    assert h.result(timeout=0) == ref
    assert sched.overload.spec_cap() == 0  # level 2 held to the end


def test_max_new_clamp_applies_to_new_admissions_only(engine):
    cfg = OverloadConfig(max_new_caps={
        "interactive": None, "standard": 4, "best_effort": 2,
    })
    sched, _ = make_sched(engine, overload=cfg)
    sampling = SamplingParams(max_new_tokens=10)
    h_before = sched.submit([1, 2, 3], sampling, priority="standard")
    sched.overload.ladder._level = 3
    h_std = sched.submit([4, 5, 6], sampling, priority="standard")
    h_be = sched.submit([4, 5, 7], sampling, priority="best_effort")
    h_int = sched.submit([4, 5, 8], sampling, priority="interactive")
    sched.overload.ladder._level = 0
    drain(sched, [h_before, h_std, h_be, h_int])
    assert len(h_before.result(timeout=0)) == 10  # admitted pre-clamp
    assert len(h_std.result(timeout=0)) == 4
    assert len(h_be.result(timeout=0)) == 2
    assert len(h_int.result(timeout=0)) == 10


def test_level4_sheds_queued_best_effort(engine):
    sched, clock = make_sched(engine)
    sampling = SamplingParams(max_new_tokens=2)
    running = [sched.submit([1, 2, 3], sampling) for _ in range(3)]
    hb = sched.submit([9, 9, 9], sampling, priority="best_effort")
    sched.overload.ladder._level = 4
    # new best-effort refused with reason "degraded"
    with pytest.raises(OverloadedError) as ei:
        sched.submit([8, 8, 8], sampling, priority="best_effort")
    assert ei.value.reason == "degraded"
    # the tick sheds what was queued
    sched.step()
    with pytest.raises(OverloadedError) as ev:
        hb.result(timeout=0)
    assert ev.value.reason == "degraded"
    sched.overload.ladder._level = 0
    drain(sched, running)
    rej = sched.overload.rejections()
    assert rej["by_reason"]["degraded"] == 2
    assert rej["by_priority"]["best_effort"] == 2


# ---------------------------------------------------------------------------
# infeasibility fast-fail
# ---------------------------------------------------------------------------


def test_infeasible_fast_fail_pinned_roofline(engine):
    """With a pinned TTFT predictor, a deadline below the prediction is
    denied (typed, counted separately from sheds); a deadline above it
    is admitted."""
    sched, _ = make_sched(engine)
    sched.overload.ttft_predictor = lambda n, depth: 1.0  # pinned roofline
    sampling = SamplingParams(max_new_tokens=2)
    with pytest.raises(InfeasibleError) as ei:
        sched.submit([1, 2, 3], sampling, deadline_s=0.5)
    assert ei.value.reason == "infeasible"
    assert ei.value.predicted_ttft_s == 1.0
    acts = sched.overload.activations()
    assert acts["infeasible"] == 1 and acts["sheds"] == 0
    assert sched.stats.get("rejected_infeasible") == 1
    h = sched.submit([1, 2, 3], sampling, deadline_s=2.0)
    drain(sched, [h])
    assert h.result(timeout=0)


def test_default_predictor_scales_with_queue(engine):
    """The default roofline predictor is positive and grows with queue
    depth (each queued request costs ~one prefill ahead of yours)."""
    sched, _ = make_sched(engine)
    p0 = sched.overload.predicted_ttft_s(8)
    assert p0 is not None and p0 > 0
    base = sched.overload.ttft_predictor
    assert base(8, 4) > base(8, 0)


# ---------------------------------------------------------------------------
# fault site
# ---------------------------------------------------------------------------


def test_serving_admission_fault_site(engine):
    """The serving.admission site forces typed rejections
    deterministically — the chaos hook for limiter/shed paths."""
    sched, _ = make_sched(engine)
    sampling = SamplingParams(max_new_tokens=2)
    plan = FaultPlan(seed=0)
    plan.on(faults.SERVING_ADMISSION, mode="error",
            error=OverloadedError("forced", reason="limiter",
                                  priority="standard", retry_after_s=2.0),
            nth=(0,))
    with plan.active():
        with pytest.raises(OverloadedError) as ei:
            sched.submit([1, 2, 3], sampling)
        h = sched.submit([1, 2, 3], sampling)  # second call passes
    assert ei.value.reason == "limiter"
    assert plan.fired(faults.SERVING_ADMISSION) == 1
    drain(sched, [h])
    assert h.result(timeout=0)


# ---------------------------------------------------------------------------
# inertness
# ---------------------------------------------------------------------------


def test_overload_machinery_inert_off_pressure_path(engine):
    """A fault-free, unpressured run activates nothing: no throttles,
    cuts, sheds, infeasible denials, or ladder transitions, and none of
    the self-healing (engine restart, quarantine, watchdog, step retry)."""
    sched, clock = make_sched(engine)
    resets = engine.resets
    sampling = SamplingParams(max_new_tokens=4)
    handles = [sched.submit([i + 1, i + 2, i + 3], sampling)
               for i in range(6)]
    for _ in range(200):
        if all(h.done() for h in handles):
            break
        sched.step()
        clock.advance(0.05)  # cross limiter intervals while serving
    acts = sched.overload.activations()
    assert acts == {
        "throttled": 0, "limit_cuts": 0, "sheds": 0, "infeasible": 0,
        "rejected": 0, "degrade_transitions": 0, "degrade_level": 0,
    }
    rs = sched.recovery_stats
    assert (engine.resets - resets, rs.recoveries, rs.quarantined,
            rs.watchdog_trips, rs.step_retries) == (0,) * 5


# ---------------------------------------------------------------------------
# fleet: spill, fleet-wide shed, autoscale
# ---------------------------------------------------------------------------


def make_fleet(decoder_params, n=2, **fleet_kwargs):
    clock = fleet_kwargs.pop("clock", None) or FakeClock()

    def factory():
        return GenerationEngine(
            decoder_params, CFG, max_batch_slots=3, block_size=8,
            prompt_buckets=BUCKETS,
        )

    return Fleet(factory, n, clock=clock, warmup=False,
                 scheduler_kwargs=fleet_kwargs.pop("scheduler_kwargs", {}),
                 **fleet_kwargs), clock


def _saturate(replica):
    """Pin one replica's limiter shut (no admissions at any class)."""
    lim = replica.scheduler.overload.limiter
    with lim._lock:
        lim._limit = 0.0


def test_fleet_spills_past_saturated_replica(decoder_params):
    fleet, _ = make_fleet(decoder_params, n=2)
    r0, r1 = fleet.replicas
    _saturate(r0)
    sampling = SamplingParams(max_new_tokens=2)
    handles = [fleet.submit([1, 2, 3], sampling) for _ in range(3)]
    assert len(r0.scheduler._queue) + len(r0.scheduler._running) == 0
    assert fleet.fleet_stats.decisions().get("spill", 0) == 3
    for _ in range(200):
        if all(h.done() for h in handles):
            break
        fleet.step()
    assert all(h.result(timeout=0) for h in handles)


def test_fleet_shed_only_when_all_saturated(decoder_params):
    fleet, _ = make_fleet(decoder_params, n=2)
    for r in fleet.replicas:
        _saturate(r)
    sampling = SamplingParams(max_new_tokens=2)
    with pytest.raises(OverloadedError) as ei:
        fleet.submit([1, 2, 3], sampling)
    assert ei.value.reason == "limiter"
    assert ei.value.retry_after_s is not None
    assert fleet.fleet_stats.snapshot()["sheds"] == 1
    assert fleet.fleet_stats.decisions().get("fleet_shed") == 1


def test_autoscale_signal_sustained(decoder_params):
    """Want-more only after sustained all-replica saturation; recovery
    returns the signal to 0; sustained idleness asks for fewer."""
    fleet, clock = make_fleet(decoder_params, n=2)
    adv = fleet.autoscale
    assert adv.signal == 0
    for r in fleet.replicas:
        _saturate(r)
    fleet.check()
    assert adv.signal == 0  # not sustained yet
    clock.advance(adv.up_hold_s + 1.0)
    fleet.check()
    assert adv.signal == 1
    assert adv.want_replicas(2) == 3
    rep = fleet.autoscale_report()
    assert rep["signal"] == 1 and rep["want_replicas"] == 3
    assert set(rep["replicas"]) == {"r0", "r1"}
    # recovery: limiters reopen -> signal drops immediately...
    for r in fleet.replicas:
        lim = r.scheduler.overload.limiter
        with lim._lock:
            lim._limit = lim.max_limit
    fleet.check()
    assert adv.signal == 0
    # ...and sustained idleness asks for fewer
    clock.advance(adv.down_hold_s + 1.0)
    fleet.check()
    assert adv.signal == -1
    assert adv.want_replicas(2) == 1
    prom = fleet.prom_fleet()
    assert prom["autoscale"] == {"signal": -1, "want_replicas": 1}


# ---------------------------------------------------------------------------
# transport round trips
# ---------------------------------------------------------------------------


@pytest.mark.observability
def test_http_retry_after_round_trip(decoder_params):
    """An overloaded submit answers 503 with a Retry-After header and
    the structured reason/priority body over real HTTP."""
    from flexflow_tpu.serving import InferenceServer
    from flexflow_tpu.serving.generation import GenerationModel

    eng = GenerationEngine(
        decoder_params, CFG, max_batch_slots=3, block_size=8,
        prompt_buckets=BUCKETS,
    )
    model = GenerationModel(eng, name="lm")
    lim = model.scheduler.overload.limiter
    with lim._lock:
        lim._limit = 0.0  # every admission throttles
    srv = InferenceServer(port=0)
    srv.register_generation(model)
    srv.start()
    try:
        req = urllib.request.Request(
            f"http://127.0.0.1:{srv.port}/v2/models/lm/generate",
            data=json.dumps({
                "prompt": [1, 2, 3], "max_new_tokens": 2,
                "priority": "best_effort",
            }).encode(),
            headers={"Content-Type": "application/json"},
        )
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(req, timeout=30)
        err = ei.value
        assert err.code == 503
        assert int(err.headers["Retry-After"]) >= 1
        body = json.loads(err.read())
        assert body["reason"] == "limiter"
        assert body["priority"] == "best_effort"
        assert body["retry_after_s"] > 0
        # /v2/overload explains the refusal
        with urllib.request.urlopen(
            f"http://127.0.0.1:{srv.port}/v2/overload", timeout=30
        ) as r:
            rep = json.loads(r.read())["models"]["lm"]
        assert rep["rejections"]["by_reason"]["limiter"] == 1
        assert rep["rejections"]["by_priority"]["best_effort"] == 1
    finally:
        srv.stop()


@pytest.mark.observability
def test_grpc_retry_metadata_round_trip(decoder_params):
    """RESOURCE_EXHAUSTED with retry-after-ms + overload-* trailing
    metadata over real gRPC."""
    grpc = pytest.importorskip("grpc")
    from flexflow_tpu.serving.generation import GenerationModel
    from flexflow_tpu.serving.grpc_server import GrpcInferenceServer, pb

    eng = GenerationEngine(
        decoder_params, CFG, max_batch_slots=3, block_size=8,
        prompt_buckets=BUCKETS,
    )
    model = GenerationModel(eng, name="lm")
    lim = model.scheduler.overload.limiter
    with lim._lock:
        lim._limit = 0.0
    srv = GrpcInferenceServer(port=0)
    srv.register_generation(model)
    srv.start()
    try:
        channel = grpc.insecure_channel(f"127.0.0.1:{srv.port}")
        stream = channel.unary_stream(
            "/inference.GRPCInferenceService/ModelStreamInfer",
            request_serializer=lambda m: m.SerializeToString(),
            response_deserializer=pb.ModelInferResponse.FromString,
        )
        req = pb.ModelInferRequest(model_name="lm")
        t = req.inputs.add()
        t.name = "tokens"
        t.datatype = "INT32"
        t.shape.extend([3])
        t.contents.int_contents.extend([1, 2, 3])
        req.parameters["priority"].string_param = "best_effort"
        with pytest.raises(grpc.RpcError) as ei:
            list(stream(req, timeout=30))
        err = ei.value
        assert err.code() == grpc.StatusCode.RESOURCE_EXHAUSTED
        md = {k: v for k, v in (err.trailing_metadata() or ())}
        assert int(md["retry-after-ms"]) >= 1000
        assert md["overload-reason"] == "limiter"
        assert md["overload-priority"] == "best_effort"
        channel.close()
    finally:
        srv.stop()
