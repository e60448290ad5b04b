"""Observability tests: request tracing (TTFT/TPOT/queue time), the
engine flight recorder, and the Prometheus exposition.

Acceptance criteria covered (ISSUE 5):
  * a generation request served over HTTP exposes a complete trace with
    queue-time, TTFT, and TPOT (/v2/debug/traces + error embedding)
  * GET /metrics emits valid Prometheus text covering every
    pre-existing /v2/stats counter and gauge (golden-file pinned)
  * an induced engine restart and a quarantine each capture a
    flight-recorder snapshot containing the failing step
  * satellite fixes: nearest-rank percentiles, gauge registration vs
    snapshot race, exact counters under concurrent hammering
"""
import json
import os
import threading
import urllib.error
import urllib.request

import jax
import numpy as np
import pytest

from flexflow_tpu.generation import (
    ContinuousBatchingScheduler,
    GenerationEngine,
    PoisonedRequestError,
    RecoveryPolicy,
    SamplingParams,
    init_decoder_params,
)
from flexflow_tpu.models.transformer import TransformerConfig
from flexflow_tpu.obs import (
    FlightRecorder,
    PredictionLedger,
    RequestTrace,
    StepAnatomy,
    TraceRing,
    render_prometheus,
    validate_exposition,
)
from flexflow_tpu.runtime import faults
from flexflow_tpu.runtime.faults import FaultPlan
from flexflow_tpu.serving import InferenceServer
from flexflow_tpu.serving.generation import GenerationModel
from flexflow_tpu.serving.stats import Histogram, LatencyWindow, ServingStats, TokenRate

pytestmark = pytest.mark.observability

CFG = TransformerConfig(
    num_layers=2, hidden_size=32, num_heads=4, ff_size=64,
    seq_length=64, vocab_size=50, causal=True,
)


from conftest import FakeClock  # noqa: E402


@pytest.fixture(scope="module")
def decoder_params():
    return init_decoder_params(jax.random.key(0), CFG)


@pytest.fixture(scope="module")
def engine(decoder_params):
    return GenerationEngine(
        decoder_params, CFG, max_batch_slots=3, block_size=8,
        prompt_buckets=(8, 16, 32, 64),
    )


@pytest.fixture(autouse=True)
def _no_leaked_plan():
    yield
    assert faults.active_plan() is None


# ---------------------------------------------------------------- satellites
def test_percentiles_nearest_rank():
    w = LatencyWindow(maxlen=16)
    w.record(1.0)
    w.record(2.0)
    snap = w.snapshot()
    # nearest rank: p50 of 2 samples is the FIRST, not the max
    assert snap["p50_s"] == 1.0
    assert snap["p95_s"] == 2.0
    assert snap["p99_s"] == 2.0

    w2 = LatencyWindow(maxlen=128)
    for i in range(100):
        w2.record((i + 1) / 100.0)
    snap = w2.snapshot()
    assert snap["p50_s"] == pytest.approx(0.50)
    assert snap["p95_s"] == pytest.approx(0.95)
    assert snap["p99_s"] == pytest.approx(0.99)

    w3 = LatencyWindow()
    w3.record(0.25)
    assert w3.snapshot()["p50_s"] == 0.25
    assert LatencyWindow().snapshot()["p50_s"] == 0.0


def test_gauge_registration_during_snapshot():
    """A model loading mid-scrape registers gauges while snapshot()
    iterates — must never raise 'dictionary changed size'."""
    stats = ServingStats()
    stop = threading.Event()
    errors = []

    def register():
        i = 0
        while not stop.is_set():
            stats.add_gauge(f"g{i % 997}", lambda i=i: i)
            i += 1

    def scrape():
        try:
            while not stop.is_set():
                stats.snapshot()
                stats.gauge_values()
        except Exception as e:  # pragma: no cover - the bug under test
            errors.append(e)

    threads = [threading.Thread(target=register) for _ in range(2)]
    threads += [threading.Thread(target=scrape) for _ in range(2)]
    for t in threads:
        t.start()
    import time as _time

    _time.sleep(0.4)
    stop.set()
    for t in threads:
        t.join(timeout=5)
    assert not errors, f"snapshot raced gauge registration: {errors[0]!r}"


def test_concurrent_stats_exact_totals():
    """Hammer counters/windows/histograms/token-rate/trace-ring from N
    threads while scraping /metrics-style renders; totals must be exact
    and no scrape may raise."""
    stats = ServingStats()
    rate = TokenRate(clock=lambda: 0.0)
    ring = TraceRing(capacity=64)
    n_threads, n_iter = 8, 500
    stop = threading.Event()
    errors = []

    def writer(tid):
        for i in range(n_iter):
            stats.incr("admitted")
            stats.incr("completed")
            stats.latency.record(0.001 * (i % 7))
            stats.observe("ttft", 0.002)
            stats.observe("queue_time", 0.0005)
            rate.record(3)
            tr = RequestTrace(tid * n_iter + i, clock=lambda: 0.0)
            tr.mark_accept(prompt_len=4)
            tr.mark_finish("completed")
            ring.add(tr)

    def scraper():
        try:
            while not stop.is_set():
                text = render_prometheus({"m": stats})
                assert not validate_exposition(text)
                stats.snapshot()
                ring.recent(8)
        except Exception as e:
            errors.append(e)

    scrapers = [threading.Thread(target=scraper) for _ in range(2)]
    writers = [threading.Thread(target=writer, args=(t,)) for t in range(n_threads)]
    for t in scrapers + writers:
        t.start()
    for t in writers:
        t.join(timeout=60)
    stop.set()
    for t in scrapers:
        t.join(timeout=10)
    assert not errors, f"scrape failed mid-hammer: {errors[0]!r}"
    total = n_threads * n_iter
    assert stats.get("admitted") == total
    assert stats.get("completed") == total
    assert stats.latency.count == total
    assert stats.histogram_snapshots()["ttft"]["count"] == total
    assert stats.window_snapshots()["queue_time"]["count"] == total
    assert rate.total == 3 * total
    assert ring.total == total
    assert len(ring) == 64  # bounded


def test_histogram_cumulative_buckets():
    h = Histogram(buckets=(0.001, 0.01, 0.1))
    for v in (0.0005, 0.005, 0.05, 0.5, 5.0):
        h.observe(v)
    snap = h.snapshot()
    assert snap["count"] == 5
    assert snap["sum"] == pytest.approx(5.5555)
    les = [le for le, _ in snap["buckets"]]
    assert les[-1] == float("inf")
    cums = [c for _, c in snap["buckets"]]
    assert cums == [1, 2, 3, 5]  # cumulative, +Inf catches the tail


# ----------------------------------------------------------------- exposition
def _golden_stats():
    """Deterministic stats for the golden rendering (binary-exact
    floats only, so repr() round-trips identically everywhere)."""
    s = ServingStats(latency_window=8)
    s.incr("admitted", 3)
    s.incr("completed", 2)
    s.incr("failed", 1)
    s.incr("drafter_errors")  # dynamic counter joins the family
    s.latency.record(0.25)
    s.latency.record(0.5)
    s.observe("ttft", 0.25)
    s.observe("ttft", 0.5)
    s.observe("tpot", 0.125)
    s.add_gauge("queue_depth", lambda: 2)
    s.add_gauge("cache_occupancy", lambda: 0.25)
    s.add_gauge("dead_gauge", lambda: 1 / 0)  # must be skipped, not fatal
    # PR 6 capacity/compute/SLO families (binary-exact values)
    s.add_gauge("cache_frag_slots", lambda: 5)
    s.add_gauge("cache_pressure_time_s", lambda: 1.5)
    s.add_gauge("cache_admission_waits", lambda: 1)
    s.add_gauge("mfu", lambda: 0.125)
    s.add_gauge("achieved_tflops", lambda: 0.5)
    # ISSUE 15 mesh families (binary-exact values)
    s.add_gauge("mesh_devices", lambda: 4)
    s.add_gauge("tp_degree", lambda: 4)
    s.add_gauge("cache_shard_bytes", lambda: 4096)
    s.add_gauge("cache_shard_heads", lambda: 2)
    s.add_gauge("goodput_tokens_total", lambda: 8)
    s.add_gauge("goodput_ratio", lambda: 0.75)
    s.add_gauge("slo_ttft_p95_burn_fast", lambda: 2)
    s.add_gauge("slo_breaching_total", lambda: 1)
    # PR 7 truth families (binary-exact values)
    s.add_gauge("perf_prediction_pairs", lambda: 4)
    s.add_gauge("perf_prediction_error_p50", lambda: 0.5)
    s.add_gauge("perf_prediction_error_max", lambda: 2)
    s.add_gauge("perf_drift_alarms", lambda: 1)
    # prefix caching / KV tiering families (binary-exact values)
    s.add_gauge("prefix_cache_hit_ratio", lambda: 0.75)
    s.add_gauge("prefix_cache_blocks_reused_total", lambda: 6)
    s.add_gauge("prefix_cache_tokens_reused_total", lambda: 96)
    s.add_gauge("prefix_cache_cow_copies_total", lambda: 1)
    s.add_gauge("prefix_cache_swaps_in_total", lambda: 2)
    s.add_gauge("prefix_cache_swaps_out_total", lambda: 3)
    s.add_gauge("prefix_cache_host_bytes", lambda: 4096)
    s.add_gauge("prefix_cache_resident_blocks", lambda: 5)
    s.add_gauge("prefix_cache_offloaded_blocks", lambda: 2)
    s.add_gauge("prefix_cache_victim_pops_total", lambda: 9)
    s.add_gauge("prefix_cache_victim_stale_total", lambda: 4)
    s.add_gauge("prefix_cache_victim_keys", lambda: 3)
    # ISSUE 14 overload-control families (binary-exact values); the
    # per-reason/per-priority rejection split joins requests_total as
    # dynamic counters like drafter_errors above
    s.incr("rejected_limiter")
    s.incr("rejected_best_effort")
    s.add_gauge("overload_limit", lambda: 8)
    s.add_gauge("overload_inflight", lambda: 6)
    s.add_gauge("overload_throttled_total", lambda: 3)
    s.add_gauge("overload_limit_cuts_total", lambda: 2)
    s.add_gauge("overload_sheds_total", lambda: 1)
    s.add_gauge("overload_infeasible_total", lambda: 1)
    s.add_gauge("overload_queue_depth_interactive", lambda: 1)
    s.add_gauge("overload_queue_depth_standard", lambda: 2)
    s.add_gauge("overload_queue_depth_best_effort", lambda: 4)
    s.add_gauge("degrade_level", lambda: 2)
    s.add_gauge("degrade_transitions_total", lambda: 3)
    # ISSUE 12 step-anatomy families (binary-exact values)
    s.add_gauge("step_anatomy_steps_observed", lambda: 7)
    # ISSUE 16 disaggregated-serving KV import counters (binary-exact)
    s.add_gauge("kv_imports", lambda: 2)
    s.add_gauge("kv_imports_rejected", lambda: 1)
    # ISSUE 18 constrained-decoding families (binary-exact values)
    s.add_gauge("constrained_grammar_cache_hits_total", lambda: 3)
    s.add_gauge("constrained_grammar_cache_misses_total", lambda: 1)
    s.add_gauge("constrained_grammar_compile_seconds_total", lambda: 0.25)
    s.add_gauge("constrained_masked_steps_total", lambda: 12)
    s.add_gauge("constrained_dead_end_failures_total", lambda: 1)
    # ISSUE 19 durable-serving families (binary-exact values)
    s.add_gauge("durable_wal_appends_total", lambda: 9)
    s.add_gauge("durable_wal_bytes_total", lambda: 2048)
    s.add_gauge("durable_fsyncs_total", lambda: 4)
    s.add_gauge("durable_wal_append_failures_total", lambda: 1)
    s.add_gauge("durable_replayed_streams_total", lambda: 2)
    s.add_gauge("durable_replayed_tokens_total", lambda: 6)
    s.add_gauge("durable_torn_records_total", lambda: 1)
    s.add_gauge("durable_rolling_restarts_total", lambda: 1)
    s.add_gauge("durable_wal_segments", lambda: 2)
    # ISSUE 20 request-journey families (binary-exact values)
    s.add_gauge("journey_journeys_total", lambda: 3)
    s.add_gauge("journey_spans_total", lambda: 12)
    s.add_gauge("journey_spooled_spans_total", lambda: 6)
    s.add_gauge("journey_spool_truncated_total", lambda: 1)
    s.add_gauge("journey_remote_parents_total", lambda: 1)
    return s


def _golden_anatomy():
    """Deterministic step-anatomy snapshot for the
    flexflow_serving_step_phase_seconds family: one decode step with
    binary-exact span durations landing in distinct buckets (the
    observe path itself is pinned, not a hand-built dict)."""
    an = StepAnatomy(enabled=True)
    an.observe_step(
        "decode",
        [("dispatch", 0.0, 0.0005), ("block", 0.0005, 0.0025),
         ("execute", 0.0005, 0.0025), ("readback", 0.0025, 0.003),
         ("bookkeep", 0.003, 0.0035)],
        0.0, 0.004, tokens=2,
    )
    return an.prom_snapshot()


def _golden_ledger():
    """Deterministic prediction ledger for the flexflow_sim_* families:
    binary-exact predicted/measured (0.25 / 0.375 -> rel err exactly
    0.5, which also trips the drift alarm at the 4th pair), one key
    with quote + backslash to keep label-escaping pinned, and one
    unpredicted measurement."""
    led = PredictionLedger(clock=lambda: 0.0)
    led.predict("decode", 0.25, label="decode (v5e)",
                provenance="serving roofline")
    for _ in range(4):
        led.measure("decode", 0.375)
    tricky = 'op:LINEAR|pa"ram\\s|64x32:bf16|1'
    led.predict(tricky, 0.25, label="LINEAR 64x32 bf16",
                provenance="calibration table entry from (in-memory)")
    led.measure(tricky, 0.25)
    led.measure("op:unseen", 0.125)
    return led


def _golden_replica_stats():
    """A fleet replica's stats for the golden rendering: keyed by
    (model, replica), so every serving family carries the replica
    label (binary-exact values only)."""
    s = ServingStats(latency_window=8)
    s.incr("admitted", 2)
    s.incr("completed", 2)
    s.latency.record(0.25)
    s.add_gauge("queue_depth", lambda: 1)
    return s


def _golden_handoff_latency():
    """Deterministic handoff-latency histogram (binary-exact observes
    landing in distinct buckets)."""
    h = Histogram()
    h.observe(0.0625)
    h.observe(0.25)
    return h.snapshot()


_GOLDEN_FLEET = {
    "states": {"active": 1, "draining": 1, "dead": 0},
    "failovers_total": 1,
    "migrated_streams_total": 3,
    "replaced_total": 1,
    "router_decisions": {"affinity": 2, "least_loaded": 5, "spill": 1},
    "autoscale": {"signal": 1, "want_replicas": 3},
    # ISSUE 16 disaggregated serving: per-pool states + the KV handoff
    # protocol families (key-gated — unified fleets omit these keys and
    # render exactly as before)
    "pools": {
        "prefill": {"states": {"active": 1, "draining": 0, "dead": 0}},
        "decode": {"states": {"active": 2, "draining": 0, "dead": 1}},
    },
    "handoff": {
        "transfers": {"ok": 4, "corrupt": 1, "error": 1, "stalled": 1},
        "bytes_total": 4096,
        "replay_fallbacks_total": 3,
        "latency": _golden_handoff_latency(),
    },
}


def test_prometheus_golden_exposition():
    """The full exposition text is pinned: a metric rename breaks THIS
    test instead of everyone's dashboards."""
    text = render_prometheus(
        {"lm": _golden_stats(), ("gen", "r0"): _golden_replica_stats()},
        fault_sites={"generation.decode_step": {"calls": 5, "fires": 1}},
        ledger=_golden_ledger(),
        fleets={"gen": _GOLDEN_FLEET},
        anatomy={"lm": _golden_anatomy()},
    )
    assert not validate_exposition(text)
    golden_path = os.path.join(os.path.dirname(__file__), "data", "prometheus_golden.txt")
    with open(golden_path) as f:
        golden = f.read()
    assert text == golden, (
        "Prometheus exposition drifted from tests/data/prometheus_golden.txt.\n"
        "If the change is INTENTIONAL (new metric), regenerate the golden; "
        "if it renames an existing metric, don't — dashboards depend on it.\n"
        f"--- got ---\n{text}"
    )


def test_prometheus_label_escaping():
    s = ServingStats()
    s.incr("admitted")
    tricky = 'mo"del\\with\nnewline'
    text = render_prometheus({tricky: s})
    assert not validate_exposition(text)
    assert 'model="mo\\"del\\\\with\\nnewline"' in text


# -------------------------------------------------------------------- tracing
def test_trace_latency_decomposition_on_virtual_clock(engine):
    clock = FakeClock()
    sched = ContinuousBatchingScheduler(engine, clock=clock)
    h = sched.submit([1, 2, 3], SamplingParams(max_new_tokens=4))
    clock.advance(1.0)  # queued for exactly 1s
    sched.step()  # admit + prefill (first token)
    clock.advance(0.5)
    sched.step()  # decode
    clock.advance(0.5)
    while not h.done():
        if not sched.step():
            break
    assert h.result(timeout=0)
    tr = h.trace
    assert tr.queue_time_s == pytest.approx(1.0)
    assert tr.ttft_s == pytest.approx(1.0)
    # tokens 2..4 arrived over the two 0.5s advances -> tpot = 1.0 / 3
    assert tr.tpot_s == pytest.approx(1.0 / 3.0)
    d = tr.to_dict()
    assert d["outcome"] == "completed"
    names = [e["event"] for e in d["events"]]
    assert names[0] == "accept" and "admit" in names and "first_token" in names
    assert names[-1] == "finish"
    # the ring holds it, retrievable by id
    assert sched.trace_ring.get(tr.request_id) is tr
    # the stats windows were fed
    ws = sched.stats.window_snapshots()
    assert ws["queue_time"]["count"] >= 1 and ws["ttft"]["count"] >= 1
    assert ws["tpot"]["count"] >= 1


def test_observability_disabled_is_inert_and_exact(engine):
    on = ContinuousBatchingScheduler(engine, observability=True)
    off = ContinuousBatchingScheduler(engine, observability=False)
    prompts = [[1, 2, 3], [7, 6, 5, 4]]
    outs = {}
    for name, sched in (("on", on), ("off", off)):
        handles = [sched.submit(p, SamplingParams(max_new_tokens=6)) for p in prompts]
        while any(not h.done() for h in handles):
            if not sched.step():
                break
        outs[name] = [h.result(timeout=0) for h in handles]
    assert outs["on"] == outs["off"]  # tracing never changes the stream
    assert len(off.trace_ring) == 0
    assert off.flight.snapshot() == []
    assert len(on.trace_ring) == 2
    kinds = {r["kind"] for r in on.flight.snapshot()}
    assert "prefill" in kinds and "decode" in kinds
    rec = next(r for r in on.flight.snapshot() if r["kind"] == "decode")
    assert "device" in rec["phases"] and rec["phases"]["device"] >= 0
    assert {"occupancy", "queue_depth", "blocks_free", "seq"} <= set(rec)


def test_flight_recorder_ring_and_chrome_trace():
    fr = FlightRecorder(capacity=4, clock=FakeClock())
    for i in range(7):
        fr.record_step("decode", phases={"device": 0.001}, occupancy=i)
    snap = fr.snapshot()
    assert len(snap) == 4  # bounded
    assert [r["occupancy"] for r in snap] == [3, 4, 5, 6]
    assert [r["seq"] for r in snap] == [4, 5, 6, 7]
    trace = fr.to_chrome_trace()
    assert trace["traceEvents"]
    assert all({"name", "ph", "pid", "ts"} <= set(e) for e in trace["traceEvents"][1:])
    json.dumps(trace)  # chrome requires valid JSON


def test_quarantine_attaches_flight_snapshot(engine):
    """A NaN-poisoned request fails with the flight-recorder postmortem
    on the error, its trace in the ring, and the failing step in the
    snapshot."""
    sched = ContinuousBatchingScheduler(
        engine, recovery=RecoveryPolicy(sleep=lambda _s: None)
    )
    plan = FaultPlan(seed=0)
    plan.on("generation.decode_step", mode="nan", nth=(0,),
            select=lambda v: np.ones_like(np.asarray(v[1]), bool))
    with plan.active():
        h = sched.submit([1, 2, 3], SamplingParams(max_new_tokens=6))
        for _ in range(50):
            if h.done():
                break
            sched.step()
    with pytest.raises(PoisonedRequestError) as exc:
        h.result(timeout=0)
    snap = exc.value.flight_snapshot
    assert snap["kind"] == "quarantine"
    assert any(r["kind"] == "decode" for r in snap["records"])
    tr = sched.trace_ring.get(h.trace.request_id)
    assert tr is not None and tr.outcome == "PoisonedRequestError"
    assert any(e[1] == "quarantine" for e in tr.events)


def test_restart_incident_contains_failing_step(engine):
    """A crash-induced engine restart leaves a postmortem in
    flight.incidents with the step_failed marker, and the replayed
    request's trace records the replay."""
    sched = ContinuousBatchingScheduler(
        engine, recovery=RecoveryPolicy(sleep=lambda _s: None)
    )
    plan = FaultPlan(seed=0)
    plan.on("generation.decode_step", mode="error",
            error=RuntimeError("injected device crash"), nth=(1, 2))
    with plan.active():
        h = sched.submit([4, 5, 6], SamplingParams(max_new_tokens=8))
        for _ in range(100):
            if h.done():
                break
            sched.step()
    assert len(h.result(timeout=0)) == 8  # replayed to completion
    restarts = [i for i in sched.flight.incidents if i["kind"] == "restart"]
    assert restarts, [i["kind"] for i in sched.flight.incidents]
    assert any(r["kind"] == "step_failed" for r in restarts[-1]["records"])
    assert sched.recovery_stats.recoveries >= 1
    tr = sched.trace_ring.get(h.trace.request_id)
    assert tr.replays >= 1
    assert any(e[1] == "replay" for e in tr.events)
    kinds = {r["kind"] for r in sched.flight.snapshot()}
    assert "recovery" in kinds


# ----------------------------------------------------------------- HTTP e2e
@pytest.fixture(scope="module")
def gen_server(decoder_params):
    eng = GenerationEngine(
        decoder_params, CFG, max_batch_slots=3, block_size=8,
        prompt_buckets=(8, 16, 32, 64),
    )
    srv = InferenceServer(port=0)
    srv.register_generation(GenerationModel(eng, name="lm"))
    srv.start()
    yield srv
    srv.stop()


def _post(base, path, payload):
    req = urllib.request.Request(
        base + path, data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"},
    )
    try:
        with urllib.request.urlopen(req, timeout=120) as r:
            return r.status, json.loads(r.read())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read())


def test_http_generate_exposes_complete_trace_and_metrics(gen_server):
    base = f"http://127.0.0.1:{gen_server.port}"
    code, resp = _post(base, "/v2/models/lm/generate",
                       {"prompt": [1, 2, 3, 4], "max_new_tokens": 6})
    assert code == 200 and len(resp["tokens"]) == 6

    # complete trace over HTTP: queue time + TTFT + TPOT + waterfall
    traces = json.load(
        urllib.request.urlopen(f"{base}/v2/debug/traces", timeout=30)
    )["traces"]
    assert traces
    tr = traces[0]
    assert tr["model"] == "lm" and tr["transport"] == "http"
    assert tr["outcome"] == "completed"
    for k in ("queue_time_s", "ttft_s", "tpot_s"):
        assert tr[k] is not None and tr[k] >= 0.0
    names = [e["event"] for e in tr["events"]]
    assert "accept" in names and "admit" in names and "first_token" in names
    # retrievable individually by id
    one = json.load(urllib.request.urlopen(
        f"{base}/v2/debug/traces?id={tr['request_id']}", timeout=30
    ))["traces"]
    assert len(one) == 1 and one[0]["request_id"] == tr["request_id"]

    # /metrics: valid exposition, pre-existing counters + gauges + the
    # new histograms all present
    with urllib.request.urlopen(f"{base}/metrics", timeout=30) as r:
        assert r.headers["Content-Type"].startswith("text/plain")
        metrics = r.read().decode()
    assert not validate_exposition(metrics)
    stats_snapshot = gen_server.generators["lm"].stats.snapshot()
    for counter in ("admitted", "rejected", "expired", "completed", "failed", "cancelled"):
        assert f'outcome="{counter}"' in metrics
        assert counter in stats_snapshot
    for gauge in ("queue_depth", "running", "tokens_per_s", "cache_occupancy",
                  "recoveries", "watchdog_trips", "spec_acceptance_rate"):
        assert f"flexflow_serving_{gauge}{{" in metrics, gauge
    assert 'flexflow_serving_requests_total{model="lm",outcome="completed"}' in metrics
    for hist in ("ttft", "tpot", "queue_time"):
        count_line = [
            l for l in metrics.splitlines()
            if l.startswith(f"flexflow_serving_{hist}_seconds_count")
        ]
        assert count_line and float(count_line[0].rsplit(" ", 1)[1]) >= 1

    # timeline: chrome://tracing JSON with the decode steps on it
    tl = json.load(urllib.request.urlopen(f"{base}/v2/debug/timeline", timeout=30))
    assert {e["name"] for e in tl["traceEvents"]} >= {"prefill", "decode"}


def test_http_error_response_embeds_postmortem(gen_server):
    """A quarantined request's HTTP 500 carries trace + flight dump."""
    base = f"http://127.0.0.1:{gen_server.port}"
    plan = FaultPlan(seed=0)
    plan.on("generation.decode_step", mode="nan", nth=(0,),
            select=lambda v: np.ones_like(np.asarray(v[1]), bool))
    with plan.active():
        code, resp = _post(base, "/v2/models/lm/generate",
                           {"prompt": [9, 9, 1], "max_new_tokens": 6})
    assert code == 500
    assert resp["type"] == "PoisonedRequestError"
    assert resp["trace"]["outcome"] == "PoisonedRequestError"
    assert any(e["event"] == "quarantine" for e in resp["trace"]["events"])
    assert resp["flight"]["kind"] == "quarantine"
    assert any(r["kind"] == "decode" for r in resp["flight"]["records"])
    # fault-site hit counters were scrapeable while the plan was live
    with plan.active():
        metrics = urllib.request.urlopen(f"{base}/metrics", timeout=30).read().decode()
        assert 'flexflow_fault_site_calls_total{site="generation.decode_step"}' in metrics
        assert not validate_exposition(metrics)
