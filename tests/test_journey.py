"""Fleet-wide request journey tests (ISSUE 20): W3C traceparent
round-trips at the HTTP/gRPC ingress, parent-linked hop chains stitched
into ONE causal timeline across forced failover, disaggregated KV
handoff, and SIGKILL + WAL warm restart (all on virtual clocks), the
bounded on-disk span spool (ring eviction + torn-tail truncation), and
the off switches: ``observability=False`` and ``journeys=False`` must
both be fully inert AND byte-exact against the reference streams.

The core property is **single stitched journey, gap-free parent
links**: every non-root span's parent must exist somewhere in the
stitched set (``complete``), and — for requests that never crossed a
process death — the stitched span count must equal the context's
attempted-hop count, so a dropped span is a test failure, not a silent
gap. Warm-restarted journeys are held to completeness + single root
instead of the exact count: the WAL snapshot is taken at admission, so
hops recorded between the snapshot and the crash are real spans the
restored counter never saw.

Engines are deliberately tiny (1 layer / width 16, ONE prefill
bucket): every fresh GenerationEngine re-jits its program family, and
journey semantics are depth-independent.
"""
import json
import os
import urllib.request

import jax
import pytest

from flexflow_tpu.generation import (
    ContinuousBatchingScheduler,
    GenerationEngine,
    RecoveryPolicy,
    SamplingParams,
    init_decoder_params,
)
from flexflow_tpu.models.transformer import TransformerConfig
from flexflow_tpu.obs import (
    NULL_JOURNEY,
    JourneyIndex,
    JourneyRecorder,
    JourneySpan,
    JourneySpool,
    JourneyStats,
    format_traceparent,
    journey_to_chrome_trace,
    journey_to_otlp,
    parse_traceparent,
    stitch,
)
from flexflow_tpu.obs.trace import NULL_TRACE
from flexflow_tpu.runtime import faults
from flexflow_tpu.runtime.faults import FaultPlan, replica_kill

pytestmark = pytest.mark.journey

CFG = TransformerConfig(
    num_layers=1, hidden_size=16, num_heads=2, ff_size=32,
    seq_length=64, vocab_size=40, causal=True,
)
BUCKETS = (8,)
BLOCK = 8
NO_SLEEP = RecoveryPolicy(sleep=lambda _s: None)
TIGHT_BUDGET = RecoveryPolicy(max_restarts=1, sleep=lambda _s: None)

from conftest import FakeClock  # noqa: E402

PROMPTS = [[1, 2, 3], [4, 5, 6, 7], [9, 8, 7, 6, 5], [1, 2, 3, 4, 4]]
GREEDY = SamplingParams(max_new_tokens=8)

# a well-formed remote traceparent (the W3C spec's own example ids)
REMOTE_TRACE = "0af7651916cd43dd8448eb211c80319c"
REMOTE_SPAN = "b7ad6b7169203331"
REMOTE_TP = f"00-{REMOTE_TRACE}-{REMOTE_SPAN}-01"


@pytest.fixture(scope="module")
def decoder_params():
    return init_decoder_params(jax.random.key(0), CFG)


@pytest.fixture(autouse=True)
def _no_leaked_plan():
    yield
    assert faults.active_plan() is None, "a test leaked an installed FaultPlan"


def make_engine(decoder_params, slots=3):
    return GenerationEngine(
        decoder_params, CFG, max_batch_slots=slots, block_size=BLOCK,
        prompt_buckets=BUCKETS,
    )


def make_factory(decoder_params, slots=3):
    def factory():
        return make_engine(decoder_params, slots=slots)
    return factory


def drive(stepper, handles, steps=500):
    for _ in range(steps):
        if all(h.done() for h in handles):
            return
        stepper()


def span_names(journey):
    return [s["name"] for s in journey["spans"]]


def assert_gap_free(journey):
    """The acceptance property: exactly one root, every other span's
    parent present in the stitched set."""
    assert journey["complete"], journey
    assert journey["n_roots"] == 1
    ids = {s["span_id"] for s in journey["spans"]}
    dangling = [
        s for s in journey["spans"]
        if s["parent_id"] is not None and s["parent_id"] not in ids
    ]
    # the single root may carry a remote parent; nothing else may dangle
    assert len(dangling) <= 1, dangling


# ---------------------------------------------------------------------------
# traceparent parsing + context chain (no engine)
# ---------------------------------------------------------------------------


def test_traceparent_parse_format_round_trip():
    assert parse_traceparent(REMOTE_TP) == (REMOTE_TRACE, REMOTE_SPAN)
    # case-insensitive, whitespace-tolerant (header transports vary)
    assert parse_traceparent(f"  {REMOTE_TP.upper()}  ") == (
        REMOTE_TRACE, REMOTE_SPAN)
    assert parse_traceparent(format_traceparent(REMOTE_TRACE, REMOTE_SPAN)) \
        == (REMOTE_TRACE, REMOTE_SPAN)
    # rejections: missing, malformed, forbidden version, zero ids —
    # a bad header roots the journey locally, never fails the request
    for bad in (
        None, "", "garbage", "00-xyz-abc-01",
        f"ff-{REMOTE_TRACE}-{REMOTE_SPAN}-01",
        f"00-{'0' * 32}-{REMOTE_SPAN}-01",
        f"00-{REMOTE_TRACE}-{'0' * 16}-01",
        f"00-{REMOTE_TRACE[:-2]}-{REMOTE_SPAN}-01",
    ):
        assert parse_traceparent(bad) is None, bad


def test_context_chain_snapshot_restore():
    """Hops form a sequential parent chain; snapshot/restore preserves
    identity so a restored context's next hop parents onto the
    pre-crash tip."""
    clock = FakeClock()
    rec = JourneyRecorder(lane="r0", clock=clock)
    ctx = rec.mint(parent=parse_traceparent(REMOTE_TP))
    assert ctx.journey_id == REMOTE_TRACE and ctx.remote_parent
    s1 = ctx.hop("ingress", transport="http")
    clock.advance(0.5)
    s2 = ctx.hop("submit")
    spans = rec.spans(REMOTE_TRACE)
    assert [s.name for s in spans] == ["ingress", "submit"]
    assert spans[0].parent_id == REMOTE_SPAN  # joined the remote chain
    assert spans[1].parent_id == s1
    assert ctx.hops == 2
    assert ctx.traceparent() == format_traceparent(REMOTE_TRACE, s2)
    assert rec.stats.remote_parents == 1 and rec.stats.spans == 2

    snap = ctx.snapshot()
    restored = ctx.__class__.restore(snap)
    assert restored.journey_id == REMOTE_TRACE
    assert restored.hops == 2 and restored.remote_parent
    restored.recorder = rec
    restored.hop("warm_restart")
    warm = rec.spans(REMOTE_TRACE)[-1]
    assert warm.parent_id == s2  # parented onto the pre-crash tip

    # the stitched chain is complete: one (remote-parented) root
    assert_gap_free(stitch(REMOTE_TRACE, rec.spans(REMOTE_TRACE)))


def test_null_journey_is_inert():
    assert NULL_JOURNEY.hop("anything", key=1) is None
    assert NULL_JOURNEY.traceparent() is None
    assert NULL_JOURNEY.snapshot() is None
    assert NULL_JOURNEY.journey_id is None and NULL_JOURNEY.hops == 0


def test_stitch_flags_missing_span_as_incomplete():
    """Removing a mid-chain span splits the tree into two roots —
    ``complete`` goes False, which is exactly what the chaoscheck
    completeness gates key on."""
    rec = JourneyRecorder(lane="r0", clock=FakeClock())
    ctx = rec.mint()
    for name in ("submit", "admit", "prefill", "finish"):
        ctx.hop(name)
    spans = rec.spans(ctx.journey_id)
    full = stitch(ctx.journey_id, spans)
    assert full["complete"] and full["n_spans"] == ctx.hops == 4
    assert span_names(full) == ["submit", "admit", "prefill", "finish"]
    gapped = stitch(ctx.journey_id, [s for s in spans if s.name != "admit"])
    assert not gapped["complete"] and gapped["n_roots"] == 2


def test_renderings_cover_all_lanes_and_spans():
    recs = [JourneyRecorder(lane=l, clock=FakeClock()) for l in ("http", "r0")]
    ctx = recs[0].mint()
    ctx.hop("ingress")
    ctx.recorder = recs[1]  # adoption retargets the lane
    ctx.hop("admit")
    journey = JourneyIndex(recorders=recs).get(ctx.journey_id)
    assert journey["lanes"] == ["http", "r0"]
    chrome = journey_to_chrome_trace(journey)
    events = [e for e in chrome["traceEvents"] if e["ph"] == "X"]
    assert len(events) == 2
    assert {e["args"]["lane"] for e in events} == {"http", "r0"}
    otlp = journey_to_otlp(journey)
    assert len(otlp["resourceSpans"]) == 2  # one resource per lane
    names = [
        sp["name"]
        for rs in otlp["resourceSpans"]
        for sc in rs["scopeSpans"] for sp in sc["spans"]
    ]
    assert sorted(names) == ["admit", "ingress"]


# ---------------------------------------------------------------------------
# on-disk span spool: ring bound + torn-tail truncation (no engine)
# ---------------------------------------------------------------------------


def _span(i, jid="j" * 32):
    return JourneySpan(jid, f"{i:016x}", None, f"hop{i}", "r0",
                       float(i), float(i) + 0.5, {"i": i})


def test_spool_ring_bounded_evicts_oldest(tmp_path):
    d = str(tmp_path / "journeys")
    spool = JourneySpool(d, max_bytes=4096, segment_bytes=1024)
    for i in range(200):
        spool.append(_span(i))
    spool.close()
    files = [f for f in os.listdir(d) if f.endswith(".seg")]
    total = sum(os.path.getsize(os.path.join(d, f)) for f in files)
    # bounded: at most the budget plus one in-flight segment
    assert total <= 4096 + 1024, (total, files)
    spans, torn = spool.scan()
    assert torn == 0
    got = [s.attrs["i"] for s in spans]
    assert got == sorted(got)  # oldest-first within what survived
    assert 199 in got and 0 not in got  # newest kept, oldest evicted


def test_spool_torn_tail_truncated_and_counted(tmp_path):
    d = str(tmp_path / "journeys")
    stats = JourneyStats()
    spool = JourneySpool(d, stats=stats)
    for i in range(3):
        spool.append(_span(i))
    spool.close()
    (seg,) = [f for f in os.listdir(d) if f.endswith(".seg")]
    path = os.path.join(d, seg)
    with open(path, "ab") as f:
        f.write(b"\x40\x00\x00\x00\xde\xad\xbe\xefcrash")  # torn frame
    spans, torn = spool.scan()
    assert torn == 1 and stats.spool_truncated == 1
    assert [s.attrs["i"] for s in spans] == [0, 1, 2]
    # the tear was truncated IN PLACE: a rescan is clean
    spans2, torn2 = spool.scan()
    assert torn2 == 0 and [s.attrs["i"] for s in spans2] == [0, 1, 2]


def test_index_merges_ring_and_spool_without_double_count(tmp_path):
    """A journey split across a dead process's spool and a live ring
    stitches into one complete timeline; a span present in BOTH (the
    live ring mirrors into the spool) is counted once."""
    spool = JourneySpool(str(tmp_path / "journeys"))
    rec = JourneyRecorder(lane="r0", clock=FakeClock(), spool=spool)
    ctx = rec.mint()
    ctx.hop("submit")
    ctx.hop("admit")  # both hops now in ring AND spool
    journey = JourneyIndex(recorders=[rec], spools=[spool]).get(ctx.journey_id)
    assert journey["n_spans"] == 2 == ctx.hops
    assert_gap_free(journey)
    # process death: the ring is gone, the spool alone still stitches
    from_spool = JourneyIndex(spools=[spool]).get(ctx.journey_id)
    assert from_spool["n_spans"] == 2
    assert_gap_free(from_spool)
    spool.close()


# ---------------------------------------------------------------------------
# HTTP + gRPC ingress round-trips (one shared engine/server)
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def served(decoder_params):
    from flexflow_tpu.serving import InferenceServer
    from flexflow_tpu.serving.generation import GenerationModel

    srv = InferenceServer(port=0)
    model = GenerationModel(make_engine(decoder_params), name="lm")
    srv.register_generation(model)
    srv.start()
    yield srv, model
    srv.stop()


def test_http_traceparent_in_out_and_debug_endpoint(served):
    srv, _model = served
    base = f"http://127.0.0.1:{srv.port}"
    req = urllib.request.Request(
        f"{base}/v2/models/lm/generate",
        data=json.dumps({"prompt": [1, 2, 3], "max_new_tokens": 6}).encode(),
        headers={"Content-Type": "application/json",
                 "traceparent": REMOTE_TP},
    )
    r = urllib.request.urlopen(req, timeout=60)
    body = json.loads(r.read())
    # the client's trace id IS the journey id — external tracers join
    assert body["journey_id"] == REMOTE_TRACE
    tp_out = r.headers["traceparent"]
    assert parse_traceparent(tp_out)[0] == REMOTE_TRACE

    dbg = json.loads(urllib.request.urlopen(
        f"{base}/v2/debug/journey/{REMOTE_TRACE}", timeout=30).read())
    journey = dbg["journey"]
    assert_gap_free(journey)
    names = span_names(journey)
    for hop in ("ingress", "submit", "admit", "prefill", "finish"):
        assert hop in names, names
    assert "http" in journey["lanes"] and len(journey["lanes"]) >= 2
    assert dbg["chrome_trace"]["traceEvents"]
    assert dbg["otlp"]["resourceSpans"]
    listing = json.loads(urllib.request.urlopen(
        f"{base}/v2/debug/journey", timeout=30).read())
    assert REMOTE_TRACE in listing["journeys"]

    # a malformed header must root locally, never fail the request
    bad = urllib.request.Request(
        f"{base}/v2/models/lm/generate",
        data=json.dumps({"prompt": [4, 5], "max_new_tokens": 4}).encode(),
        headers={"Content-Type": "application/json",
                 "traceparent": "ff-bogus"},
    )
    body2 = json.loads(urllib.request.urlopen(bad, timeout=60).read())
    assert body2["journey_id"] and body2["journey_id"] != REMOTE_TRACE


def test_grpc_metadata_traceparent_round_trip(served):
    grpc = pytest.importorskip("grpc")
    from flexflow_tpu.serving.grpc_server import GrpcInferenceServer, pb

    srv, _model = served
    gsrv = GrpcInferenceServer(port=0, http_server=srv)
    gsrv.start()
    try:
        channel = grpc.insecure_channel(f"127.0.0.1:{gsrv.port}")
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
        t.contents.int_contents.extend([7, 8, 9])
        req.parameters["max_new_tokens"].int64_param = 4
        tp = f"00-{'ab' * 16}-{'cd' * 8}-01"
        call = stream(req, timeout=60, metadata=(("traceparent", tp),))
        responses = list(call)
        final = responses[-1]
        assert final.parameters["journey_id"].string_param == "ab" * 16
        trailing = {k: v for k, v in (call.trailing_metadata() or ())}
        assert parse_traceparent(trailing["traceparent"])[0] == "ab" * 16
        # the gRPC ingress shares the HTTP server's recorder: one index
        # covers both transports
        journey = srv.journey_index().get("ab" * 16)
        assert_gap_free(journey)
        assert "ingress" in span_names(journey)
        channel.close()
    finally:
        gsrv.stop()


# ---------------------------------------------------------------------------
# the off switches: inert AND byte-exact
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def switch_engine(decoder_params):
    """One engine for both tests of the switches: each drains it fully."""
    return make_engine(decoder_params)


def test_journeys_off_is_inert_and_byte_exact(switch_engine):
    """``observability=False`` (everything off) and ``journeys=False``
    (tracing on, journeys off) both produce byte-identical streams to
    the engine's own reference, with NULL contexts end to end."""
    eng = switch_engine
    ref = [eng.generate([list(p)], GREEDY)[0] for p in PROMPTS]

    for kwargs, trace_expected in (
        (dict(observability=False), False),
        (dict(journeys=False), True),
    ):
        sched = ContinuousBatchingScheduler(
            eng, recovery=NO_SLEEP, clock=FakeClock(), **kwargs)
        assert sched.journeys is None
        handles = [sched.submit(p, GREEDY) for p in PROMPTS]
        reqs = [h._request for h in handles]
        assert all(r.journey is NULL_JOURNEY for r in reqs)
        if not trace_expected:
            assert all(r.trace is NULL_TRACE for r in reqs)
        drive(sched.step, handles)
        assert [h.result(0) for h in handles] == [list(t) for t in ref], \
            f"journeys-off arm forked a stream ({kwargs})"
        assert all(r.journey is NULL_JOURNEY for r in reqs)  # stayed null
        assert sched.journey_stats.spans == 0
    # full drain: every block is back, or warm in the prefix index
    from conftest import assert_blocks_conserved
    assert_blocks_conserved(eng)


def test_journeys_on_is_byte_exact_and_every_journey_stitches(switch_engine):
    """The on side of the switch, on one scheduler with no fleet around
    it: a batch that joins and leaves mid-flight streams what the
    journeys-off scheduler streams, and every request's journey stitches
    gap-free with every hop it took."""
    eng = switch_engine

    def run(**kwargs):
        sched = ContinuousBatchingScheduler(
            eng, recovery=NO_SLEEP, clock=FakeClock(), **kwargs)
        handles = [sched.submit(p, GREEDY) for p in PROMPTS]
        drive(sched.step, handles)
        return [h.result(0) for h in handles], handles, sched

    off, _, _ = run(journeys=False)
    on, handles, sched = run()
    assert on == off
    assert sched.journey_stats.spans > 0
    index = JourneyIndex().add(sched.journeys)
    for h in handles:
        journey = index.get(h._request.journey.journey_id)
        assert_gap_free(journey)
        assert journey["n_spans"] == h._request.journey.hops


# ---------------------------------------------------------------------------
# stitching across forced failover (virtual-clock fleet)
# ---------------------------------------------------------------------------


def test_failover_yields_single_stitched_journey(decoder_params):
    from flexflow_tpu.serving.fleet import Fleet

    fleet = Fleet(
        make_factory(decoder_params), 2, clock=FakeClock(),
        scheduler_kwargs=dict(recovery=TIGHT_BUDGET),
    )
    plan = FaultPlan(seed=0)
    replica_kill(plan, "r0", every=1)
    with plan.active():
        handles = [fleet.submit(p, GREEDY) for p in PROMPTS]
        drive(fleet.step, handles)
    assert all(h.done() for h in handles)
    assert fleet.fleet_stats.snapshot()["failovers"] == 1

    index = JourneyIndex(recorders=fleet.journey_recorders())
    migrated = 0
    for h in handles:
        req = h._request
        journey = index.get(req.journey.journey_id)
        assert journey is not None
        assert_gap_free(journey)
        # exact completeness: every attempted hop survived stitching
        assert journey["n_spans"] == req.journey.hops
        names = span_names(journey)
        if "failover" in names:
            migrated += 1
            assert "adopt" in names
            # the journey crossed replicas: router lane + both schedulers
            assert len(journey["lanes"]) >= 3, journey["lanes"]
    assert migrated >= 1
    fleet.stop()


# ---------------------------------------------------------------------------
# stitching across the disaggregated prefill -> decode handoff
# ---------------------------------------------------------------------------


def test_disagg_handoff_yields_single_stitched_journey(decoder_params):
    from flexflow_tpu.serving.fleet import DisaggregatedFleet

    dfleet = DisaggregatedFleet(
        make_factory(decoder_params), n_prefill=1, n_decode=1,
        clock=FakeClock(), handoff_backoff_s=0.0,
        scheduler_kwargs=dict(recovery=NO_SLEEP),
    )
    handles = [dfleet.submit(p, GREEDY) for p in PROMPTS[:2]]
    drive(dfleet.step, handles)
    assert all(h.done() for h in handles)

    index = JourneyIndex(recorders=dfleet.journey_recorders())
    for h in handles:
        req = h._request
        journey = index.get(req.journey.journey_id)
        assert_gap_free(journey)
        assert journey["n_spans"] == req.journey.hops
        names = span_names(journey)
        for hop in ("kv_handoff_pack", "kv_handoff", "adopt", "finish"):
            assert hop in names, names
        lanes = journey["lanes"]
        assert any(l.startswith("p") for l in lanes), lanes
        assert any(l.startswith("d") for l in lanes), lanes
    dfleet.stop()


# ---------------------------------------------------------------------------
# stitching across simulated process death + WAL warm restart
# ---------------------------------------------------------------------------


def test_warm_restart_keeps_journey_identity_and_stitches(
        tmp_path, decoder_params):
    """Process death mid-decode (scheduler + Durability abandoned, the
    SIGKILL shape): the WAL admission snapshot restores each stream's
    journey id, post-restart hops parent onto the pre-crash chain tip
    via the on-disk spool, and the successor's ring + the spool ALONE
    stitch one complete journey — the dead process's ring is
    deliberately never consulted."""
    from flexflow_tpu.serving.durable import Durability, DurabilityConfig

    sched = ContinuousBatchingScheduler(
        make_engine(decoder_params), recovery=NO_SLEEP, clock=FakeClock())
    Durability(sched, DurabilityConfig(wal_dir=str(tmp_path), fsync=False))
    handles = [sched.submit(p, GREEDY) for p in PROMPTS[:3]]
    for _ in range(5):
        sched.step()
    assert any(not h.done() for h in handles), "died too late to test replay"
    pre_crash = {
        tuple(h._request.original_prompt): h._request.journey.journey_id
        for h in handles
    }
    assert all(pre_crash.values())
    # process death: no close, no flush — page cache keeps the spool

    sched2 = ContinuousBatchingScheduler(
        make_engine(decoder_params), recovery=NO_SLEEP, clock=FakeClock())
    dur2 = Durability(sched2, DurabilityConfig(wal_dir=str(tmp_path),
                                               fsync=False))
    dur2.warm_restart()
    adopted = [e.req for e in sched2.journal.entries()]
    assert adopted
    drive(sched2.step, [r.handle for r in adopted])

    index = JourneyIndex().add(sched2.journeys).add_spool(dur2.journey_spool)
    for req in adopted:
        jid = req.journey.journey_id
        # identity survived the process: same id as before the crash
        assert jid == pre_crash[tuple(req.original_prompt)]
        journey = index.get(jid)
        assert_gap_free(journey)
        names = span_names(journey)
        for hop in ("submit", "warm_restart", "adopt", "finish"):
            assert hop in names, names
    dur2.close()
