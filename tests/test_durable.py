"""Durable serving tests (ISSUE 19): WAL framing and lifecycle,
crash-safe journaling, byte-exact warm restart after simulated process
death, absolute-wall-deadline conversion across the down-window, the
SSE resume endpoint, and a virtual-clock rolling restart.

The core property under test is **restart exactness**: a process that
dies mid-decode (simulated by ABANDONING a scheduler + Durability
without closing either — exactly what SIGKILL leaves behind) must warm
restart into byte-identical streams, because tokens are a
deterministic function of (prompt, seed, count) and the journal holds
all three. The un-fsynced tail needs no special handling: replay
regrows it from the same recompute invariant PRs 4/8/16 proved for
preemption and failover.

Engines here are deliberately tiny (1 layer / width 16): every fresh
GenerationEngine re-jits its program family, and durability semantics
are depth-independent.
"""
import json
import os
import urllib.error
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
from flexflow_tpu.runtime import faults
from flexflow_tpu.runtime.faults import FaultPlan
from flexflow_tpu.runtime.wal import (
    WalCorruptionError,
    WriteAheadLog,
    encode_record,
    list_segments,
    replay_streams,
    scan_wal,
)
from flexflow_tpu.serving.durable import (
    Durability,
    DurabilityConfig,
    FingerprintMismatchError,
)

pytestmark = pytest.mark.durable

CFG = TransformerConfig(
    num_layers=1, hidden_size=16, num_heads=2, ff_size=32,
    seq_length=64, vocab_size=40, causal=True,
)
BUCKETS = (8, 32, 64)
BLOCK = 8
NO_SLEEP = RecoveryPolicy(sleep=lambda _s: None)

from conftest import FakeClock  # noqa: E402


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


def make_sched(engine, clock=None):
    return ContinuousBatchingScheduler(
        engine, recovery=NO_SLEEP, clock=clock or FakeClock()
    )


def drive(sched, handles, steps=500):
    for _ in range(steps):
        if all(h.done() for h in handles):
            return
        if not sched.step():
            return


_REF_ENGINE = None


def solo_reference(decoder_params, prompts, samplings):
    global _REF_ENGINE
    if _REF_ENGINE is None:
        _REF_ENGINE = make_engine(decoder_params)
    return [
        _REF_ENGINE.generate([list(p)], s)[0]
        for p, s in zip(prompts, samplings)
    ]


PROMPTS = [[1, 2, 3], [4, 5, 6, 7], [9, 8, 7, 6, 5]]
GREEDY = SamplingParams(max_new_tokens=12)
SEEDED = SamplingParams(max_new_tokens=12, temperature=0.8, top_k=10, seed=42)


# ---------------------------------------------------------------------------
# WAL layer: framing, torn tails, corruption, rotation, commit frontier
# ---------------------------------------------------------------------------


def test_wal_roundtrip_and_close(tmp_path):
    """Appended records come back in order from a fresh scan; the
    header record carries the writer's fingerprint; a closed log
    rejects further appends with the typed WalError."""
    from flexflow_tpu.runtime.wal import WalError

    d = str(tmp_path / "wal")
    wal = WriteAheadLog(d, fsync=False, fingerprint="fp-abc")
    recs = [{"t": "admit", "id": "s1", "prompt": [1, 2]},
            {"t": "tok", "id": "s1", "toks": [5, 6]},
            {"t": "end", "id": "s1", "outcome": "completed"}]
    for r in recs:
        wal.append(r)
    wal.flush()
    wal.close()
    got, torn = scan_wal(d)
    assert torn == 0
    assert [r for r in got if r.get("t") != "header"] == recs
    headers = [r for r in got if r.get("t") == "header"]
    assert headers and headers[0]["fp"] == "fp-abc"
    with pytest.raises(WalError):
        wal.append({"t": "tok", "id": "s1", "toks": [7]})
    wal.close()  # idempotent


def test_wal_torn_tail_truncated_and_counted(tmp_path):
    """A segment that simply ENDS early — the shape a crash mid-append
    leaves — is truncated in place and counted, and every record before
    the tear survives."""
    d = str(tmp_path / "wal")
    wal = WriteAheadLog(d, fsync=False)
    wal.append({"t": "admit", "id": "s1", "prompt": [1]})
    wal.append({"t": "tok", "id": "s1", "toks": [9, 9]})
    wal.flush()
    wal.close()
    (_, path), = list_segments(d)
    frame = encode_record({"t": "tok", "id": "s1", "toks": [3]})
    with open(path, "ab") as f:
        f.write(frame[: len(frame) - 3])  # cut mid-payload
    before = os.path.getsize(path)
    got, torn = scan_wal(d)
    assert torn == 1
    assert [r["t"] for r in got] == ["header", "admit", "tok"]
    assert os.path.getsize(path) == before - (len(frame) - 3)
    # rescanning the truncated file is clean
    assert scan_wal(d)[1] == 0


def test_wal_mid_file_corruption_is_typed(tmp_path):
    """A bad record with framed data AFTER it is not a torn tail —
    fsync promised that byte range, so the scan refuses with the typed
    WalCorruptionError instead of silently dropping durable records."""
    d = str(tmp_path / "wal")
    wal = WriteAheadLog(d, fsync=False)
    wal.append({"t": "admit", "id": "s1", "prompt": [1]})
    wal.append({"t": "end", "id": "s1", "outcome": "completed"})
    wal.flush()
    wal.close()
    (_, path), = list_segments(d)
    with open(path, "rb") as f:
        data = bytearray(f.read())
    # flip one payload byte of the FIRST record (skip its 8-byte frame
    # header); the records after it make this mid-file damage
    data[10] ^= 0xFF
    with open(path, "wb") as f:
        f.write(bytes(data))
    with pytest.raises(WalCorruptionError):
        scan_wal(d)


def test_wal_rotation_and_reap(tmp_path):
    """Tiny segments force rotation; a sealed segment whose streams all
    ENDed reaps on the next flush, while a still-open stream pins its
    admit segment on disk."""
    d = str(tmp_path / "wal")
    wal = WriteAheadLog(d, fsync=False, max_segment_bytes=256)
    for i in range(8):
        wal.append({"t": "admit", "id": f"s{i}", "prompt": [i] * 8})
        wal.append({"t": "tok", "id": f"s{i}", "toks": [1, 2, 3]})
        wal.append({"t": "end", "id": f"s{i}", "outcome": "completed"})
        wal.flush()
    assert wal.active_index > 0  # rotation actually happened
    # everything ENDed: only the active segment (and at most the one
    # just sealed before it) may remain
    assert wal.segment_count() <= 2
    assert wal.counters()["reaped_segments"] >= 1
    # an open stream pins its admit segment across later rotations
    wal.append({"t": "admit", "id": "pinned", "prompt": [7] * 8})
    wal.flush()
    seg_before = wal.active_index
    for i in range(8, 16):
        wal.append({"t": "admit", "id": f"s{i}", "prompt": [i] * 8})
        wal.append({"t": "end", "id": f"s{i}", "outcome": "completed"})
        wal.flush()
    assert wal.active_index > seg_before  # rotated past the pinned admit
    records, _ = scan_wal(d)
    assert any(r.get("id") == "pinned" and r["t"] == "admit"
               for r in records), "open stream's admit segment was reaped"
    wal.close()


def test_wal_predecessor_segments_survive_until_recovered(tmp_path):
    """A successor writer must NOT reap a dead sibling's segments on
    its own flushes — only mark_recovered (the warm-restart handshake)
    releases them."""
    d = str(tmp_path / "wal")
    dead = WriteAheadLog(d, fsync=False)
    dead.append({"t": "admit", "id": "s1", "prompt": [1]})
    dead.flush()  # never closed: simulated process death

    wal = WriteAheadLog(d, fsync=False)
    assert wal.active_index == dead.active_index + 1
    for i in range(4):
        wal.append({"t": "admit", "id": f"n{i}", "prompt": [i]})
        wal.append({"t": "end", "id": f"n{i}", "outcome": "completed"})
        wal.flush()
    indices = [idx for idx, _ in list_segments(d)]
    assert dead.active_index in indices, "predecessor segment reaped early"
    wal.mark_recovered()
    indices = [idx for idx, _ in list_segments(d)]
    assert dead.active_index not in indices
    wal.close()


def test_wal_commit_frontier_and_sync(tmp_path):
    """flush() only REQUESTS a commit (the paced committer owns the
    fsync); sync() blocks until the frontier covers everything written,
    so commit_lag is 0 right after it."""
    d = str(tmp_path / "wal")
    # an hour-long pacing interval: the committer will never get there
    # on its own inside this test, so a zero lag proves sync() did the
    # inline commit itself
    wal = WriteAheadLog(d, fsync=True, commit_interval_s=3600.0)
    wal.append({"t": "admit", "id": "s1", "prompt": [1]})
    wal.flush()
    wal.sync()
    wm = wal.watermark()
    assert wm["commit_lag"] == 0 and wm["unflushed"] == 0
    assert wal.counters()["fsyncs"] >= 1
    wal.close()


def test_replay_streams_orders_and_dedups(tmp_path):
    """replay_streams folds admit/tok/end by id: the NEWEST re-ADMIT
    wins (warm-restart idempotency), token deltas accumulate after it,
    and ended streams are marked."""
    records = [
        {"t": "admit", "id": "a", "prompt": [1], "generated": []},
        {"t": "tok", "id": "a", "toks": [5]},
        {"t": "admit", "id": "a", "prompt": [1], "generated": [5]},  # re-admit
        {"t": "tok", "id": "a", "toks": [6, 7]},
        {"t": "admit", "id": "b", "prompt": [2], "generated": []},
        {"t": "end", "id": "b", "outcome": "completed"},
    ]
    streams = {s.admit["id"]: s for s in replay_streams(records)}
    assert streams["a"].tokens == [5, 6, 7]
    assert not streams["a"].ended
    assert streams["b"].ended


# ---------------------------------------------------------------------------
# journal mirroring + warm restart exactness
# ---------------------------------------------------------------------------


def test_journal_mirrors_admissions_tokens_and_ends(tmp_path, decoder_params):
    """Every admission writes a full replay snapshot, each emitted
    token lands in a group-committed TOK delta, and completion writes
    exactly one END — the on-disk journal IS the stream."""
    eng = make_engine(decoder_params)
    sched = make_sched(eng)
    dur = Durability(sched, DurabilityConfig(wal_dir=str(tmp_path), fsync=False))
    handles = [sched.submit(p, GREEDY) for p in PROMPTS]
    drive(sched, handles)
    results = [h.result(0) for h in handles]
    dur.sync()
    dur.close()
    records, torn = scan_wal(str(tmp_path))
    assert torn == 0
    streams = {s.admit["id"]: s for s in replay_streams(records)}
    admits = [r for r in records if r["t"] == "admit"]
    assert len(admits) == 3
    by_prompt = {tuple(a["prompt"]): a["id"] for a in admits}
    for prompt, result in zip(PROMPTS, results):
        s = streams[by_prompt[tuple(prompt)]]
        assert s.tokens == list(result)
        assert s.ended
    ends = [r for r in records if r["t"] == "end"]
    assert len(ends) == 3 and all(e["outcome"] == "completed" for e in ends)
    # the admit snapshot carries everything replay needs
    assert admits[0]["sampling"]["max_new_tokens"] == 12
    assert admits[0]["max_new"] == 12


def test_fault_free_journal_is_a_pure_observer(tmp_path, decoder_params):
    """The same batch on one warmed engine with and without the WAL
    (real fsyncs): byte-identical streams, nothing degraded off the log,
    the log written and synced, no program traced for the journaling arm,
    and nothing self-healed."""
    samps = [GREEDY, SEEDED, GREEDY]
    eng = make_engine(decoder_params)

    def run(durable):
        sched = make_sched(eng)
        dur = Durability(sched, DurabilityConfig(wal_dir=str(tmp_path))) if durable else None
        handles = [sched.submit(p, s) for p, s in zip(PROMPTS, samps)]
        drive(sched, handles)
        outs = [h.result(0) for h in handles]
        if dur is not None:
            dur.close()
        return outs, sched, dur

    plain, _, _ = run(False)
    warm = dict(eng.trace_counts)
    journaled, sched, dur = run(True)
    assert journaled == plain
    assert dur.journal.degraded_count() == 0
    wal = dur.wal.counters()
    assert wal["appends"] > 0 and wal["fsyncs"] > 0
    assert dict(eng.trace_counts) == warm
    rs = sched.recovery_stats
    assert (eng.resets, rs.recoveries, rs.quarantined, rs.watchdog_trips, rs.step_retries) == (0,) * 5


def test_warm_restart_byte_exact_after_abandon(tmp_path, decoder_params):
    """Simulated process death mid-decode (scheduler + Durability
    abandoned, never closed) warm-restarts into byte-identical streams
    — greedy and seeded-temperature, including tokens that were only
    page-cache-buffered at death."""
    samps = [GREEDY, SEEDED, GREEDY]
    ref = solo_reference(decoder_params, PROMPTS, samps)

    sched = make_sched(make_engine(decoder_params))
    Durability(sched, DurabilityConfig(wal_dir=str(tmp_path), fsync=False))
    handles = [sched.submit(p, s) for p, s in zip(PROMPTS, samps)]
    for _ in range(5):
        sched.step()
    assert any(not h.done() for h in handles), "died too late to test replay"
    # process death: no close, no flush — the WAL keeps what the last
    # group commit wrote, replay regrows the rest

    sched2 = make_sched(make_engine(decoder_params))
    dur2 = Durability(sched2, DurabilityConfig(wal_dir=str(tmp_path), fsync=False))
    replay = dur2.warm_restart()
    assert replay["replayed_streams"] == sum(1 for h in handles if not h.done())
    adopted = [e.req for e in sched2.journal.entries()]
    drive(sched2, [r.handle for r in adopted])
    assert all(r.handle.done() for r in adopted)
    want = {tuple(p): list(t) for p, t in zip(PROMPTS, ref)}
    for req in adopted:
        assert req.generated == want[tuple(req.original_prompt)], (
            "warm restart forked a stream"
        )
    # the re-journal put the adopted streams into the NEW log and
    # released the predecessor segments
    assert dur2.report()["counters"]["replayed_streams"] == len(adopted)
    dur2.close()


def test_fingerprint_mismatch_refuses_typed(tmp_path, decoder_params):
    """Config drift between the journal writer and the restarting
    engine raises the typed FingerprintMismatchError and adopts
    nothing — a mismatched replay could silently fork every stream."""
    sched = make_sched(make_engine(decoder_params))
    Durability(sched, DurabilityConfig(wal_dir=str(tmp_path), fsync=False))
    sched.submit([7, 7, 7], GREEDY)
    for _ in range(3):
        sched.step()

    other_cfg = TransformerConfig(
        num_layers=1, hidden_size=16, num_heads=2, ff_size=32,
        seq_length=64, vocab_size=50, causal=True,  # vocab drifted
    )
    other = GenerationEngine(
        init_decoder_params(jax.random.key(0), other_cfg), other_cfg,
        max_batch_slots=3, block_size=BLOCK, prompt_buckets=BUCKETS,
    )
    sched_b = make_sched(other)
    dur_b = Durability(sched_b, DurabilityConfig(wal_dir=str(tmp_path), fsync=False))
    with pytest.raises(FingerprintMismatchError) as ei:
        dur_b.warm_restart()
    assert ei.value.expected != ei.value.found
    assert not sched_b.journal.entries()
    dur_b.close()


def test_append_failure_degrades_one_stream(tmp_path, decoder_params):
    """A failed journal append takes that ONE stream off the log with a
    counted warning; generation is untouched and the other streams stay
    durable."""
    eng = make_engine(decoder_params)
    sched = make_sched(eng)
    dur = Durability(sched, DurabilityConfig(wal_dir=str(tmp_path), fsync=False))
    plan = FaultPlan(seed=0)
    plan.on("serving.wal_append", mode="error",
            error=OSError("disk says no"), nth=(0,))
    with plan.active():
        handles = [sched.submit(p, GREEDY) for p in PROMPTS]
        drive(sched, handles)
    results = [h.result(0) for h in handles]
    assert all(len(r) == 12 for r in results)
    assert dur.journal.degraded_count() == 1
    assert dur.stats.counts()["wal_append_failures"] == 1
    dur.sync()
    # the two survivors are fully journaled; the degraded stream wrote
    # no END (it left the log at its failed admit)
    records, _ = scan_wal(str(tmp_path), before_index=None)
    ended = [s for s in replay_streams(records) if s.ended]
    assert len(ended) == 2
    dur.close()


# ---------------------------------------------------------------------------
# absolute wall deadlines across the down-window (satellite 5)
# ---------------------------------------------------------------------------


def test_deadline_remaining_budget_survives_restart(tmp_path, decoder_params):
    """The journal stores the deadline as ABSOLUTE WALL TIME; replay
    converts the REMAINING wall budget onto the new scheduler's clock.
    A 4 s down-window shrinks a 30 s budget by exactly 4 s — the
    restart can neither extend the deadline (new epoch restarting the
    budget) nor double-charge it (down-window counted twice)."""
    sclock, wall = FakeClock(0.0), FakeClock(1000.0)
    sched = make_sched(make_engine(decoder_params), clock=sclock)
    Durability(sched, DurabilityConfig(
        wal_dir=str(tmp_path), fsync=False, wall_clock=wall))
    h = sched.submit([1, 2, 3], GREEDY, deadline_s=30.0)
    for _ in range(3):
        sched.step()
    assert not h.done()
    # down-window: 4 s of wall time pass with the process dead; the
    # new process boots with a completely different scheduler epoch
    wall.advance(4.0)
    sclock2 = FakeClock(500.0)
    sched2 = make_sched(make_engine(decoder_params), clock=sclock2)
    dur2 = Durability(sched2, DurabilityConfig(
        wal_dir=str(tmp_path), fsync=False, wall_clock=wall))
    replay = dur2.warm_restart()
    assert replay["replayed_streams"] == 1 and not replay["expired_streams"]
    (req,) = [e.req for e in sched2.journal.entries()]
    assert req.deadline - sclock2() == pytest.approx(30.0 - 4.0)
    drive(sched2, [req.handle])
    assert req.handle.result(0) == solo_reference(
        decoder_params, [[1, 2, 3]], [GREEDY])[0]
    dur2.close()


def test_deadline_expired_during_down_window(tmp_path, decoder_params):
    """A budget that ran out while the process was down expires at
    replay WITHOUT re-admission, and the resume index serves the typed
    terminal outcome instead of a 404."""
    sclock, wall = FakeClock(0.0), FakeClock(1000.0)
    sched = make_sched(make_engine(decoder_params), clock=sclock)
    Durability(sched, DurabilityConfig(
        wal_dir=str(tmp_path), fsync=False, wall_clock=wall))
    h = sched.submit([4, 5, 6], GREEDY, deadline_s=10.0)
    for _ in range(3):
        sched.step()
    assert not h.done()
    wall.advance(60.0)  # well past the 10 s budget
    sched2 = make_sched(make_engine(decoder_params), clock=FakeClock(0.0))
    dur2 = Durability(sched2, DurabilityConfig(
        wal_dir=str(tmp_path), fsync=False, wall_clock=wall))
    replay = dur2.warm_restart()
    assert replay["replayed_streams"] == 0
    assert len(replay["expired_streams"]) == 1
    assert not sched2.journal.entries()
    (did,) = replay["expired_streams"]
    state, obj = dur2.lookup(did)
    assert state == "done" and obj["outcome"] == "expired"
    # the journaled prefix is preserved for the reconnecting client
    assert len(obj["tokens"]) >= 1
    dur2.close()


# ---------------------------------------------------------------------------
# HTTP surface: SSE event ids + the resume endpoint
# ---------------------------------------------------------------------------


def test_resume_endpoint_replays_sse(tmp_path, decoder_params):
    """The streaming response carries monotonic SSE event ids and the
    durable id; GET /v2/generate/resume/{id} replays the same tokens
    with the SAME ids, and Last-Event-ID skips what the client holds."""
    from flexflow_tpu.serving import InferenceServer
    from flexflow_tpu.serving.generation import GenerationModel

    srv = InferenceServer(port=0)
    model = GenerationModel(make_engine(decoder_params), name="lm")
    model.enable_durability(DurabilityConfig(
        wal_dir=str(tmp_path), fsync=False))
    srv.register_generation(model)
    srv.start()
    try:
        base = f"http://127.0.0.1:{srv.port}"
        req = urllib.request.Request(
            f"{base}/v2/models/lm/generate",
            data=json.dumps({"prompt": [1, 2, 3], "max_new_tokens": 8,
                             "stream": True}).encode(),
            headers={"Content-Type": "application/json"},
        )
        r = urllib.request.urlopen(req, timeout=60)
        chunks = r.read().decode().strip().split("\n\n")
        events, ids = [], []
        for ch in chunks:
            lines = dict(ln.split(": ", 1) for ln in ch.split("\n"))
            events.append(json.loads(lines["data"]))
            if "id" in lines:
                ids.append(int(lines["id"]))
        done = events[-1]
        assert done["done"] is True
        tokens = done["tokens"]
        assert ids == list(range(len(tokens)))  # monotonic from 0
        did = done["durable_id"]

        rr = urllib.request.urlopen(
            f"{base}/v2/generate/resume/{did}", timeout=60)
        assert rr.headers["X-Durable-Id"] == did
        replay = [json.loads(ch.split("data: ", 1)[1])
                  for ch in rr.read().decode().strip().split("\n\n")]
        assert [e["token"] for e in replay[:-1]] == tokens
        assert replay[-1]["done"] is True
        assert replay[-1]["outcome"] == "completed"

        # SSE reconnect convention: the client holds ids 0..2 already
        rr2 = urllib.request.urlopen(
            f"{base}/v2/generate/resume/{did}?last_event_id=2", timeout=60)
        partial = [json.loads(ch.split("data: ", 1)[1])
                   for ch in rr2.read().decode().strip().split("\n\n")]
        assert [e["token"] for e in partial[:-1]] == tokens[3:]

        missing = urllib.request.Request(
            f"{base}/v2/generate/resume/nope-0")
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(missing, timeout=30)
        assert ei.value.code == 404
    finally:
        srv.stop()


# ---------------------------------------------------------------------------
# rolling restart on a virtual-clock fleet
# ---------------------------------------------------------------------------


def test_fleet_rolling_restart_zero_loss(tmp_path, decoder_params):
    """A 2-replica rolling restart on the synchronous virtual-clock
    fleet: every in-flight stream finishes byte-exactly, both slots
    swap, and the successors' durable stats record the rotation."""
    from flexflow_tpu.serving.fleet import Fleet

    def factory():
        return make_engine(decoder_params)

    clock = FakeClock()
    fleet = Fleet(
        factory, 2, clock=clock, warmup=False,
        durability_root=str(tmp_path), durability_fsync=False,
        scheduler_kwargs=dict(recovery=NO_SLEEP),
    )
    prompts = PROMPTS + [[2, 4, 6, 8]]
    ref = solo_reference(decoder_params, prompts, [GREEDY] * len(prompts))
    handles = [fleet.submit(p, GREEDY) for p in prompts]

    def pump():
        fleet.step()
        clock.advance(0.05)

    roll = fleet.rolling_restart(drain_wait_s=30.0, pump=pump)
    assert roll["ok"], roll
    assert [e["slot"] for e in roll["replicas"]] == [0, 1]
    for _ in range(500):
        if all(h.done() for h in handles):
            break
        pump()
    got = [h.result(0) for h in handles]
    assert got == [list(t) for t in ref], "rolling restart forked a stream"
    # both successors attached a slot journal and counted the rotation
    rep = fleet.durable_report()
    assert set(rep["replicas"]) == {r.id for r in fleet.replicas}
    counts = [v["counters"]["rolling_restarts"]
              for v in rep["replicas"].values()]
    assert counts == [1, 1]
    fleet.stop()
