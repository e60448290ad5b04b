"""Disaggregated prefill/decode serving tests (ISSUE 16): the KV-block
wire format (pack -> CRC -> import round-trip), byte-exact streams
through the prefill-pool -> handoff -> decode-pool path across mixed
sampling modes, every handoff failure class terminating in a byte-exact
stream (bounded retry, CRC-caught corruption, retry exhaustion and
deadline expiry into decode-pool journal replay), pool-aware routing,
and the per-pool layout chooser.

Everything runs on virtual clocks with synchronous ``dfleet.step()``
driving — without ``start()`` the handoff pumps inline at offer, so the
fault legs are single-threaded and deterministic; one live-mode test
exercises ``start()``/``stop()`` and the dedicated handoff worker
thread. The tp-mismatch reshard (tp=1 payload onto a tp=2 decode pool)
needs a forced multi-device host geometry at process start, so it lives
in ``tools/chaoscheck.py --disagg`` (the tpu-ci leg), not here.

Kept deliberately lean on fresh engines (each one re-jits its program
family): tiny 1-layer config, 1+1 pools, merged scenario assertions.
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from flexflow_tpu.generation import (
    GenerationEngine,
    RecoveryPolicy,
    SamplingParams,
    SpeculationConfig,
    init_decoder_params,
)
from flexflow_tpu.generation.prefix import KVHandoffPayload, PackedBlock
from flexflow_tpu.models.transformer import TransformerConfig
from flexflow_tpu.runtime import faults
from flexflow_tpu.runtime.faults import FaultPlan
from flexflow_tpu.search.serving_strategy import choose_pool_strategies
from flexflow_tpu.serving.fleet import DisaggregatedFleet

pytestmark = pytest.mark.disagg

CFG = TransformerConfig(
    num_layers=1, hidden_size=16, num_heads=2, ff_size=32,
    seq_length=64, vocab_size=40, causal=True,
)
# ONE prefill bucket: every prompt here is <= 5 tokens, and this file
# builds ~15 engines (each fresh fleet jits two program families) —
# extra buckets would multiply compile time for nothing
BUCKETS = (8,)
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


def make_factory(decoder_params, slots=3):
    def factory():
        return GenerationEngine(
            decoder_params, CFG, max_batch_slots=slots, block_size=BLOCK,
            prompt_buckets=BUCKETS,
        )
    return factory


def make_disagg(decoder_params, *, clock=None, **kw):
    clock = clock or FakeClock()
    kw.setdefault("scheduler_kwargs", dict(recovery=NO_SLEEP))
    # zero backoff: retries come due immediately on a frozen clock
    kw.setdefault("handoff_backoff_s", 0.0)
    return DisaggregatedFleet(
        make_factory(decoder_params), n_prefill=1, n_decode=1,
        clock=clock, **kw,
    )


def drive(dfleet, handles, steps=500):
    for _ in range(steps):
        if all(h.done() for h in handles):
            return
        dfleet.step()


_REF_ENGINE = None


def solo_reference(decoder_params, prompts, samplings, specs=None):
    global _REF_ENGINE
    if _REF_ENGINE is None:
        _REF_ENGINE = make_factory(decoder_params)()
    specs = specs or [None] * len(prompts)
    return [
        _REF_ENGINE.generate([list(p)], s, speculation=sp)[0]
        for p, s, sp in zip(prompts, samplings, specs)
    ]


def no_leaked_blocks(engine):
    return engine.allocator.num_free == engine.allocator.num_total


def kv_imports(pool):
    return sum(
        r.scheduler.recovery_stats.kv_imports
        for r in pool._replicas_snapshot()
    )


PROMPTS = [[1, 2, 3], [4, 5, 6, 7], [9, 8, 7, 6, 5], [1, 2, 3, 4, 4]]
GREEDY = SamplingParams(max_new_tokens=12)
# one request per PROMPT: greedy across a block boundary (12 > BLOCK), two
# seeded temperatures, and a speculative stream
MIXED_SAMPLING = [
    GREEDY,
    SamplingParams(max_new_tokens=10, temperature=0.8, top_k=10, seed=42),
    SamplingParams(max_new_tokens=10, temperature=0.7, top_k=8, seed=7),
    SamplingParams(max_new_tokens=10),
]
MIXED_SPECS = [None, None, None, SpeculationConfig(k=3, method="ngram")]


# ---------------------------------------------------------------------------
# wire format
# ---------------------------------------------------------------------------


def test_wire_pack_import_roundtrip(decoder_params):
    """pack -> wire -> import -> repack is byte-identical, CRCs verify
    on arrival, and a flipped byte on the wire fails verification."""
    a = make_factory(decoder_params)()
    b = make_factory(decoder_params)()
    # deterministic nonzero cache contents (fresh caches are all-zero,
    # which would round-trip trivially)
    shape = a.cache.k.shape
    rng = np.random.default_rng(0)
    k = jnp.asarray(rng.standard_normal(shape), dtype=a.cache.k.dtype)
    v = jnp.asarray(rng.standard_normal(shape), dtype=a.cache.v.dtype)
    a.cache.update(k, v)

    n_pos = 2 * BLOCK - 3  # trailing partial block packs too
    payload = a.pack_kv_blocks([0, 1], n_pos)
    assert len(payload.blocks) == 2
    assert payload.verify()
    assert payload.nbytes > 0

    b.import_kv_blocks([2, 4], payload.blocks)
    echo = b.pack_kv_blocks([2, 4], n_pos)
    assert echo.verify()
    for sent, got in zip(payload.blocks, echo.blocks):
        assert np.array_equal(sent.host_k, got.host_k)
        assert np.array_equal(sent.host_v, got.host_v)

    # corruption on the wire: CRC catches a single flipped element
    bad_k = payload.blocks[0].host_k.copy()
    bad_k.flat[0] += 1.0
    tampered = PackedBlock(bad_k, payload.blocks[0].host_v,
                           crc=payload.blocks[0].crc)
    assert not tampered.verify()
    assert not KVHandoffPayload(
        n_pos, BLOCK, [tampered] + list(payload.blocks[1:])
    ).verify()


# ---------------------------------------------------------------------------
# byte-exact handoff, pool-aware routing
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def sync_fleet(decoder_params):
    """ONE shared 1+1 fleet for every synchronous scenario — each fresh
    DisaggregatedFleet jits two full program families (~3.5s), and the
    scenarios only read counter DELTAS, so sharing is order-independent
    (every test snapshots before it submits, and every stream it admits
    terminates before it returns)."""
    clock = FakeClock()
    dfleet = make_disagg(decoder_params, clock=clock, handoff_timeout_s=5.0)
    return dfleet, clock


def snap(dfleet):
    return {
        "transfers": dict(dfleet.handoff.transfers),
        "retries": dfleet.handoff.retries_total,
        "replays": dfleet.handoff.replay_fallbacks,
        "imports": kv_imports(dfleet.decode),
    }


def test_disagg_streams_byte_exact_mixed(decoder_params, sync_fleet):
    """Greedy (across a block boundary, 12 > BLOCK), seeded temperature,
    and speculative streams through prefill-pool -> handoff -> decode-
    pool match the solo single-engine reference byte-for-byte; every
    stream rode a delivered handoff (no replay fallback), decode-side
    imports account for every stream, admission stays on the prefill
    pool, and both pools return every cache block."""
    samp, specs = MIXED_SAMPLING, MIXED_SPECS
    ref = solo_reference(decoder_params, PROMPTS, samp, specs)

    dfleet, _clock = sync_fleet
    before = snap(dfleet)
    handles = [
        dfleet.submit(p, s, speculation=sp)
        for p, s, sp in zip(PROMPTS, samp, specs)
    ]
    drive(dfleet, handles)
    assert [h.result(timeout=0) for h in handles] == ref

    after = snap(dfleet)
    assert after["transfers"]["ok"] - before["transfers"]["ok"] == len(PROMPTS)
    assert after["replays"] == before["replays"]
    assert dfleet.handoff.bytes_total > 0
    assert dfleet.handoff.in_flight == 0
    # pool-aware routing: decode replicas imported every stream and
    # never prefilled; prefill replicas never imported
    assert after["imports"] - before["imports"] == len(PROMPTS)
    assert kv_imports(dfleet.prefill) == 0
    for pool in (dfleet.prefill, dfleet.decode):
        for r in pool._replicas_snapshot():
            assert no_leaked_blocks(r.engine), f"leaked blocks on {r.id}"


def test_disagg_steady_state_traces_nothing(sync_fleet):
    """Once a mix of greedy, seeded and speculative streams has crossed
    the handoff, the same mix again traces no program on any replica
    engine of either pool, and none was ever traced twice."""
    dfleet, _clock = sync_fleet
    engines = [r.engine for pool in (dfleet.prefill, dfleet.decode)
               for r in pool._replicas_snapshot()]

    def wave():
        handles = [dfleet.submit(p, s, speculation=sp)
                   for p, s, sp in zip(PROMPTS, MIXED_SAMPLING, MIXED_SPECS)]
        drive(dfleet, handles)
        return [h.result(timeout=0) for h in handles]

    first = wave()
    warm = [dict(e.trace_counts) for e in engines]
    assert wave() == first
    assert [dict(e.trace_counts) for e in engines] == warm
    assert [e.recompiles() for e in engines] == [{}] * len(engines)


# ---------------------------------------------------------------------------
# failure classes: every one terminates in a byte-exact stream
# ---------------------------------------------------------------------------


def test_transfer_error_bounded_retry_exact(decoder_params, sync_fleet):
    """A transfer attempt that raises is retried (bounded); the stream
    still delivers over the handoff, byte-exactly — no replay."""
    ref = solo_reference(decoder_params, PROMPTS[:2], [GREEDY, GREEDY])
    dfleet, _clock = sync_fleet
    before = snap(dfleet)
    plan = FaultPlan(seed=0)
    plan.on(faults.FLEET_KV_HANDOFF, mode="error",
            error=RuntimeError("injected transfer failure"), nth=(0,))
    with plan.active():
        handles = [dfleet.submit(p, GREEDY) for p in PROMPTS[:2]]
        drive(dfleet, handles)
    assert [h.result(timeout=0) for h in handles] == ref
    after = snap(dfleet)
    assert after["retries"] - before["retries"] >= 1
    assert after["transfers"]["ok"] - before["transfers"]["ok"] == 2
    assert after["replays"] == before["replays"]


def test_corruption_crc_caught_replays_exact(decoder_params, sync_fleet):
    """NaN-poisoned wire blocks fail CRC on arrival and never import —
    corruption is terminal for the transfer (a poisoned cache must not
    exist, even briefly); the stream falls back to decode-pool journal
    replay and stays byte-exact. The clean stream delivers normally."""
    ref = solo_reference(decoder_params, PROMPTS[:2], [GREEDY, GREEDY])
    dfleet, _clock = sync_fleet
    before = snap(dfleet)
    plan = FaultPlan(seed=0)
    plan.on(faults.FLEET_KV_HANDOFF, mode="nan", nth=(0,))
    with plan.active():
        handles = [dfleet.submit(p, GREEDY) for p in PROMPTS[:2]]
        drive(dfleet, handles)
    assert [h.result(timeout=0) for h in handles] == ref
    after = snap(dfleet)
    assert after["transfers"]["corrupt"] - before["transfers"]["corrupt"] == 1
    assert after["transfers"]["ok"] - before["transfers"]["ok"] == 1
    assert after["replays"] - before["replays"] == 1


def test_retry_exhaustion_replays_on_decode_pool(decoder_params, sync_fleet):
    """Every attempt failing exhausts the retry budget; the terminal
    fallback journal-replays the stream on the decode pool (recompute-
    prefill from the request) — byte-exact, nothing lost."""
    ref = solo_reference(decoder_params, PROMPTS[:1], [GREEDY])
    dfleet, _clock = sync_fleet
    before = snap(dfleet)
    plan = FaultPlan(seed=0)
    plan.on(faults.FLEET_KV_HANDOFF, mode="error",
            error=RuntimeError("injected transfer failure"), every=1)
    with plan.active():
        h = dfleet.submit(PROMPTS[0], GREEDY)
        drive(dfleet, [h])
    assert h.result(timeout=0) == ref[0]
    after = snap(dfleet)
    assert after["transfers"]["error"] - before["transfers"]["error"] == 1
    assert after["replays"] - before["replays"] == 1
    assert after["imports"] == before["imports"]  # replayed, not imported


def test_stalled_deadline_expires_into_replay(
    decoder_params, sync_fleet, monkeypatch
):
    """A handoff that cannot deliver (decode brownout holds it pending)
    expires at its deadline into decode-pool journal replay; the stream
    completes byte-exactly once the pool is reachable again."""
    ref = solo_reference(decoder_params, PROMPTS[:1], [GREEDY])
    dfleet, clock = sync_fleet
    before = snap(dfleet)
    monkeypatch.setattr(
        dfleet.decode.router, "place_failover", lambda reps: None
    )
    h = dfleet.submit(PROMPTS[0], GREEDY)
    for _ in range(50):  # prefill completes; the handoff stays pending
        dfleet.step()
        if dfleet.handoff.in_flight:
            break
    assert dfleet.handoff.in_flight == 1
    clock.advance(6.0)
    dfleet.handoff.check()
    after = snap(dfleet)
    assert after["transfers"]["stalled"] - before["transfers"]["stalled"] == 1
    assert after["replays"] - before["replays"] == 1
    assert dfleet.handoff.in_flight == 0
    monkeypatch.undo()
    drive(dfleet, [h])
    assert h.result(timeout=0) == ref[0]


# ---------------------------------------------------------------------------
# live mode: the dedicated handoff worker thread
# ---------------------------------------------------------------------------


def test_live_worker_thread_delivers_exact(decoder_params):
    """start() moves transfers onto the handoff worker thread (offers
    notify it instead of pumping inline on the prefill loop); the
    stream still delivers over the handoff, byte-exactly, and stop()
    joins the worker."""
    import time

    ref = solo_reference(decoder_params, PROMPTS[:1], [GREEDY])
    dfleet = make_disagg(decoder_params, clock=time.monotonic, poll_s=0.01)
    dfleet.start()
    try:
        assert dfleet.handoff._worker is not None
        assert dfleet.handoff._worker.is_alive()
        worker = dfleet.handoff._worker
        got = dfleet.generate(PROMPTS[0], GREEDY, timeout=30)
    finally:
        dfleet.stop()
    assert got == ref[0]
    assert not worker.is_alive()
    assert dfleet.handoff.replay_fallbacks == 0


# ---------------------------------------------------------------------------
# per-pool layout chooser
# ---------------------------------------------------------------------------


def test_choose_pool_strategies_split():
    """The per-pool chooser returns independent prefill/decode choices
    from one candidate set; pins select, invalid pins raise."""
    out = choose_pool_strategies(CFG, mesh_devices=2, max_batch_slots=4)
    assert set(out) == {"prefill", "decode"}
    for pool in ("prefill", "decode"):
        assert out[pool].tp_degree in (1, 2)  # 2 heads over 2 devices
        assert out[pool].candidates
    pinned = choose_pool_strategies(
        CFG, mesh_devices=2, pinned_prefill_tp=2, pinned_decode_tp=1
    )
    assert pinned["prefill"].tp_degree == 2 and pinned["prefill"].pinned
    assert pinned["decode"].tp_degree == 1 and pinned["decode"].pinned
    with pytest.raises(ValueError):
        choose_pool_strategies(CFG, mesh_devices=2, pinned_decode_tp=3)
