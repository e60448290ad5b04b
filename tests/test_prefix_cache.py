"""Cross-request prefix caching tests (ISSUE 11): radix-indexed
copy-on-write KV reuse with host-RAM tiering.

Acceptance criteria covered:
  * exactness matrix: token streams are byte-identical with caching on
    and off — greedy, seeded temperature, and speculative — across
    block and bucket boundaries, including the fully-covered-prompt
    COW path
  * allocator conservation extended to refcounts and the host tier:
    shared, resident, offloaded, and free always sum to totals across
    a randomized admit / preempt / evict / swap schedule
  * chaos: a failed or corrupted (CRC) swap-in falls back to recompute
    with byte-exact output (``generation.kv_offload``), and a failed
    radix lookup degrades to a miss (``generation.prefix_lookup``)
  * crash-replay onto a warm prefix cache reproduces the uncached
    stream exactly (reset invalidates the index wholesale; replay
    re-matches or recomputes)
  * preempt-stash: a preempted request's re-admission reuses its own
    stashed blocks instead of recomputing
"""
import jax
import numpy as np
import pytest

from flexflow_tpu.generation import (
    CacheConfig,
    ContinuousBatchingScheduler,
    GenerationEngine,
    RecoveryPolicy,
    SamplingParams,
    SpeculationConfig,
    init_decoder_params,
)
from flexflow_tpu.models.transformer import TransformerConfig
from flexflow_tpu.runtime.faults import FaultPlan

from conftest import FakeClock, assert_blocks_conserved  # noqa: E402

pytestmark = pytest.mark.generation

CFG = TransformerConfig(
    num_layers=2, hidden_size=32, num_heads=4, ff_size=64,
    seq_length=64, vocab_size=50, causal=True,
)
BLOCK = 8
BUCKETS = (8, 16, 32, 64)


@pytest.fixture(scope="module")
def decoder_params():
    return init_decoder_params(jax.random.key(0), CFG)


def make_engine(decoder_params, *, enabled=True, num_blocks=None,
                block_size=BLOCK, slots=3, host_bytes=None, spec_k=3):
    cache = None
    if num_blocks is not None:
        cache = CacheConfig(
            num_layers=CFG.num_layers, num_heads=CFG.num_heads,
            head_dim=CFG.hidden_size // CFG.num_heads,
            num_blocks=num_blocks, block_size=block_size,
        )
    return GenerationEngine(
        decoder_params, CFG, cache_config=cache, max_batch_slots=slots,
        block_size=block_size, prompt_buckets=BUCKETS,
        max_spec_tokens=spec_k, prefix_cache=enabled,
        host_cache_bytes=host_bytes,
    )


TEMPLATE = list(range(1, 18))  # 17 tokens: 2 full blocks + a partial


def _matrix_prompts():
    """Shared-template prompts crossing block (8) and bucket (8/16/32)
    boundaries, plus exact-cover repeats (the COW path) and a
    one-token divergence inside the boundary block."""
    return [
        TEMPLATE + [30, 31, 32],        # bucket 32, shares 2 full blocks
        TEMPLATE + [33],                # 18 tokens
        list(TEMPLATE),                 # exact template -> full-cover COW
        list(TEMPLATE),                 # exact repeat again
        TEMPLATE[:8] + [40, 41],        # one-block template, bucket 16
        TEMPLATE[:8],                   # exact one-block cover
        TEMPLATE[:16] + [42] * 17,      # crosses into bucket 64
        [7, 7, 7],                      # sub-block: never cached
    ]


SAMPLINGS = {
    "greedy": SamplingParams(max_new_tokens=9),
    "seeded_temperature": SamplingParams(
        max_new_tokens=9, temperature=0.8, top_k=10, seed=42
    ),
}


@pytest.mark.parametrize("mode", ["greedy", "seeded_temperature", "speculative"])
def test_exactness_matrix_on_off(decoder_params, mode):
    """THE invariant: byte-identical token streams with caching on and
    off, for every sampling mode, with reuse actually happening."""
    spec = SpeculationConfig(k=3, method="ngram") if mode == "speculative" else None
    sampling = SAMPLINGS.get(mode, SAMPLINGS["greedy"])
    prompts = _matrix_prompts()
    off = make_engine(decoder_params, enabled=False)
    ref = off.generate(prompts, sampling, speculation=spec)
    on = make_engine(decoder_params, enabled=True)
    got = on.generate(prompts, sampling, speculation=spec)
    assert got == ref
    pc = on.prefix_cache
    assert pc.hits >= 4, pc.snapshot()
    assert pc.tokens_reused_total > 0
    assert pc.cow_copies_total >= 1  # the exact-template repeats
    # decode/verify stay the single fixed-shape programs
    assert on.trace_counts["decode"] == 1
    if mode == "speculative":
        assert on.trace_counts["verify"] == 1
    assert on.recompiles() == {}


def test_cow_keeps_shared_block_immutable(decoder_params):
    """A fully-covered prompt COW-copies the boundary block (its last
    position must be recomputed for logits, and that write lands inside
    the last matched block — 16 tokens: reuse caps at 15, mid-block);
    the shared original must still serve later requests with its
    original content (repeats byte-identical), and refcounts drain."""
    eng = make_engine(decoder_params, enabled=True)
    samp = SamplingParams(max_new_tokens=6)
    prompt = TEMPLATE[:16]  # exactly 2 blocks; len-1 = 15 is mid-block
    first = eng.generate([list(prompt)], samp)[0]
    assert eng.prefix_cache.cow_copies_total == 0  # first run: miss
    second = eng.generate([list(prompt)], samp)[0]
    third = eng.generate([list(prompt)], samp)[0]
    assert first == second == third
    assert eng.prefix_cache.cow_copies_total == 2
    snap = eng.prefix_cache.snapshot()
    assert snap["shared_blocks"] == 0  # nothing referenced after drain
    assert_blocks_conserved(eng)


def test_conservation_with_tiers_randomized(decoder_params):
    """Randomized shared-template schedule over a tiny cache: admit,
    preempt, evict-to-host, swap-in, COW — shared + resident +
    offloaded + free always account for every block, on every step."""
    eng = make_engine(decoder_params, num_blocks=8, block_size=4)
    eng.prefix_cache.swap_overhead_s = 0.0  # transfer always beats recompute
    sched = ContinuousBatchingScheduler(
        eng, recovery=RecoveryPolicy(sleep=lambda _s: None)
    )
    rs = np.random.RandomState(11)
    # two templates of 3 full blocks each: both warm = 6 of the 7
    # usable blocks, so alternating traffic keeps evicting the idle
    # template to the host tier and swapping it back in
    templates = [list(range(1, 13)), list(range(20, 32))]
    handles = []
    spec = SpeculationConfig(k=2, method="ngram")
    for i in range(140):
        if len(handles) < 12 and rs.rand() < 0.4:
            template = templates[len(handles) % 2]
            prompt = template[: int(rs.choice([8, 12, 12]))] + rs.randint(
                0, CFG.vocab_size, int(rs.randint(1, 4))
            ).tolist()
            handles.append(sched.submit(
                prompt,
                SamplingParams(max_new_tokens=int(rs.randint(1, 8))),
                speculation=spec if rs.rand() < 0.4 else None,
            ))
        sched.step()
        assert_tiers_conserved(sched)
    for _ in range(400):
        if all(h.done() for h in handles):
            break
        if not sched.step():
            break
        assert_tiers_conserved(sched)
    assert all(h.done() for h in handles)
    pc = eng.prefix_cache
    snap = pc.snapshot()
    assert snap["swaps_out_total"] > 0, "pressure never offloaded a block"
    assert snap["hits"] > 0
    assert_blocks_conserved(eng)
    alloc = eng.allocator
    assert alloc.total_allocated == (
        alloc.total_freed + alloc.total_reset_reclaimed + pc.resident_blocks
    )


def assert_tiers_conserved(sched):
    rep = sched.cache_report()
    blocks = rep["blocks"]
    pc = rep["prefix_cache"]
    assert blocks["used"] + blocks["free"] == blocks["total"], blocks
    private = sum(r["blocks"] - r["shared_blocks"] for r in rep["residency"])
    assert private + pc["resident_blocks"] == blocks["used"], rep
    assert pc["shared_blocks"] <= pc["resident_blocks"]
    assert (
        pc["offloaded_blocks"] * rep["config"]["bytes_per_block"]
        == pc["host_bytes"]
    ), pc
    assert pc["host_bytes"] <= pc["host_budget_bytes"] or pc["offloaded_blocks"] == 0


def test_offload_swap_in_roundtrip_exact(decoder_params):
    """Evicted-to-host blocks swap back in (when the transfer beats the
    recompute roofline) and the stream is byte-identical."""
    samp = SamplingParams(max_new_tokens=6)
    ref = make_engine(decoder_params, enabled=False).generate(
        [TEMPLATE[:16] + [30]], samp
    )
    eng = make_engine(decoder_params, enabled=True)
    eng.prefix_cache.swap_overhead_s = 0.0
    eng.generate([TEMPLATE[:16] + [20]], samp)  # warm: 2 blocks registered
    assert eng.prefix_cache.resident_blocks == 2
    freed = eng.reclaim_cached(2)
    assert freed == 2
    pc = eng.prefix_cache
    assert pc.offloaded_blocks == 2 and pc.resident_blocks == 0
    assert pc.host_bytes == 2 * eng.cache_config.bytes_per_block
    out = eng.generate([TEMPLATE[:16] + [30]], samp)
    assert out == ref
    assert pc.swaps_in_total == 2
    # the swap heuristic is covered by the truth ledger
    entry = next(
        (e for e in eng.ledger.report()["entries"] if e["key"] == "kv_swap_in"),
        None,
    )
    assert entry is not None and entry["pairs"] >= 1


def test_swap_in_failure_falls_back_to_recompute(decoder_params):
    """Chaos (generation.kv_offload): a failed swap-in must not fail
    the request — reuse truncates and the suffix recomputes, byte-exact."""
    samp = SamplingParams(max_new_tokens=6)
    ref = make_engine(decoder_params, enabled=False).generate(
        [TEMPLATE[:16] + [30]], samp
    )
    eng = make_engine(decoder_params, enabled=True)
    eng.prefix_cache.swap_overhead_s = 0.0
    eng.generate([TEMPLATE[:16] + [20]], samp)
    eng.reclaim_cached(2)
    plan = FaultPlan(seed=0)
    plan.on("generation.kv_offload", mode="error",
            error=RuntimeError("dma failed"), nth=(0,))
    with plan.active():
        out = eng.generate([TEMPLATE[:16] + [30]], samp)
    assert out == ref
    pc = eng.prefix_cache
    assert pc.swap_in_failures >= 1
    assert pc.recompute_fallbacks >= 1
    assert_blocks_conserved(eng)


def test_corrupted_host_block_detected_and_recomputed(decoder_params):
    """A corrupted host buffer fails its CRC at swap-in: the block is
    dropped and the suffix recomputes — byte-exact, never garbage."""
    samp = SamplingParams(max_new_tokens=6)
    ref = make_engine(decoder_params, enabled=False).generate(
        [TEMPLATE[:16] + [30]], samp
    )
    eng = make_engine(decoder_params, enabled=True)
    eng.prefix_cache.swap_overhead_s = 0.0
    eng.generate([TEMPLATE[:16] + [20]], samp)
    eng.reclaim_cached(2)
    victim = next(
        e for e in eng.prefix_cache._by_id.values() if not e.resident
    )
    victim.host_k = victim.host_k.copy()
    victim.host_k.flat[0] += 1.0  # bit-flip the host copy
    with_corruption = eng.generate([TEMPLATE[:16] + [30]], samp)
    assert with_corruption == ref
    assert eng.prefix_cache.swap_in_failures >= 1
    assert victim.host_k is None  # corrupt copy dropped, not retried


def test_prefix_lookup_fault_degrades_to_miss(decoder_params):
    """Chaos (generation.prefix_lookup): a failed radix lookup is a
    cache miss — full recompute, identical stream, request unharmed."""
    samp = SamplingParams(max_new_tokens=6)
    eng = make_engine(decoder_params, enabled=True)
    first = eng.generate([TEMPLATE + [30]], samp)[0]
    plan = FaultPlan(seed=0)
    plan.on("generation.prefix_lookup", mode="error",
            error=RuntimeError("index corrupt"), every=1)
    with plan.active():
        second = eng.generate([TEMPLATE + [30]], samp)[0]
    assert second == first
    assert eng.prefix_cache.hits == 0  # every lookup degraded to a miss
    assert eng.prefix_cache.recompute_fallbacks >= 1


def test_crash_replay_onto_warm_prefix_cache(decoder_params):
    """Two decode crashes exhaust the single-step retry and force a
    restart + journal replay AFTER the cache is warm: the reset drops
    the index wholesale (stale KV must never match) and the replay
    recomputes — byte-exact against an uncached reference."""
    samp = SamplingParams(max_new_tokens=8)
    prompt = TEMPLATE + [26]
    ref = make_engine(decoder_params, enabled=False).generate([prompt], samp)[0]
    eng = make_engine(decoder_params, enabled=True)
    sched = ContinuousBatchingScheduler(
        eng, recovery=RecoveryPolicy(sleep=lambda _s: None)
    )
    eng.generate([TEMPLATE + [25]], samp)  # warm the radix index
    assert eng.prefix_cache.resident_blocks > 0
    plan = FaultPlan(seed=0)
    plan.on("generation.decode_step", mode="error",
            error=RuntimeError("crash"), nth=(0, 1))
    with plan.active():
        h = sched.submit(prompt, samp)
        for _ in range(300):
            if h.done():
                break
            sched.step()
    assert h.result(timeout=0) == ref
    assert eng.resets == 1
    assert sched.recovery_stats.recoveries == 1
    assert_tiers_conserved(sched)


def test_preempt_resume_reuses_stashed_blocks(decoder_params):
    """Preemption registers the victim's computed KV (prompt AND
    generated content) in the index; its recompute re-admission
    prefix-matches those blocks instead of recomputing — and the
    resumed stream is exact (covered again by test_generation's
    preempt test; here we assert the reuse actually happened)."""
    sp = SamplingParams(max_new_tokens=12, temperature=0.8, top_k=10, seed=3)
    ref = make_engine(decoder_params, enabled=False, num_blocks=40,
                      block_size=4).generate([[1, 2, 3, 4, 5]], sp)[0]
    eng = make_engine(decoder_params, enabled=True, num_blocks=6, block_size=4)
    eng.prefix_cache.swap_overhead_s = 0.0  # transfer beats recompute
    sched = ContinuousBatchingScheduler(eng, clock=FakeClock())
    h1 = sched.submit([1, 2, 3, 4, 5], sp)
    h2 = sched.submit([9, 8, 7], SamplingParams(max_new_tokens=12, seed=1))
    for _ in range(300):
        if h1.done() and h2.done():
            break
        sched.step()
    assert sched.preemptions > 0
    assert h1.result(0) == ref
    pc = eng.prefix_cache
    assert pc.registered_total > 0
    assert pc.tokens_reused_total > 0, "re-admission never reused stashed KV"


def test_router_probe_counts_cached_run(decoder_params):
    """probe() (the fleet router's affinity input) reports the cached
    full-block run capped at len-1, without counting as traffic."""
    eng = make_engine(decoder_params, enabled=True)
    samp = SamplingParams(max_new_tokens=2)
    eng.generate([TEMPLATE + [30]], samp)  # registers 2 full blocks
    lookups = eng.prefix_cache.lookups
    assert eng.prefix_cache.probe(TEMPLATE + [31]) == 16
    assert eng.prefix_cache.probe(list(TEMPLATE[:16])) == 15  # capped len-1
    assert eng.prefix_cache.probe([99, 98]) == 0
    assert eng.prefix_cache.lookups == lookups  # probes are not traffic


def test_disabled_prefix_cache_is_inert(decoder_params):
    """prefix_cache=False: no registration, no reuse, no index-owned
    blocks — the pre-feature allocator behavior, exactly."""
    eng = make_engine(decoder_params, enabled=False)
    samp = SamplingParams(max_new_tokens=4)
    eng.generate([list(TEMPLATE)], samp)
    eng.generate([list(TEMPLATE)], samp)
    snap = eng.prefix_cache.snapshot()
    assert snap["registered_total"] == 0 and snap["hits"] == 0
    assert eng.allocator.num_free == eng.allocator.num_total
