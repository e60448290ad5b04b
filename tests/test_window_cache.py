"""One cache manager, two kinds of attention layer (PR 31): the window
layers' pool, its tables, the release behind the window, the prefix-hit
rule, and every path that touches a sequence's blocks (preemption,
rollback, reset and replay, eviction to the host tier and back), at
rehearsal size on the CPU. The served tokens are held to the plain
reference's argmax throughout: a block read after its release, or a
prefix resumed without its window, changes them."""
import json
import pathlib
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from benchmark import spec  # noqa: E402
from benchmark.reference import mellum2  # noqa: E402
from flexflow_tpu.core.types import DataType  # noqa: E402
from flexflow_tpu.generation import GenerationEngine  # noqa: E402
from flexflow_tpu.generation.cache import CacheConfig, pools_from_budget  # noqa: E402
from flexflow_tpu.generation.engine import SamplingParams  # noqa: E402
from flexflow_tpu.generation.recovery import RecoveryPolicy  # noqa: E402
from flexflow_tpu.generation.scheduler import ContinuousBatchingScheduler  # noqa: E402
from flexflow_tpu.runtime.faults import FaultPlan  # noqa: E402

FILE = json.loads((ROOT / "benchmark/configs/mellum2-12b.json").read_text())
CONFIG = spec._merge(FILE, FILE["rehearsal"])
WINDOW, BS = 16, 8
PER_SEQ = -(-WINDOW // BS) + 1


@pytest.fixture(scope="module")
def weights():
    return mellum2.cast_params(mellum2.init_params(5, CONFIG), jnp.float32)


def make_engine(params, slots=4, **kw):
    cfg = mellum2.engine_config(CONFIG, 128)
    kw.setdefault("prompt_buckets", [32, 64])
    return GenerationEngine(params, cfg, max_batch_slots=slots, block_size=BS, max_seq_len=128, **kw)


def prompt(seed, n):
    return [int(t) for t in np.random.RandomState(seed).randint(0, 512, size=n)]


def greedy_reference(params, prompt_tokens, n_new):
    """The reference's own greedy continuation (full forward a token)."""
    seq = list(prompt_tokens)
    for _ in range(n_new):
        at = jnp.asarray([[len(seq) - 1]])
        seq.append(int(jnp.argmax(mellum2.logits_at(params, jnp.asarray([seq + [0] * (128 - len(seq))]), at, CONFIG)[0, 0])))
    return seq[len(prompt_tokens):]


def generate(eng, prompts, n_new, **kw):
    with jax.default_matmul_precision("highest"):
        return eng.generate(prompts, SamplingParams(max_new_tokens=n_new), **kw)


def drive(sched, handles, steps=2000):
    with jax.default_matmul_precision("highest"):
        for _ in range(steps):
            if all(h.done() for h in handles):
                break
            sched.step()
    return [h.result(timeout=0) for h in handles]


@pytest.fixture(scope="module")
def wanted(weights):
    """Prompts of 0.5 to 4 windows and the reference's 24 tokens after each."""
    prompts = [prompt(10 + n, n) for n in (8, 24, 40, 64)]
    return prompts, [greedy_reference(weights, p, 24) for p in prompts]


# ------------------------------------------------------------------ sizing
def test_pools_are_sized_by_what_a_sequence_can_hold(weights):
    eng = make_engine(weights)
    cc, wc = eng.cache_config, eng.window_config
    assert (cc.num_layers, wc.num_layers, wc.window) == (2, 6, WINDOW) and cc.window == 0
    assert wc.blocks_per_sequence(128) == PER_SEQ == 3 and cc.blocks_per_sequence(128) == 16
    # every slot's bound, one admission's suffix prefill beside it, scratch
    assert wc.num_blocks == 1 + 4 * PER_SEQ + 128 // BS and cc.num_blocks == 1 + 4 * 16
    assert wc.bytes_per_block == 2 * 6 * BS * 2 * 16 * 4 and cc.bytes_per_block == 2 * 2 * BS * 2 * 16 * 4
    assert eng.cache.state["wk"].shape == (6, wc.num_blocks, BS, 2, 16) and eng.cache.k.shape[0] == 2
    assert eng.max_blocks_per_seq == 16


def test_one_budget_buys_both_pools_the_same_sequences():
    kv = dict(num_heads=4, head_dim=128, block_size=16, dtype=DataType.BFLOAT16)
    full, window = pools_from_budget(3 << 30, 3072, dict(kv, num_layers=3), dict(kv, num_layers=9, window=1024))
    per = 192 * full.bytes_per_block + 65 * window.bytes_per_block  # 18.9 MB + 19.2 MB a sequence of 3,072
    assert abs(per / 1e6 - 38.04) < 0.01 and full.bytes_per_block == 98304 and window.bytes_per_block == 294912
    assert (full.num_blocks - 1) // 192 == (window.num_blocks - 1) // 65 == (3 << 30) // per
    assert full.total_bytes + window.total_bytes <= (3 << 30) + per
    with pytest.raises(ValueError, match="need a block of each pool"):
        pools_from_budget(1 << 10, 3072, dict(kv, num_layers=3), dict(kv, num_layers=9, window=1024))


def test_a_budget_given_to_the_engine_is_split_by_layer_kind(weights):
    two_sequences = 2 * (16 * 4096 + 3 * 12288)  # 16 full blocks and 3 window blocks each
    eng = make_engine(weights, slots=2, cache_budget_bytes=two_sequences)
    assert eng.cache_config.num_blocks == 1 + 2 * 16 and eng.window_config.num_blocks == 1 + 2 * 3
    with pytest.raises(ValueError, match="the release counts on it"):
        make_engine(weights, slots=4, cache_budget_bytes=two_sequences)


def test_a_configuration_without_window_layers_builds_the_cache_it_built(weights):
    from flexflow_tpu.generation import init_decoder_params
    from flexflow_tpu.models.transformer import TransformerConfig

    cfg = TransformerConfig(num_layers=2, hidden_size=64, num_heads=4, ff_size=128, seq_length=64, vocab_size=97, causal=True)
    eng = GenerationEngine(init_decoder_params(jax.random.key(0), cfg), cfg, max_batch_slots=4, block_size=8)
    assert eng.window_config is None and eng.window_allocator is None and eng.cache.state == {}
    assert eng.cache.k.shape == (2, 1 + 4 * 8, 8, 4, 16) and eng.cache_config.window == 0
    assert eng.blocks_in_use() == (0, 32) and eng.unsupported == {}
    assert CacheConfig.for_slots(2, 4, 16, 64, 4, block_size=8) == eng.cache_config


# ----------------------------------------------------------------- release
def test_a_live_sequence_holds_the_window_and_no_more(weights, wanted):
    prompts, want = wanted
    eng = make_engine(weights)
    assert generate(eng, prompts, 24) == want
    stats = eng.cache_stats()
    assert stats["window"]["held_by_a_sequence_peak"] == PER_SEQ
    lo = lambda position: max(0, position - WINDOW + 1) // BS  # noqa: E731  (the first block a query at `position` reaches)
    # a prefill keeps what the first decode query reaches; the last of the 23 decode queries sits at len + 22
    assert stats["window_released_total"] == sum(lo(len(p) + 22) - lo(len(p)) for p in prompts) == 7
    # nothing is left held: what is out of the free list belongs to cached prefixes
    assert eng.window_tables == {}
    halves = sum(1 for e in eng.prefix_cache._by_id.values() if e.wblock)
    assert eng.window_allocator.num_total - eng.window_allocator.num_free == halves
    assert 0 < stats["one_table_bytes"] and stats["live_bytes"] < stats["one_table_bytes"]


def test_a_released_window_block_is_never_read(weights, wanted):
    """Every block of the window pool that no table and no cached prefix
    holds is poisoned after every step, scratch included: the tokens do
    not change, so no step read one."""
    prompts, want = wanted
    eng = make_engine(weights, prefix_cache=False)
    sched = ContinuousBatchingScheduler(eng, overlap=False)
    handles = [sched.submit(p, SamplingParams(max_new_tokens=24)) for p in prompts]
    with jax.default_matmul_precision("highest"):
        for _ in range(2000):
            if all(h.done() for h in handles):
                break
            sched.step()
            held = {b for t in eng.window_tables.values() for b in t.blocks}
            free = jnp.asarray([b for b in range(eng.window_config.num_blocks) if b not in held])
            # (a masked position's value is multiplied by a probability of 0: NaN keys, huge values)
            eng.cache.state["wk"] = eng.cache.state["wk"].at[:, free].set(jnp.nan)
            eng.cache.state["wv"] = eng.cache.state["wv"].at[:, free].set(1e30)
    assert [h.result(timeout=0) for h in handles] == want
    assert eng.window_released_total > 0 and eng.window_held_peak == PER_SEQ


def test_the_release_runs_inside_the_pipelined_step(weights, wanted):
    prompts, want = wanted
    eng = make_engine(weights)
    advance, calls = eng.advance_windows, []
    eng.advance_windows = lambda *a: calls.append(1) or advance(*a)
    sched = ContinuousBatchingScheduler(eng, overlap=True)
    assert drive(sched, [sched.submit(p, SamplingParams(max_new_tokens=24)) for p in prompts]) == want
    assert len(calls) == eng.step_counts["decode"]  # once a step, pipelined (the scheduler's call) or not (the engine's)
    pipe = sched.pipeline_stats()
    assert pipe["pipelined_steps_total"] > 0 and pipe["drains_total"]["pressure"] == 0
    assert set(pipe["drains_total"]) == {"nonsteady", "finish", "pressure", "idle"}  # no new kind of drain
    assert eng.window_released_total > 0
    section = sched.stats.snapshot()["cache"]
    assert section["window"]["blocks_total"] == eng.window_allocator.num_total and "live_bytes" in section


def test_the_release_span_has_a_counter_beside_it(weights, wanted):
    """``ff.cache.window_release`` opens once a decode step: the ``cache``
    section counts the spans and their seconds, monotone."""
    prompts, want = wanted
    eng = make_engine(weights)
    sched = ContinuousBatchingScheduler(eng, overlap=True)
    assert eng.cache_stats()["window_releases_total"] == 0 and eng.cache_stats()["window_release_total_s"] == 0.0
    handles = [sched.submit(p, SamplingParams(max_new_tokens=24)) for p in prompts]
    seen = []
    while not all(h.done() for h in handles):
        sched.step()
        section = sched.stats.snapshot()["cache"]
        seen.append((section["window_releases_total"], section["window_release_total_s"]))
    assert [h.result(timeout=0) for h in handles] == want
    assert seen == sorted(seen) and seen[-1][0] == eng.step_counts["decode"] and seen[-1][1] > 0


def test_the_gauges_report_the_fuller_pool(weights):
    eng = make_engine(weights)
    eng.window_allocator.allocate(10)
    assert eng.blocks_in_use() == (10, eng.window_allocator.num_total)
    eng.allocator.allocate(60)
    assert eng.blocks_in_use() == (60, 64)


# -------------------------------------------------------------- prefix hits
def test_a_prefix_is_resumed_only_where_its_window_is_still_held(weights):
    """A 40-token prompt served, then the same 40 tokens with another
    tail: the hit is taken at block 5's boundary behind which the
    entries' window halves are held, and the tokens are the
    reference's. With the halves dropped the hit is refused."""
    shared = prompt(1, 40)
    eng = make_engine(weights)
    generate(eng, [shared + prompt(2, 6)], 4)
    pc = eng.prefix_cache
    entries = pc.match(shared + prompt(3, 6))
    assert len(entries) == 5 and [bool(e.wblock) for e in entries] == [False, False, False, True, True]
    plan = eng.prefix_plan(shared + prompt(3, 6))
    assert plan.reuse_tokens == 40 and plan.cow is None
    tail = shared + prompt(3, 6)
    assert generate(eng, [tail], 12)[0] == greedy_reference(weights, tail, 12)
    assert pc.hits == 1 and pc.tokens_reused_total == 40
    # a shorter boundary whose window lies in blocks already released: none
    assert eng.prefix_plan(shared[:24] + prompt(4, 9)).reuse_tokens == 0
    # the halves go (the window pool takes them back): the same prompt is recomputed, and right
    assert pc.reclaim_window(100) > 0 and not any(e.wblock for e in pc._by_id.values())
    assert eng.prefix_plan(tail).reuse_tokens == 0
    other = shared + prompt(5, 6)
    assert generate(eng, [other], 12)[0] == greedy_reference(weights, other, 12)
    assert pc.hits == 1


def test_eviction_to_the_host_tier_and_back_carries_the_window_half(weights):
    shared = prompt(1, 40)
    eng = make_engine(weights)
    eng.prefix_cache.swap_overhead_s = 0.0  # a transfer beats recomputing
    eng.prefix_cache.host_link_bytes_per_s = 1e15
    generate(eng, [shared + prompt(2, 6)], 4)
    pc = eng.prefix_cache
    assert eng.reclaim_cached(100) == 5 and pc.resident_blocks == 0 and pc.offloaded_blocks == 5
    assert [pc.has_window(e) for e in pc.match(shared + [1, 2])] == [False, False, False, True, True]
    assert eng.window_allocator.num_free == eng.window_allocator.num_total
    tail = shared + prompt(3, 6)
    assert generate(eng, [tail], 12)[0] == greedy_reference(weights, tail, 12)
    assert pc.swaps_in_total == 5 and pc.hits == 1 and pc.tokens_reused_total == 40
    # a host copy gone bad: the entry is dropped and the prompt recomputed
    assert eng.reclaim_cached(100) > 0
    victim = pc.match(shared + [1, 2])[4]
    victim.host_s = victim.host_s + 1
    again = shared + prompt(6, 6)
    assert generate(eng, [again], 12)[0] == greedy_reference(weights, again, 12)
    assert pc.swap_in_failures >= 1


# ------------------------------------------- preemption, rollback, recovery
def test_preemption_stashes_the_window_and_resumes_inside_it(weights):
    """A full pool too small for two long streams: the younger is
    preempted, its blocks (window halves of what it still held) stashed,
    and its re-admission resumes from them. Both streams are the
    reference's."""
    a, b = prompt(21, 24), prompt(22, 20)
    cfg = mellum2.engine_config(CONFIG, 128)
    cc = CacheConfig(num_layers=2, num_heads=2, head_dim=16, num_blocks=1 + 12, block_size=BS, dtype=cfg.dtype)
    eng = GenerationEngine(weights, cfg, cc, max_batch_slots=2, prompt_buckets=[32, 64], max_seq_len=128)
    eng.prefix_cache.swap_overhead_s = 0.0
    sched = ContinuousBatchingScheduler(eng)
    out = drive(sched, [sched.submit(p, SamplingParams(max_new_tokens=40)) for p in (a, b)])
    assert sched.preemptions > 0
    assert out == [greedy_reference(weights, a, 40), greedy_reference(weights, b, 40)]
    assert eng.prefix_cache.tokens_reused_total > 0 and eng.window_tables == {}
    assert eng.window_held_peak <= PER_SEQ


def test_reset_and_replay_rebuild_both_pools(weights, wanted):
    prompts, want = wanted
    eng = make_engine(weights)
    sched = ContinuousBatchingScheduler(eng, recovery=RecoveryPolicy(sleep=lambda _s: None))
    plan = FaultPlan(seed=0)
    plan.on("generation.decode_step", mode="error", error=RuntimeError("crash"), nth=(9, 10, 11))
    with plan.active():
        out = drive(sched, [sched.submit(p, SamplingParams(max_new_tokens=24)) for p in prompts[:3]])
    assert out == want[:3] and eng.resets >= 1
    assert eng.window_tables == {} and eng.window_held_peak <= PER_SEQ


def test_rollback_of_a_step_puts_the_window_arrays_back(weights):
    eng = make_engine(weights, slots=2, donate_cache=False)
    sched = ContinuousBatchingScheduler(eng, overlap=False)
    h = sched.submit(prompt(30, 20), SamplingParams(max_new_tokens=8))
    with jax.default_matmul_precision("highest"):
        sched.step()
    before = dict(eng.cache.state)
    b = eng.max_batch_slots
    active = np.asarray([True, False])
    state = next(iter(sched._running.values()))
    tables = np.zeros((b, eng.max_blocks_per_seq), np.int32)
    tables[state.slot, : len(state.blocks)] = state.blocks
    positions = np.full((b,), state.cached_len, np.int32)
    step = eng.decode_async(np.zeros((b,), np.int32), positions, tables, active, np.zeros((b,), np.float32),
                            np.zeros((b,), np.int32), np.zeros((b,), np.uint32), np.zeros((b,), np.int32))
    assert eng.cache.state["wk"] is not before["wk"] and step.prev_window["wk"] is before["wk"]
    eng.rollback_decode(step)
    assert eng.cache.state["wk"] is before["wk"] and eng.cache.state["wv"] is before["wv"]
    assert drive(sched, [h]) == [greedy_reference(weights, prompt(30, 20), 8)]


# ----------------------------------------------------------------- refusals
@pytest.mark.parametrize("path,call", [
    ("speculation", lambda e: e.verify(*[np.zeros((2, 5), np.int32)] + [np.zeros((2,), np.int32)] * 2
                                       + [np.zeros((2, 16), np.int32)] + [np.zeros((2,), np.float32)] * 4)),
    ("kv_handoff", lambda e: e.pack_kv_blocks([1, 2], 12)),
    ("kv_handoff", lambda e: e.import_kv_block(1, np.zeros(1), np.zeros(1))),
])
def test_paths_that_cannot_carry_the_window_are_refused_by_name(weights, path, call):
    eng = make_engine(weights, slots=2)
    assert set(eng.unsupported) == {"speculation", "kv_handoff", "tensor_parallel"}
    with pytest.raises(NotImplementedError, match="sliding-window layers \\(window 16\\)"):
        call(eng)
    assert "window 16" in eng.unsupported[path]


def test_tensor_parallel_is_refused_at_construction(weights):
    with pytest.raises(NotImplementedError, match="tp_degree > 1 is refused .* sliding-window"):
        make_engine(weights, tp_degree=2)
