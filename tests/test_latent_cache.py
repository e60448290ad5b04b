"""The latent cache (PR 34): one array a layer, ONE row a position, in
the block pool, the prefix cache with its host tier, and every path that
touches a sequence's blocks (a prefix hit's suffix prefill, preemption,
rollback, reset and replay, eviction to the host tier and back), at
rehearsal size on the CPU. The served tokens are held to the plain
reference's argmax throughout: a row lost, moved or read at the wrong
width changes them."""
import json
import pathlib
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from benchmark import spec  # noqa: E402
from benchmark.reference import joyai  # noqa: E402
from flexflow_tpu.core.types import DataType  # noqa: E402
from flexflow_tpu.generation import GenerationEngine  # noqa: E402
from flexflow_tpu.generation.cache import CacheConfig  # noqa: E402
from flexflow_tpu.generation.engine import SamplingParams, unsupported_paths  # noqa: E402
from flexflow_tpu.generation.recovery import RecoveryPolicy  # noqa: E402
from flexflow_tpu.generation.scheduler import ContinuousBatchingScheduler  # noqa: E402
from flexflow_tpu.obs.capacity import ServingFlops  # noqa: E402
from flexflow_tpu.runtime.faults import FaultPlan  # noqa: E402

FILE = json.loads((ROOT / "benchmark/configs/joyai-llm-flash.json").read_text())
CONFIG = spec._merge(FILE, FILE["rehearsal"])
BS, LAYERS, STORED = 8, 4, 128  # rows of 32 + 8 values, stored at 128 lanes


@pytest.fixture(scope="module")
def weights():
    return joyai.cast_params(joyai.init_params(5, CONFIG), jnp.float32)


def make_engine(params, slots=4, **kw):
    cfg = joyai.engine_config(CONFIG, 128)
    kw.setdefault("prompt_buckets", [32, 64])
    return GenerationEngine(params, cfg, max_batch_slots=slots, block_size=BS, max_seq_len=128, **kw)


def prompt(seed, n):
    return [int(t) for t in np.random.RandomState(seed).randint(0, 512, size=n)]


def greedy_reference(params, prompt_tokens, n_new):
    """The reference's own greedy continuation (full forward a token)."""
    seq = list(prompt_tokens)
    for _ in range(n_new):
        at = jnp.asarray([[len(seq) - 1]])
        seq.append(int(jnp.argmax(joyai.logits_at(params, jnp.asarray([seq + [0] * (128 - len(seq))]), at, CONFIG)[0, 0])))
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
    prompts = [prompt(10 + n, n) for n in (8, 24, 40, 64)]
    return prompts, [greedy_reference(weights, p, 24) for p in prompts]


# ------------------------------------------------------------------ shapes
def test_the_cache_holds_one_row_a_token_a_layer(weights):
    eng = make_engine(weights)
    cc = eng.cache_config
    assert cc.latent and (cc.num_layers, cc.num_heads, cc.head_dim) == (LAYERS, 1, 40)
    assert cc.row_shape == (STORED,) and cc.value_row_shape == (0,)
    assert eng.cache.k.shape == (LAYERS, 1 + 4 * 16, BS, STORED) and eng.cache.v.shape == (LAYERS, 1 + 4 * 16, BS, 0)
    assert eng.cache.v.size == 0 and eng.cache.state == {} and eng.window_config is None
    assert cc.bytes_per_token == LAYERS * STORED * 4 and cc.bytes_per_block == BS * cc.bytes_per_token
    assert cc.total_bytes == eng.cache.k.size * 4  # what is counted is what is stored
    assert eng.prefix_cache.bytes_per_block == cc.bytes_per_block
    stats = eng.cache_stats()["latent"]
    assert stats["bytes_per_token"] == LAYERS * STORED * 4 and (stats["entry_width"], stats["stored_width"]) == (40, STORED)
    assert eng.kernel_stats() == {"latent": {"body": "reference", "group": 4}}


def test_the_published_row_is_576_values_stored_at_640():
    cfg = joyai.engine_config(FILE, 3072)
    assert cfg.latent_width == 576 and cfg.num_heads == 32 and cfg.held_experts == 16 and cfg.num_experts == 256
    cc = CacheConfig.for_slots(20, 1, 576, 3072, 48, block_size=64, dtype=DataType.BFLOAT16, latent=True)
    assert cc.row_shape == (640,) and cc.bytes_per_token == 20 * 1280 and cc.num_blocks == 1 + 48 * 48
    # per-head K/V of the same model would be 32 x (192 + 128) x 2 B a layer: 17.8 x the published row, 16 x the stored
    assert 32 * (192 + 128) * 2 == 20480 and 20480 / 1152 == pytest.approx(17.8, abs=0.03)
    flops = ServingFlops.from_config(cfg, dtype=DataType.BFLOAT16)
    assert flops.kv_bytes_per_pos == 20 * 1280 and flops.per_ctx_flops == 20 * 2 * 32 * (576 + 512)
    # 2,105 M parameters in the layers + 529.5 M in the vocabulary matrices; norms and the selection bias beside
    assert flops.param_count == pytest.approx(2.634e9, rel=2e-3)


def test_a_budget_buys_rows_not_heads():
    kv = dict(num_layers=20, num_heads=1, head_dim=576, block_size=64, dtype=DataType.BFLOAT16)
    latent = CacheConfig.from_budget(1 << 30, latent=True, **kv)
    assert latent.num_blocks == (1 << 30) // (64 * 20 * 1280) and latent.latent


def test_a_configuration_without_latent_layers_builds_the_cache_it_built():
    from flexflow_tpu.generation import init_decoder_params
    from flexflow_tpu.models.transformer import TransformerConfig

    cfg = TransformerConfig(num_layers=2, hidden_size=64, num_heads=4, ff_size=128, seq_length=64, vocab_size=97, causal=True)
    eng = GenerationEngine(init_decoder_params(jax.random.key(0), cfg), cfg, max_batch_slots=4, block_size=8)
    assert not eng.cache_config.latent and eng.cache.k.shape == eng.cache.v.shape == (2, 1 + 4 * 8, 8, 4, 16)
    assert eng.cache_config.bytes_per_block == 2 * 2 * 8 * 4 * 16 * 4 and eng.unsupported == {}
    assert eng.kernel_stats() == {"full": {"body": "reference", "group": 1}}
    assert CacheConfig.for_slots(2, 4, 16, 64, 4, block_size=8) == eng.cache_config


def test_a_decode_step_carries_the_cache_through_and_copies_nothing_of_its_size(weights):
    """The decode program's StableHLO: the latent array goes from its
    parameter to its result through scatters of the step's rows alone
    (one a layer) — no slice, gather result, pad or copy of the cache's
    shape, and V's empty array is not touched."""
    eng = make_engine(weights)
    b, mb, v = 4, eng.max_blocks_per_seq, 512
    z = lambda shape, dt=jnp.int32: jnp.zeros(shape, dt)  # noqa: E731
    lowered = jax.jit(eng._decode_impl).lower(
        eng.params, z((b,)), z((b,)), eng.cache.k, eng.cache.v, z((b, mb)), z((b,)), z((b,), jnp.float32), z((b,)),
        z((b,), jnp.float32), z((b,), jnp.uint32), z((b,)), z((b, v), jnp.float32), {}, eng.expert_counts,
    )
    text = lowered.as_text()
    whole, empty = "tensor<4x65x8x128xf32>", "tensor<4x65x8x0xf32>"
    makes = [line.strip() for line in text.splitlines() if line.rstrip().endswith(f"-> {whole}")]
    # every value of the cache's shape is the result of a scatter of the step's 4 rows into the one before it
    assert len(makes) == LAYERS and all(m == f"}}) : ({whole}, tensor<4x3xi32>, tensor<4x128xf32>) -> {whole}" for m in makes), makes
    # and V's empty array goes from its parameter to the result in no operation: the signature (twice) and the return
    assert [line.count(empty) for line in text.splitlines() if empty in line] == [2, 1]


# -------------------------------------------------------------- prefix hits
def test_a_prefix_hit_s_suffix_prefill_over_cached_rows_is_the_reference(weights):
    """A 40-token prompt served, then the same 40 tokens with another
    tail: the suffix is prefilled in the ABSORBED form over the cached
    latent blocks, and the tokens are the reference's."""
    shared = prompt(1, 40)
    eng = make_engine(weights)
    generate(eng, [shared + prompt(2, 6)], 4)
    before = dict(eng.latent_calls)
    tail = shared + prompt(3, 6)
    assert eng.prefix_plan(tail).reuse_tokens == 40
    assert generate(eng, [tail], 12)[0] == greedy_reference(weights, tail, 12)
    pc = eng.prefix_cache
    assert pc.hits == 1 and pc.tokens_reused_total == 40
    assert eng.latent_calls["expanded"] == before["expanded"]  # the hit ran no expanded prefill
    assert eng.trace_counts.get("prefix_prefill[32]") == 1


def test_eviction_to_the_host_tier_and_back_moves_the_rows(weights):
    shared = prompt(1, 40)
    eng = make_engine(weights)
    eng.prefix_cache.swap_overhead_s = 0.0  # a transfer beats recomputing
    eng.prefix_cache.host_link_bytes_per_s = 1e15
    generate(eng, [shared + prompt(2, 6)], 4)
    pc = eng.prefix_cache
    assert eng.reclaim_cached(100) == 5 and pc.resident_blocks == 0 and pc.offloaded_blocks == 5
    entry = pc.match(shared + [1, 2])[0]
    assert entry.host_k.shape == (LAYERS, BS, STORED) and entry.host_v.shape == (LAYERS, BS, 0)
    assert pc.host_bytes == 5 * eng.cache_config.bytes_per_block == 5 * entry.host_k.nbytes
    tail = shared + prompt(3, 6)
    assert generate(eng, [tail], 12)[0] == greedy_reference(weights, tail, 12)
    assert pc.swaps_in_total == 5 and pc.hits == 1 and pc.tokens_reused_total == 40
    # a host copy gone bad: the entry is dropped and the prompt recomputed
    assert eng.reclaim_cached(100) > 0
    victim = pc.match(shared + [1, 2])[4]
    victim.host_k = victim.host_k + 1
    again = shared + prompt(6, 6)
    assert generate(eng, [again], 12)[0] == greedy_reference(weights, again, 12)
    assert pc.swap_in_failures >= 1


# ------------------------------------------- preemption, rollback, recovery
def test_preemption_stashes_the_rows_and_resumes_from_them(weights):
    a, b = prompt(21, 24), prompt(22, 20)
    cfg = joyai.engine_config(CONFIG, 128)
    cc = CacheConfig(num_layers=LAYERS, num_heads=1, head_dim=40, num_blocks=1 + 12, block_size=BS, dtype=cfg.dtype, latent=True)
    eng = GenerationEngine(weights, cfg, cc, max_batch_slots=2, prompt_buckets=[32, 64], max_seq_len=128)
    eng.prefix_cache.swap_overhead_s = 0.0
    sched = ContinuousBatchingScheduler(eng)
    out = drive(sched, [sched.submit(p, SamplingParams(max_new_tokens=40)) for p in (a, b)])
    assert sched.preemptions > 0
    assert out == [greedy_reference(weights, a, 40), greedy_reference(weights, b, 40)]
    assert eng.prefix_cache.tokens_reused_total > 0


def test_reset_and_replay_rebuild_the_rows(weights, wanted):
    prompts, want = wanted
    eng = make_engine(weights)
    sched = ContinuousBatchingScheduler(eng, recovery=RecoveryPolicy(sleep=lambda _s: None))
    plan = FaultPlan(seed=0)
    plan.on("generation.decode_step", mode="error", error=RuntimeError("crash"), nth=(9, 10, 11))
    with plan.active():
        out = drive(sched, [sched.submit(p, SamplingParams(max_new_tokens=24)) for p in prompts[:3]])
    assert out == want[:3] and eng.resets >= 1
    assert eng.cache.k.shape[-1] == STORED and eng.cache.v.shape[-1] == 0  # rebuilt in the shapes they had


def test_rollback_of_a_step_puts_the_latent_array_back(weights):
    eng = make_engine(weights, slots=2, donate_cache=False)
    sched = ContinuousBatchingScheduler(eng, overlap=False)
    h = sched.submit(prompt(30, 20), SamplingParams(max_new_tokens=8))
    with jax.default_matmul_precision("highest"):
        sched.step()
    before = eng.cache.k
    b = eng.max_batch_slots
    state = next(iter(sched._running.values()))
    tables = np.zeros((b, eng.max_blocks_per_seq), np.int32)
    tables[state.slot, : len(state.blocks)] = state.blocks
    step = eng.decode_async(np.zeros((b,), np.int32), np.full((b,), state.cached_len, np.int32), tables,
                            np.asarray([True, False]), np.zeros((b,), np.float32), np.zeros((b,), np.int32),
                            np.zeros((b,), np.uint32), np.zeros((b,), np.int32))
    assert eng.cache.k is not before and step.prev_k is before
    eng.rollback_decode(step)
    assert eng.cache.k is before
    assert drive(sched, [h]) == [greedy_reference(weights, prompt(30, 20), 8)]


def test_the_pipelined_loop_serves_the_reference_s_tokens(weights, wanted):
    prompts, want = wanted
    eng = make_engine(weights)
    sched = ContinuousBatchingScheduler(eng, overlap=True)
    assert drive(sched, [sched.submit(p, SamplingParams(max_new_tokens=24)) for p in prompts]) == want
    pipe = sched.pipeline_stats()
    assert pipe["pipelined_steps_total"] > 0 and pipe["drains_total"]["pressure"] == 0
    section = sched.stats.snapshot()["cache"]["latent"]
    assert section["absorbed_calls_total"] >= LAYERS * eng.step_counts["decode"] and section["tokens_held"] > 0
    assert section["live_bytes"] == section["tokens_held"] * LAYERS * STORED * 4
    assert section["per_head_bytes"] == section["tokens_held"] * LAYERS * 4 * (16 + 8 + 16) * 4
    assert sched.stats.snapshot()["experts"]["held"] == [0, 1, 2, 3]


# ----------------------------------------------------------------- refusals
@pytest.mark.parametrize("path,call", [
    ("speculation", lambda e: e.verify(*[np.zeros((2, 5), np.int32)] + [np.zeros((2,), np.int32)] * 2
                                       + [np.zeros((2, 16), np.int32)] + [np.zeros((2,), np.float32)] * 4)),
    ("kv_handoff", lambda e: e.pack_kv_blocks([1, 2], 12)),
    ("kv_handoff", lambda e: e.import_kv_block(1, np.zeros(1), np.zeros(1))),
])
def test_paths_that_cannot_carry_a_latent_row_are_refused_by_name(weights, path, call):
    eng = make_engine(weights, slots=2)
    assert set(eng.unsupported) == {"speculation", "kv_handoff", "tensor_parallel"}
    with pytest.raises(NotImplementedError, match="latent layers"):
        call(eng)
    assert eng.unsupported[path] == unsupported_paths("latent", eng.dcfg)[path]


def test_tensor_parallel_is_refused_at_construction(weights):
    with pytest.raises(NotImplementedError, match="tp_degree > 1 is refused .* latent layers"):
        make_engine(weights, tp_degree=2)


@pytest.mark.parametrize("kind, says", [("conv", "convolution layers"), ("window", "sliding-window layers (window 0)"),
                                        ("latent", "latent layers")])
def test_the_refusals_are_keyed_by_layer_kind(kind, says):
    paths = unsupported_paths(kind, joyai.engine_config(CONFIG, 128))
    assert set(paths) == {"speculation", "kv_handoff", "tensor_parallel"} and all(says in why for why in paths.values())


def test_latent_layers_beside_another_kind_are_refused():
    import dataclasses

    cfg = joyai.engine_config(CONFIG, 128)
    with pytest.raises(ValueError, match="latent layers beside another kind"):
        dataclasses.replace(cfg, layer_types=("latent", "attention", "latent", "latent"))
    with pytest.raises(ValueError, match="five widths"):
        dataclasses.replace(cfg, kv_lora_rank=0)
    with pytest.raises(ValueError, match="outside the 16 routed experts"):
        dataclasses.replace(cfg, experts_held=(3, 16))
