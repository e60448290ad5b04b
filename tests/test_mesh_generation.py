"""Multi-chip sharded generation (ISSUE 15).

Two layers of coverage:

* **In-process** (single device): the 1-device mesh engine is
  bit-for-bit the legacy engine (the exactness anchor), cache sizing is
  per-device-HBM- and sharing-aware, chip specs scale to mesh geometry,
  the serving-layout search scores/chooses/pins TP degrees and registers
  its decision in the truth ledger, and the ``generation.collective``
  site exists but never fires on unsharded engines.
* **Subprocess** (forced 4-device host mesh — XLA must see the device
  count before backend init, so the matrix runs in one child process):
  all sampling modes, speculative decoding, prefix caching, and the
  overlap pipeline produce token streams BYTE-IDENTICAL to the 1-device
  engine; sharded jits never retrace at steady state; a failed
  collective journal-replays byte-exactly over the sharded cache; and
  the head-sharded Pallas kernel path (interpret mode) matches the
  reference composition.
"""
import json
import os
import subprocess
import sys

import jax
import pytest

from flexflow_tpu.generation import (
    GenerationEngine,
    SamplingParams,
    init_decoder_params,
)
from flexflow_tpu.generation.cache import CacheConfig
from flexflow_tpu.generation.sharding import ServingLayout, validate_kv_shards
from flexflow_tpu.models.transformer import TransformerConfig
from flexflow_tpu.runtime import faults
from flexflow_tpu.search.calibration import chip_spec_for, mesh_device_kind
from flexflow_tpu.search.serving_strategy import (
    choose_serving_strategy,
    tp_candidates,
)
from flexflow_tpu.serving.generation import GenerationModel

pytestmark = pytest.mark.mesh

CFG = TransformerConfig(
    num_layers=2, hidden_size=32, num_heads=4, ff_size=64,
    seq_length=64, vocab_size=61, causal=True,
)


@pytest.fixture(scope="module")
def params():
    return init_decoder_params(jax.random.key(0), CFG)


# ------------------------------------------------------------ 1-device mesh
def test_one_device_mesh_bit_for_bit(params):
    """tp_degree=1 routes through the full mesh-native path (sharded
    jits, explicit out-shardings, committed staging) and must reproduce
    the legacy engine's streams exactly — greedy AND seeded sampling."""
    prompts = [[1, 2, 3, 4, 5, 6, 7], [9, 8, 7, 6, 5]]
    greedy = SamplingParams(max_new_tokens=8)
    temp = SamplingParams(max_new_tokens=8, temperature=0.7, top_k=5, seed=3)

    legacy = GenerationEngine(params, CFG, max_batch_slots=2, block_size=8)
    meshed = GenerationEngine(
        params, CFG, max_batch_slots=2, block_size=8, tp_degree=1
    )
    assert legacy.generate(prompts, greedy) == meshed.generate(prompts, greedy)
    assert legacy.generate(prompts, temp) == meshed.generate(prompts, temp)
    assert meshed.recompiles() == {}
    assert meshed.trace_counts.get("decode", 0) == 1
    assert meshed.tp_degree == 1 and meshed.mesh_devices == 1


def test_one_device_strategy_in_ledger(params):
    """The layout decision registers in the engine's truth ledger and
    measured steps pair against it (drift telemetry covers the choice)."""
    eng = GenerationEngine(
        params, CFG, max_batch_slots=2, block_size=8, tp_degree=1
    )
    eng.generate([[1, 2, 3, 4]], SamplingParams(max_new_tokens=6))
    rep = eng.ledger.report()
    by_key = {e["key"]: e for e in rep["entries"]}
    assert "serving_strategy:decode" in by_key
    assert "serving_strategy:prefill" in by_key
    # steady-state decode steps after the single compile joined as pairs
    assert by_key["serving_strategy:decode"]["pairs"] >= 1
    # an analytic ranking estimate must never raise "calibration drift"
    assert by_key["serving_strategy:decode"]["alarm_enabled"] is False


# ------------------------------------------------------------- cache sizing
def test_from_budget_is_per_device_hbm_aware():
    base = CacheConfig.from_budget(
        1 << 20, num_layers=2, num_heads=4, head_dim=8, block_size=16
    )
    sharded = CacheConfig.from_budget(
        1 << 20, num_layers=2, num_heads=4, head_dim=8, block_size=16,
        kv_shards=4,
    )
    # the same per-chip budget buys tp x the blocks
    assert sharded.num_blocks == base.num_blocks * 4
    with pytest.raises(ValueError, match="num_kv_heads % tp_degree"):
        CacheConfig.from_budget(
            1 << 20, num_layers=2, num_heads=4, head_dim=8, kv_shards=3
        )


def test_for_slots_sharing_discount():
    kw = dict(num_layers=2, num_heads=4, head_dim=8, max_seq_len=256,
              max_batch_slots=8, block_size=16)
    worst = CacheConfig.for_slots(**kw)
    assert worst.num_blocks == 1 + (256 // 16) * 8  # the old default bound
    shared = CacheConfig.for_slots(**kw, expected_prefix_sharing=0.5)
    assert shared.num_blocks == 1 + (256 // 16) * 8 // 2
    # floor: one full-length slot + a block per remaining slot survives
    # any discount
    deep = CacheConfig.for_slots(**kw, expected_prefix_sharing=0.99)
    assert deep.num_blocks >= 1 + 256 // 16 + 7
    with pytest.raises(ValueError, match="expected_prefix_sharing"):
        CacheConfig.for_slots(**kw, expected_prefix_sharing=1.0)


def test_validate_kv_shards_message():
    with pytest.raises(ValueError, match="num_kv_heads % tp_degree"):
        validate_kv_shards(4, 3)
    validate_kv_shards(4, 2)  # divides: no raise


# ------------------------------------------------------------ chip geometry
def test_chip_spec_scales_to_mesh_geometry():
    one = chip_spec_for("TPU v5e")
    four = chip_spec_for(mesh_device_kind("TPU v5e", 4))
    assert four.name == f"{one.name} x4"
    assert four.bf16_flops == one.bf16_flops * 4
    assert four.f32_flops == one.f32_flops * 4
    assert four.hbm_capacity == one.hbm_capacity * 4
    # per-link ICI numbers do not add up across chips
    assert four.ici_bandwidth == one.ici_bandwidth
    assert mesh_device_kind("cpu", 1) == "cpu"  # count 1 is a no-op
    assert chip_spec_for("cpu x2").f32_flops == chip_spec_for("cpu").f32_flops * 2


# --------------------------------------------------------- strategy search
def test_tp_candidates_divide_heads():
    assert tp_candidates(4, 4) == [1, 2, 4]
    assert tp_candidates(4, 3) == [1, 2]
    assert tp_candidates(6, 8) == [1, 2, 3, 6]


def test_choose_serving_strategy_scores_and_pins():
    auto = choose_serving_strategy(CFG, mesh_devices=4, max_batch_slots=4)
    assert [c["tp_degree"] for c in auto.candidates[:1]] == [auto.tp_degree]
    assert auto.pinned is False
    assert all(c["prefill_s"] > 0 and c["decode_s"] > 0 for c in auto.candidates)
    # the chosen candidate minimizes the decode-weighted blend
    assert auto.candidates[0]["blend_s"] == min(
        c["blend_s"] for c in auto.candidates
    )
    pinned = choose_serving_strategy(
        CFG, mesh_devices=4, max_batch_slots=4, pinned_tp=4
    )
    assert pinned.tp_degree == 4 and pinned.pinned is True
    assert len(pinned.candidates) == 3  # the road not taken stays visible
    with pytest.raises(ValueError, match="not a valid candidate"):
        choose_serving_strategy(CFG, mesh_devices=4, pinned_tp=3)


def test_layout_validation_and_describe():
    with pytest.raises(ValueError, match="num_kv_heads % tp_degree"):
        ServingLayout.build(num_heads=4, tp_degree=3)
    lay = ServingLayout.build(num_heads=4, tp_degree=1)
    d = lay.describe()
    assert d["tp_degree"] == 1 and d["kv_heads_per_shard"] == 4
    assert d["specs"]["block_tables"] == "replicated"


# ---------------------------------------------------- site + observability
def test_collective_site_registered_and_inert_unsharded(params):
    assert faults.GENERATION_COLLECTIVE in faults.SITES
    eng = GenerationEngine(
        params, CFG, max_batch_slots=2, block_size=8, tp_degree=1
    )
    plan = faults.FaultPlan(seed=0)
    plan.on(faults.GENERATION_COLLECTIVE, mode="error",
            error=RuntimeError("boom"), every=1)
    with plan.active():
        out = eng.generate([[1, 2, 3]], SamplingParams(max_new_tokens=4))
    assert len(out[0]) == 4
    # tp_degree == 1: no collective boundary exists, the site never fires
    assert plan.fired(faults.GENERATION_COLLECTIVE) == 0


def test_mesh_gauges_and_metadata(params):
    eng = GenerationEngine(
        params, CFG, max_batch_slots=2, block_size=8, tp_degree=1
    )
    model = GenerationModel(eng, name="lm")
    gv = model.stats.gauge_values()
    assert gv["mesh_devices"] == 1
    assert gv["tp_degree"] == 1
    assert gv["cache_shard_bytes"] == eng.cache_config.total_bytes
    assert gv["cache_shard_heads"] == CFG.num_heads
    meta = model.metadata()
    ss = meta["serving_strategy"]
    assert ss["tp_degree"] == 1 and ss["mesh_devices"] == 1
    assert ss["search"]["pinned"] is True
    assert ss["layout"]["kv_heads_per_shard"] == 4


def test_engine_expected_prefix_sharing_knob(params):
    full = GenerationEngine(params, CFG, max_batch_slots=4, block_size=8)
    shared = GenerationEngine(
        params, CFG, max_batch_slots=4, block_size=8,
        expected_prefix_sharing=0.5,
    )
    assert shared.cache_config.num_blocks < full.cache_config.num_blocks
    # a single unshared stream can still reach max_seq_len
    assert shared.cache_config.num_blocks >= 1 + 64 // 8


# ------------------------------------------------- forced 4-device matrix
_MATRIX = r"""
import json
import jax
import numpy as np

assert len(jax.devices()) == 4, jax.devices()

from flexflow_tpu.generation import (ContinuousBatchingScheduler,
                                     GenerationEngine, RecoveryPolicy,
                                     SamplingParams, SpeculationConfig,
                                     init_decoder_params)
from flexflow_tpu.models.transformer import TransformerConfig
from flexflow_tpu.runtime import faults

cfg = TransformerConfig(num_layers=2, hidden_size=32, num_heads=4, ff_size=64,
                        seq_length=64, vocab_size=61, causal=True)
params = init_decoder_params(jax.random.key(0), cfg)
res = {}

def build(tp, prefix=False):
    return GenerationEngine(params, cfg, max_batch_slots=2, block_size=8,
                            tp_degree=tp, max_spec_tokens=3,
                            prefix_cache=prefix)

prompts = [[1, 2, 3, 4, 5, 6, 7], [9, 8, 7, 6, 5], list(range(1, 20))]
modes = {
    "greedy": SamplingParams(max_new_tokens=8),
    "temp": SamplingParams(max_new_tokens=8, temperature=0.8, seed=11),
    "topk": SamplingParams(max_new_tokens=8, temperature=1.0, top_k=7, seed=5),
}
e1, e4 = build(1), build(4)
for name, samp in modes.items():
    res[f"sampling:{name}"] = e1.generate(prompts, samp) == e4.generate(prompts, samp)
res["cache_sharded"] = "model" in str(e4.cache.k.sharding.spec)
res["zero_retraces_tp4"] = e4.recompiles() == {}
res["strategy_follows_mesh"] = e4.serving_strategy_block().get("tp_degree") == 4
res["chip_spec_follows_mesh"] = e4.flops_model.chip.name.endswith(" x4")

# speculative
motif = [5, 9, 2]
sp = [(motif * 8)[:17], (motif * 8)[:11]]
spec = SpeculationConfig(k=3, method="ngram")
g = SamplingParams(max_new_tokens=8)
res["speculative"] = (build(1).generate(sp, g, speculation=spec)
                      == build(4).generate(sp, g, speculation=spec))

# prefix caching
tpl = list(np.random.RandomState(0).randint(1, 60, 24))
pp = [tpl + [7, 8], tpl + [9, 10, 11]]
p1, p4 = build(1, prefix=True), build(4, prefix=True)
res["prefix"] = p1.generate(pp, g) == p4.generate(pp, g)
res["prefix_hit"] = p4.prefix_cache.hits >= 1

# overlap pipeline on vs the 1-device engine
def run(engine, overlap):
    sched = ContinuousBatchingScheduler(engine, overlap=overlap)
    hs = [sched.submit(list(p), g) for p in prompts]
    while any(not h.done() for h in hs):
        if not sched.step():
            break
    return [h.result(timeout=0) for h in hs], sched

o1, _ = run(build(1), False)
o4, s4 = run(build(4), True)
res["overlap"] = o1 == o4
res["overlap_engaged"] = s4.pipe_dispatches > 0

# collective failure -> supervisor retry AND full restart + journal
# replay over the SHARDED cache, byte-exact both ways
policy = RecoveryPolicy(sleep=lambda _s: None)
ref_eng = build(4)
ref_sched = ContinuousBatchingScheduler(ref_eng, recovery=policy)
hs = [ref_sched.submit(list(p), g) for p in prompts]
while any(not h.done() for h in hs):
    if not ref_sched.step():
        break
ref = [h.result(timeout=0) for h in hs]
for legs, nth in (("retry", (2,)), ("restart", (2, 3))):
    eng = build(4)
    sched = ContinuousBatchingScheduler(eng, recovery=policy)
    plan = faults.FaultPlan(seed=0)
    plan.on(faults.GENERATION_COLLECTIVE, mode="error",
            error=RuntimeError("collective down"), nth=nth)
    with plan.active():
        hs = [sched.submit(list(p), g) for p in prompts]
        while any(not h.done() for h in hs):
            if not sched.step():
                break
    got = [h.result(timeout=0) for h in hs]
    res[f"collective_{legs}"] = got == ref
    if legs == "restart":
        res["collective_restarted"] = sched.recovery_stats.recoveries >= 1

# head-sharded Pallas kernel (interpret) vs reference, on the real mesh
from jax.sharding import Mesh
from flexflow_tpu.ops.kernels.decode_attention import (
    reference_paged_attention, sharded_paged_decode_attention)
mesh = Mesh(np.asarray(jax.devices()), ("model",))
rs = np.random.RandomState(0)
q = rs.randn(3, 4, 64).astype(np.float32)
kc = rs.randn(2, 6, 8, 4, 64).astype(np.float32)  # [L, nb, bs, H, D]
vc = rs.randn(2, 6, 8, 4, 64).astype(np.float32)
bt = rs.randint(0, 6, (3, 4)).astype(np.int32)
cl = np.array([5, 17, 30], np.int32)
q, kc, vc, bt, cl = map(jax.numpy.asarray, (q, kc, vc, bt, cl))
ref_o = reference_paged_attention(q, kc, vc, 1, bt, cl)
shd_o = sharded_paged_decode_attention(q, kc, vc, 1, bt, cl, mesh, interpret=True)
res["kernel_parity"] = bool(np.allclose(np.asarray(ref_o), np.asarray(shd_o),
                                        atol=2e-5))

print("MESH_MATRIX " + json.dumps(res))
"""


def test_four_device_matrix_byte_identical(tmp_path):
    """The acceptance matrix, in one child process with 4 forced host
    devices: every sampling mode, speculation, prefix caching, overlap,
    and collective-failure recovery byte-identical between the tp=4 and
    1-device engines; sharded kernel parity rides along."""
    script = tmp_path / "mesh_matrix.py"
    script.write_text(_MATRIX)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (
        env.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=4"
    ).strip()
    # the child runs from tmp_path: python puts the SCRIPT's dir on
    # sys.path, not the cwd — the repo import needs PYTHONPATH
    env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run(
        [sys.executable, str(script)],
        cwd=repo, env=env, capture_output=True, text=True, timeout=540,
    )
    assert proc.returncode == 0, f"matrix child failed:\n{proc.stdout}\n{proc.stderr}"
    line = next(
        (l for l in proc.stdout.splitlines() if l.startswith("MESH_MATRIX ")),
        None,
    )
    assert line, f"no matrix verdict in output:\n{proc.stdout}"
    res = json.loads(line[len("MESH_MATRIX "):])
    bad = {k: v for k, v in res.items() if v is not True}
    assert not bad, f"mesh matrix legs failed: {bad}"
