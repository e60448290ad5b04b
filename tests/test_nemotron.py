"""NVIDIA-Nemotron-3-Super-120B-A12B on the normal path, at rehearsal size
on the CPU (PR 48): Mamba-2 state-space layers whose state lives per slot
(a chunked prefill, a one-pass decode update, a hand-over between them),
one attention layer without positions, ungated relu^2 experts in a latent
of which the engine holds a share, one branch a layer, against the
benchmark's plain float32 reference, logits not tokens; the update kernel
in interpret mode; the shares add up; the refusals by name."""
import dataclasses
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
from benchmark.reference import nemotron_h  # noqa: E402
from flexflow_tpu.generation import GenerationEngine, decoder  # noqa: E402
from flexflow_tpu.generation.cache import SlotStateConfig  # noqa: E402
from flexflow_tpu.generation.engine import SamplingParams, unsupported_paths  # noqa: E402
from flexflow_tpu.obs.capacity import ServingFlops  # noqa: E402
from flexflow_tpu.ops import ssm  # noqa: E402

FILE = json.loads((ROOT / "benchmark/configs/nemotron-3-super-120b-a12b.json").read_text())
# hidden 64; MEM*EME: 3 ssm layers of 8 heads x 16 in 2 groups of state 16 (chunks of 8), 1 attention layer of 4 over 2
# heads of 16, 3 expert layers of 4 held of 16 relu^2 experts (top-4) in a latent of 32 beside a shared one of 48
CONFIG = spec._merge(FILE, FILE["rehearsal"])
# logits of a 7-layer float32 model summed in another order (chunks against positions, a cache against a full
# forward): errors of 1e-5; a state one token off, a wrong group or a missing expert moves them by tenths
ATOL = 2e-4


@pytest.fixture(scope="module")
def model():
    params = nemotron_h.cast_params(nemotron_h.init_params(7, CONFIG), jnp.float32)
    return nemotron_h.engine_config(CONFIG, 128), params


def reference_logits(params, tokens, config=CONFIG):
    at = jnp.tile(jnp.arange(tokens.shape[1])[None], (tokens.shape[0], 1))
    return np.asarray(nemotron_h.logits_at(params, jnp.asarray(tokens), at, config))


@pytest.fixture(scope="module")
def engine(model):
    """ONE engine for the tests that serve through it (its programs compile once); each takes it reset."""
    return engine_of(model)


@pytest.fixture
def eng(engine):
    engine.reset()
    return engine


def engine_of(model, **kw):
    cfg, params = model
    kw = {"max_batch_slots": 3, "block_size": 8, "prompt_buckets": [16, 32], "max_seq_len": 64, **kw}
    return GenerationEngine(params, cfg, **kw)


def test_the_rehearsal_preset_is_the_pattern_with_one_branch_a_layer(model):
    cfg, params = model
    assert cfg.block == "single" and cfg.layer_types == ("ssm", "ffn", "ssm", "attention", "ffn", "ssm", "ffn")
    assert cfg.ssm_layers == (0, 2, 5) and cfg.attention_layers == cfg.full_layers == (3,) and cfg.expert_layers == (1, 4, 6)
    assert cfg.stateful and not cfg.conv_layers and cfg.kv_index == (("attention", 0),)
    assert (cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_groups, cfg.ssm_state_size, cfg.ssm_conv_kernel, cfg.ssm_chunk) == (8, 16, 2, 16, 4, 8)
    assert (cfg.num_experts, cfg.experts_held, cfg.experts_per_token, cfg.moe_latent_size, cfg.shared_ff_size) == (16, (0, 1, 2, 3), 4, 32, 48)
    assert cfg.expert_activation == "relu2" and cfg.routed_scaling_factor == 5.0 and cfg.rope_parameters == {"attention": {"positions": "none"}}
    m, e = params["layers"][0], params["layers"][1]
    assert m["ssm_in"].shape == (64, 128 + 192 + 8) and m["ssm_conv_w"].shape == (192, 4) and m["ssm_a_log"].dtype == jnp.float32
    assert e["ew1"].shape == (4, 32, 24) and e["ew2"].shape == (4, 24, 32) and "ew3" not in e and e["lat_up"].shape == (32, 64)
    assert e["sw1"].shape == (64, 48) and "sw3" not in e and "ln2_g" not in e and "pos_embed" not in params
    # the program's own initialiser makes the same pytree
    own = decoder.init_decoder_params(jax.random.key(0), cfg)
    assert jax.tree.map(lambda a: (a.shape, a.dtype), own) == jax.tree.map(lambda a: (a.shape, a.dtype), params)
    # and its state-space parameters are the published initialisation's: rates 1..H, steps in [1e-3, 1e-1], D = 1
    assert np.allclose(np.exp(own["layers"][0]["ssm_a_log"]), np.arange(1, 9)) and np.all(own["layers"][0]["ssm_d"] == 1)
    step = np.asarray(jax.nn.softplus(own["layers"][0]["ssm_dt_bias"]))
    assert np.all((step >= 1e-3 - 1e-6) & (step <= 0.1 + 1e-6))


def test_the_parameter_count_is_the_published_one_recomputed_from_the_file():
    n = nemotron_h.parameter_counts(FILE)
    assert round(n["whole"] / 1e9, 2) == 120.67 and round(n["active"] / 1e9, 2) == 12.77  # "120B-A12B"
    assert round(n["held"] / 1e9, 3) == 4.648 and round(2 * n["held"] / 1e9, 2) == 9.30
    assert round(n["ssm_layer"] / 1e6, 2) == 109.64 and round(n["attention_layer"] / 1e6, 2) == 35.66
    assert round(n["expert_layer_outside"] / 1e6, 2) == 54.53 and round(n["routed_expert"] / 1e6, 3) == 5.505
    assert 2 * n["held"] / 16.9e9 > 0.25  # the floor of a cell's size, by the weights alone
    # what a sequence keeps: S [128, 64, 128] float32 and 3 rows of xBC a layer, five layers
    cfg = nemotron_h.engine_config(FILE, 2048)
    per_layer = 4 * cfg.ssm_inner * cfg.ssm_state_size + 2 * 3 * cfg.ssm_conv_width
    assert cfg.ssm_inner == 8192 and cfg.ssm_conv_width == 10240 and 5 * per_layer == 21_278_720
    flops = ServingFlops.from_config(cfg, dtype=cfg.dtype)
    assert flops.param_count == pytest.approx(n["held"], rel=2e-3) and flops.state_bytes_per_seq == 5 * 4 * 8192 * 128
    assert flops.decode_bytes(128, 128 * 1000) > 2 * 128 * flops.state_bytes_per_seq + flops.param_bytes


def test_forward_full_is_the_reference(model):
    cfg, params = model
    tokens = np.random.RandomState(1).randint(0, 512, size=(2, 45)).astype(np.int32)
    with jax.default_matmul_precision("highest"):
        got = np.asarray(decoder.forward_full(params, jnp.asarray(tokens), cfg=cfg))
    np.testing.assert_allclose(got, reference_logits(params, tokens), atol=ATOL)


@pytest.mark.parametrize("length", [8, 13, 16, 21, 32])  # on a chunk's end, inside one, on a bucket's end, past it, the last bucket's
def test_prefill_then_decode_through_the_state_is_the_full_forward(model, eng, length):
    """The engine's own programs: a prefill at a bucket (padding behind the
    prompt), the hand-over of the slot's state, decode steps through the
    state and the cache, with a slot freed and reused in between."""
    cfg, params = model
    rs = np.random.RandomState(length)
    first, prompt = rs.randint(0, 512, 11).tolist(), rs.randint(0, 512, length).tolist()
    # another sequence takes slot 0 first, runs, and leaves its state behind
    eng.generate([first], SamplingParams(max_new_tokens=5, temperature=0.0))
    assert float(jnp.abs(eng.cache.state["ssm"][:, 0]).max()) > 0
    out = eng.generate([prompt], SamplingParams(max_new_tokens=10, temperature=0.0))[0]
    tokens = np.asarray([prompt + list(out)], np.int32)
    want = reference_logits(params, np.pad(tokens, ((0, 0), (0, 42 - tokens.shape[1]))))[0]  # (one shape: one compile)
    assert list(out) == np.argmax(want[length - 1 : length + 9], -1).tolist()


def test_the_logits_step_by_step_through_the_state_a_prefill_left(model):
    """decoder.prefill, the hand-over as the engine makes it, then
    decoder.decode_step: logits against the reference's full forward."""
    cfg, params = model
    length = 13
    tokens = np.random.RandomState(0).randint(0, 512, size=(1, length + 3)).astype(np.int32)
    want = reference_logits(params, tokens)[0]
    logits, ks, vs, left = jax.jit(lambda p, t: decoder.prefill(p, t, None, cfg))(params, jnp.asarray(tokens[:, :length]))
    np.testing.assert_allclose(np.asarray(logits[0]), want[:length], atol=ATOL)
    state = {"ssm_conv": jax.vmap(lambda z: decoder.state_at(z, jnp.asarray([length]), 4))(left["xbc"]),
             "ssm": ssm.pack_state(left["state"], cfg.ssm_groups)}
    cache_k = jnp.zeros((1, 9, 8, cfg.kv_heads, cfg.dim_per_head), jnp.float32)
    table = jnp.arange(1, 9, dtype=jnp.int32)[None]
    block, offset = jnp.arange(length) // 8 + 1, jnp.arange(length) % 8
    cache_k, cache_v = (decoder.write_rows(cache_k, 0, block, offset, rows[0, 0]) for rows in (ks, vs))
    step = jax.jit(lambda p, t, pos, ck, cv, st: decoder.decode_step(p, t, pos, ck, cv, table, pos + 1, cfg=cfg, ssm=st))
    for i in range(3):
        pos = length + i
        got, cache_k, cache_v, state = step(params, jnp.asarray(tokens[:, pos]), jnp.asarray([pos]), cache_k, cache_v, state)
        np.testing.assert_allclose(np.asarray(got[0]), want[pos], atol=ATOL)


def test_a_padded_bucket_and_a_dead_slot_never_touch_a_live_state(model, eng):
    cfg, params = model
    tokens = np.random.RandomState(3).randint(0, 512, size=(2, 32)).astype(np.int32)
    lens = jnp.asarray([32, 19])
    run = jax.jit(lambda p, t, n: decoder.prefill(p, t, n, cfg))
    padded = run(params, jnp.asarray(tokens), lens)
    alone = run(params, jnp.asarray(tokens[1:, :19]), None)
    np.testing.assert_allclose(np.asarray(padded[0][1, :19]), np.asarray(alone[0][0]), atol=ATOL)
    # the state AT the sequence's own length: the padding rows behind it passed it on unchanged
    np.testing.assert_allclose(np.asarray(padded[3]["state"][:, 1]), np.asarray(alone[3]["state"][:, 0]), atol=1e-6)
    # a decode step whose slot 1 is not live leaves that slot's two parts bit for bit
    eng.generate([tokens[0, :20].tolist(), tokens[1, :9].tolist()], SamplingParams(max_new_tokens=2, temperature=0.0))
    before = {k: np.asarray(v) for k, v in eng.cache.state.items()}
    state = dict(eng.cache.state)
    live = jnp.asarray([1, 0, 0], jnp.int32)
    out = jax.jit(lambda p, ck, cv, st: decoder.decode_step(
        p, jnp.asarray([5, 6, 7]), jnp.asarray([22, 0, 0]), ck, cv,
        jnp.zeros((3, eng.max_blocks_per_seq), jnp.int32), live * 23, cfg=cfg, ssm=st)[3])(params, eng.cache.k, eng.cache.v, state)
    for name, part in out.items():
        assert np.array_equal(np.asarray(part)[:, 1:], before[name][:, 1:]) and not np.array_equal(np.asarray(part)[:, 0], before[name][:, 0])


@pytest.mark.parametrize("length, chunk", [(37, 8), (64, 16), (5, 8)])
def test_the_chunked_scan_is_the_recurrence_position_by_position(length, chunk):
    keys = jax.random.split(jax.random.key(length), 4)
    b, h, p, g, n = 2, 8, 16, 2, 16
    x = jax.random.normal(keys[0], (b, length, h, p))
    dt = jax.nn.softplus(jax.random.normal(keys[1], (b, length, h)) - 3.0).at[1, length - 3 :].set(0.0)  # (padding rows)
    a = -jnp.arange(1, h + 1, dtype=jnp.float32)
    bb, cc = jax.random.normal(keys[2], (b, length, g, n)), jax.random.normal(keys[3], (b, length, g, n))
    y0, s0 = jax.jit(ssm.recurrence)(x, dt, a, bb, cc)
    y1, s1 = jax.jit(ssm.chunk_scan, static_argnums=5)(x, dt, a, bb, cc, chunk)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y0), atol=2e-5)
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s0), atol=2e-6)
    # rows of dt = 0 pass the state on: sequence 1's state is the one three positions earlier
    _, s_short = jax.jit(ssm.recurrence)(x[1:, : length - 3], dt[1:, : length - 3], a, bb[1:, : length - 3], cc[1:, : length - 3])
    np.testing.assert_allclose(np.asarray(s1[1]), np.asarray(s_short[0]), atol=2e-6)


def test_the_update_kernel_interpreted_is_its_xla_composition_and_the_recurrence():
    keys = jax.random.split(jax.random.key(2), 6)
    slots, h, p, g, n = 3, 32, 64, 2, 128  # two heads of 64 a row of 128 lanes, as the cell stores them
    assert ssm.state_shape(h, p, g, n) == (16, 128, 128) and ssm.state_shape(128, 64, 8, 128) == (64, 128, 128)
    x = jax.random.normal(keys[0], (slots, h, p)).astype(jnp.bfloat16)
    dt = jax.nn.softplus(jax.random.normal(keys[1], (slots, h)) - 3.0).at[2].set(0.0)
    x = x.at[2].set(0)  # slot 2 is not live
    a = -jnp.arange(1, h + 1, dtype=jnp.float32)
    b, c = (jax.random.normal(k, (slots, g, n)).astype(jnp.bfloat16) for k in keys[2:4])
    plain = jax.random.normal(keys[4], (2, slots, h, p, n))
    stored = ssm.pack_state(plain, g)
    assert np.array_equal(np.asarray(ssm.unpack_state(stored, p)), np.asarray(plain))
    y_ref, s_ref = ssm.update_reference(stored, 1, x, dt, a, b, c)
    y_k, s_k = ssm.update(stored, 1, x, dt, a, b, c, interpret=True)
    np.testing.assert_allclose(np.asarray(y_k), np.asarray(y_ref), atol=1e-4)
    np.testing.assert_allclose(np.asarray(s_k), np.asarray(s_ref), atol=1e-5)
    assert np.array_equal(np.asarray(s_k[0]), np.asarray(stored[0]))  # another layer's state is not this call's
    assert np.array_equal(np.asarray(s_k[1, 2]), np.asarray(stored[1, 2]))  # a slot that is not live: bit for bit
    y_rec, s_rec = ssm.recurrence(x[:, None], dt[:, None], a, b[:, None], c[:, None], state=plain[1])
    np.testing.assert_allclose(np.asarray(y_k), np.asarray(y_rec[:, 0]), atol=1e-4)
    np.testing.assert_allclose(np.asarray(ssm.unpack_state(s_k[1], p)), np.asarray(s_rec), atol=1e-5)


def test_the_shares_add_up_to_the_whole_expert_layer(model):
    """The four shares' routed parts (each through its own W_up, which is
    linear) plus the shared expert counted once equal the uncut layer."""
    cfg, params = model
    uncut = dict(CONFIG, n_routed_experts=16, expert_share={"chips": 1, "chip": 0})
    whole = nemotron_h.cast_params(nemotron_h.init_params(11, uncut), jnp.float32)["layers"][1]
    u = jax.random.normal(jax.random.key(4), (2, 9, 64))
    want = nemotron_h.expert_layer(u, whole, uncut, range(16))
    total = jnp.zeros_like(want)
    dcfg = nemotron_h.engine_config(uncut, 128)
    # (no routed expert: the shared expert alone; an empty scan over empty stacks)
    shared_once = nemotron_h.expert_layer(u, dict(whole, ew1=whole["ew1"][:0], ew2=whole["ew2"][:0]), uncut, (), shared=True)
    for chip in range(4):
        held = tuple(range(4 * chip, 4 * chip + 4))
        share = dict(whole, ew1=whole["ew1"][4 * chip : 4 * chip + 4], ew2=whole["ew2"][4 * chip : 4 * chip + 4])
        part = nemotron_h.expert_layer(u, share, uncut, held, shared=False)
        total = total + part
        # and the program's expert layer under that share computes the same part (plus the shared expert)
        mine = dataclasses.replace(dcfg, experts_held=held)
        rows = u.reshape(-1, 64)
        with jax.default_matmul_precision("highest"):
            got = decoder._ffn(mine, 1, share, rows, jnp.ones((rows.shape[0],), bool), None, normed=rows)
        np.testing.assert_allclose(np.asarray(got).reshape(u.shape), np.asarray(
            part + shared_once), atol=ATOL)
    np.testing.assert_allclose(np.asarray(total + shared_once), np.asarray(want), atol=ATOL)


def test_the_state_is_named_parts_per_slot_and_nothing_indexes_a_prefix(model, eng):
    cfg, _ = model
    ss = eng.slot_state
    assert isinstance(ss, SlotStateConfig) and ss.names == ("ssm_conv", "ssm") and eng.state_config is None
    assert {k: (v.shape, v.dtype) for k, v in eng.cache.state.items()} == {
        "ssm_conv": ((3, 3, 3, 192), jnp.float32), "ssm": ((3, 3, 2, 16, 64), jnp.float32)}
    assert ss.bytes_per_sequence == 3 * 4 * (3 * 192 + 8 * 16 * 16) and ss.total_bytes == 3 * ss.bytes_per_sequence
    assert not eng.prefix_cache.enabled and "per slot" in eng.unsupported["prefix_reuse"]
    prompt = list(range(40, 60))
    eng.generate([prompt], SamplingParams(max_new_tokens=3, temperature=0.0))
    eng.generate([prompt], SamplingParams(max_new_tokens=3, temperature=0.0))  # the same prompt again: prefilled whole
    assert eng.prefix_plan(prompt).reuse_tokens == 0 and eng.trace_counts.get("prefix_prefill[32]", 0) == 0
    stats = eng.cache_stats()["ssm"]
    assert stats["bytes_per_slot"] == ss.bytes_per_sequence and stats["bytes_held"] == ss.total_bytes and stats["slots_live"] == 1
    # a budget pays for the slots' state before any block
    small = GenerationEngine(model[1], cfg, cache_budget_bytes=ss.total_bytes + 40 * eng.cache_config.bytes_per_block,
                             max_batch_slots=3, block_size=8, prompt_buckets=[16, 32], max_seq_len=64)
    assert small.cache_config.num_blocks <= 41
    with pytest.raises(ValueError, match="state-space state"):
        GenerationEngine(model[1], cfg, cache_budget_bytes=ss.total_bytes, max_batch_slots=3, block_size=8, max_seq_len=64)
    # crash recovery: the state is zero again
    eng.reset()
    assert not any(bool(jnp.any(v)) for v in eng.cache.state.values())


def test_what_cannot_carry_a_state_is_refused_by_name(model, eng):
    cfg, params = model
    assert set(eng.unsupported) == {"speculation", "kv_handoff", "tensor_parallel", "prefix_reuse"}
    assert eng.unsupported == unsupported_paths("ssm", cfg)
    with pytest.raises(NotImplementedError, match="state-space"):
        GenerationEngine(params, cfg, tp_degree=2, max_batch_slots=2, max_seq_len=64)
    with pytest.raises(NotImplementedError, match="append window over ssm layers"):
        decoder.verify_step(params, jnp.zeros((1, 2), jnp.int32), jnp.zeros((1, 2), jnp.int32), eng.cache.k, eng.cache.v,
                            jnp.zeros((1, 8), jnp.int32), cfg=cfg)
    with pytest.raises(ValueError, match="'single' block"):
        decoder.DecoderConfig(num_layers=2, hidden_size=8, num_heads=2, ff_size=8, seq_length=8, vocab_size=8, layer_types=("ssm", "ffn"),
                              ssm_heads=2, ssm_head_dim=4, ssm_state_size=4)
    with pytest.raises(ValueError, match="an 'ssm' layer needs"):
        decoder.DecoderConfig(num_layers=1, hidden_size=8, num_heads=2, ff_size=8, seq_length=8, vocab_size=8, layer_types=("ssm",))


def test_a_preempted_sequence_is_replayed_into_a_fresh_state(model, eng):
    """Two sequences over a pool that holds one and a half: the scheduler
    preempts, re-prefills prompt + generated, and the tokens are those of
    an engine with room."""
    cfg, params = model
    rs = np.random.RandomState(9)
    prompts = [rs.randint(0, 512, 14).tolist(), rs.randint(0, 512, 12).tolist()]
    roomy = eng.generate(prompts, SamplingParams(max_new_tokens=24, temperature=0.0))
    from flexflow_tpu.generation.cache import CacheConfig
    tight_cfg = CacheConfig(num_layers=1, num_heads=cfg.kv_heads, head_dim=cfg.dim_per_head, num_blocks=8, block_size=8, dtype=cfg.dtype)
    tight = GenerationEngine(params, cfg, tight_cfg, max_batch_slots=2, prompt_buckets=[16, 32], max_seq_len=64)
    got = tight.generate(prompts, SamplingParams(max_new_tokens=24, temperature=0.0))
    assert [list(g) for g in got] == [list(r) for r in roomy]


def test_the_probe_reads_the_state_the_programs_stored_and_the_picks_their_counters_counted(model, eng):
    """The driver's probe (``serve_nemotron.probe_engine``) on an engine
    whose slot 0 another sequence used before: request i's state lies in
    slot i after its last step, and the counters' picks are those of the
    positions fed, against the reference over the same positions."""
    from benchmark.drivers import serve_nemotron

    cfg, params = model
    rs = np.random.RandomState(0)
    prompts = [rs.randint(0, 512, n).tolist() for n in (13, 32, 8)]
    eng.generate([prompts[2]], SamplingParams(max_new_tokens=5))
    got = serve_nemotron.probe_engine(eng, params, prompts, 9)
    assert got["lengths"].tolist() == [13 + 8, 32 + 8, 8 + 8] and eng.params is params
    want = nemotron_h.probe(params, CONFIG, got["tokens"], got["lengths"], "float32")
    assert float(nemotron_h.state_error(got["state"], want["state"]).max()) < 1e-5  # (a state one position off: 1e-1)
    assert np.array_equal(got["picks"], want["picks"])
    over_prompts = nemotron_h.probe(params, CONFIG, got["tokens"][:, :32], got["prompt_lengths"], "float32")
    assert np.array_equal(got["prefill_picks"]["given"], over_prompts["picks"])


def test_rounding_the_router_s_weights_moves_picks_of_a_float32_router_and_none_of_a_bfloat16_one(model):
    """What ``router_shift`` rests on, in the reference's own arithmetics:
    to the control that multiplies bfloat16 operands the rounded weights
    ARE the weights; the stated arithmetic sees what lies below bfloat16."""
    cfg, params = model
    tokens = np.random.RandomState(4).randint(0, 512, size=(8, 128)).astype(np.int32)
    lengths, rounded = np.full((8,), 128, np.int32), nemotron_h.round_router(params)
    shift = {a: nemotron_h.pick_error(nemotron_h.probe(rounded, CONFIG, tokens, lengths, a)["picks"],
                                      nemotron_h.probe(params, CONFIG, tokens, lengths, a)["picks"]) for a in ("bfloat16", "bfloat16_router")}
    assert shift["bfloat16_router"].tolist() == [0.0, 0.0, 0.0] and shift["bfloat16"].sum() > 0
    # and a state rounded to bfloat16 lies from the stated arithmetic's where no sum in another order does
    states = {a: nemotron_h.probe(params, CONFIG, tokens, lengths, a)["state"] for a in ("bfloat16", "bfloat16_state")}
    assert float(nemotron_h.state_error(states["bfloat16_state"], states["bfloat16"])[0]) > 1e-3


def test_the_stored_state_s_distance_is_read_by_request_and_head_and_not_pooled():
    """``state_error`` on hand-made states of 8 rows x 16 heads: one head
    of one row off by half (a rounding of its ``dt`` that fell the other
    way moves ONE pair, and the pooled number with it where that head
    holds the norm) reads 0; every pair off by a thousandth (a coarser
    stored state) reads that; one row of eight off (a slot the update
    never visits) reads its rows' distance."""
    rs = np.random.RandomState(7)
    theirs = rs.standard_normal((2, 8, 16, 4, 8)).astype(np.float32)
    theirs[:, :, 0] *= 30.0  # (a head of slow decay holds most of a state's norm)
    one_pair = theirs.copy()
    one_pair[0, 3, 0] *= 1.5
    assert nemotron_h.state_error(one_pair, theirs).tolist() == [0.0, 0.0]
    assert nemotron_h.state_error_pooled(one_pair, theirs)[0] > 0.1 and nemotron_h.state_error_pooled(one_pair, theirs)[1] == 0.0
    assert nemotron_h.state_error(theirs * 1.001, theirs) == pytest.approx([1e-3, 1e-3], rel=1e-2)
    one_row = theirs.copy()
    one_row[0, 5] *= 0.5
    assert nemotron_h.state_error(one_row, theirs) == pytest.approx([0.5, 0.0])
    assert nemotron_h.state_error(one_row, theirs, 0.5).tolist() == [0.0, 0.0]  # (why the 0.9 share is held beside the median)
