"""JoyAI-LLM-Flash on the normal path, at rehearsal size on the CPU (PR
34): latent attention (one row a token a layer; an expanded prefill and
an absorbed decode that must agree with ONE reference), a shared expert
beside a SHARE of the routed ones, against the benchmark's plain float32
reference, logits not tokens; the latent kernel in interpret mode; the
cell's controls are other models."""
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
from benchmark.reference import joyai  # noqa: E402
from flexflow_tpu.generation import GenerationEngine, decoder  # noqa: E402
from flexflow_tpu.generation.cache import slot_mapping  # noqa: E402
from flexflow_tpu.generation.engine import SamplingParams  # noqa: E402
from flexflow_tpu.ops import attention  # noqa: E402
from flexflow_tpu.ops.kernels import decode_attention as da  # noqa: E402

FILE = json.loads((ROOT / "benchmark/configs/joyai-llm-flash.json").read_text())
# hidden 64, 4 heads scoring at 16 + 8 and weighing 16, rows of 32 + 8, 4 of 16 experts held top-2, 1 dense + 3 expert layers
CONFIG = spec._merge(FILE, FILE["rehearsal"])
# logits of a 4-layer float32 model summed in another order (absorbed against expanded, one head at a time
# against all at once): errors of 1e-5; a wrong scale, rotation or missing expert moves them by tenths
ATOL = 2e-4


@pytest.fixture(scope="module")
def model():
    params = joyai.cast_params(joyai.init_params(5, CONFIG), jnp.float32)
    return joyai.engine_config(CONFIG, 128), params


def reference_logits(params, tokens, config=CONFIG):
    at = jnp.tile(jnp.arange(tokens.shape[1])[None], (tokens.shape[0], 1))
    return np.asarray(joyai.logits_at(params, jnp.asarray(tokens), at, config))


def test_the_rehearsal_preset_is_latent_layers_a_shared_expert_and_a_share(model):
    cfg, params = model
    assert cfg.layer_types == ("latent",) * 4 and cfg.latent_layers == cfg.full_layers == (0, 1, 2, 3)
    assert (cfg.q_lora_rank, cfg.kv_lora_rank, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim) == (48, 32, 16, 8, 16)
    assert cfg.latent_width == 40 and cfg.rope_interleave and cfg.rope_theta == 32e6
    assert (cfg.num_experts, cfg.experts_held, cfg.experts_per_token, cfg.num_shared_experts) == (16, (0, 1, 2, 3), 2, 1)
    assert cfg.expert_layers == (1, 2, 3) and cfg.routed_scaling_factor == 2.5 and not cfg.tied_head
    layer = params["layers"][1]
    assert layer["router"].shape == (64, 16) and layer["ew1"].shape == (4, 64, 24) and layer["sw2"].shape == (24, 64)
    assert layer["w_ukv"].shape == (32, 4, 32) and layer["w_dkv"].shape == (64, 40) and "wq" not in layer
    # the program's own initialiser makes the same pytree
    own = decoder.init_decoder_params(jax.random.key(0), cfg)
    assert jax.tree.map(lambda a: a.shape, own) == jax.tree.map(lambda a: a.shape, params)


def test_forward_full_is_the_reference(model):
    cfg, params = model
    # (seed 0 holds ONE token of 192 at a near-tie of two experts' scores: its row moves by 0.6 with the
    # order of a float32 sum, every other by 4e-6; the cell's `gap_ratio` exists because of such tokens)
    tokens = np.random.RandomState(1).randint(0, 512, size=(2, 96)).astype(np.int32)
    with jax.default_matmul_precision("highest"):
        got = np.asarray(decoder.forward_full(params, jnp.asarray(tokens), cfg=cfg))
    np.testing.assert_allclose(got, reference_logits(params, tokens), atol=ATOL)


def test_padded_prefill_is_the_unpadded_forward_and_hands_over_one_row_a_position(model):
    cfg, params = model
    tokens = np.random.RandomState(2).randint(0, 512, size=(1, 64)).astype(np.int32)
    with jax.default_matmul_precision("highest"):
        logits, rows, vs = decoder.prefill(params, jnp.asarray(tokens), jnp.asarray([40]), cfg=cfg)
    assert rows.shape == (4, 1, 64, 128) and vs.shape == (4, 1, 64, 0)  # 40 values at 128 lanes; V has no width
    assert not np.asarray(rows[..., 40:]).any()  # the fill is zero
    np.testing.assert_allclose(np.asarray(logits)[:, :40], reference_logits(params, tokens[:, :40]), atol=ATOL)


@pytest.mark.parametrize("interleave", [True, False])
def test_absorbed_equals_expanded_for_one_layer_in_float32(model, interleave):
    """The two forms of ONE layer's attention over the same rows: the
    expanded one through ``masked_attention`` (score width 24, value
    width 16), the absorbed one over a cache the rows were written to.
    With ``rope_interleave`` false (no cell's: the rotary columns stored
    de-interleaved) the layer rotates halves, which is the rotation of
    pairs on columns put evens first."""
    cfg, params = model
    cfg = dataclasses.replace(cfg, rope_interleave=interleave)
    if not interleave:
        x, at = jnp.asarray(np.random.RandomState(6).randn(2, 5, 3, 8), jnp.float32), jnp.arange(10).reshape(2, 5)
        halves = lambda a: jnp.concatenate([a[..., 0::2], a[..., 1::2]], axis=-1)  # noqa: E731
        np.testing.assert_allclose(np.asarray(decoder._rope(halves(x), at, cfg.rope_theta)),
                                   np.asarray(halves(decoder._rope_pairs(x, at, cfg.rope_theta))), atol=1e-6)
    layer = params["layers"][1]
    rs = np.random.RandomState(4)
    h = jnp.asarray(rs.randn(2, 24, 64), jnp.float32)
    positions = jnp.arange(24)[None]
    bs = 8
    with jax.default_matmul_precision("highest"):
        q, rows = decoder._latent_qkv(cfg, layer, h, positions)
        expanded = decoder._expanded(cfg, q, rows, layer["w_ukv"], jnp.asarray([24, 24]))
        cache = jnp.zeros((1, 9, bs, 128), jnp.float32)
        tables = jnp.asarray([[1, 2, 3, 0], [4, 5, 6, 0]], jnp.int32)
        for b in range(2):
            block, offset = slot_mapping(tables[b], jnp.arange(24), bs)
            cache = decoder.write_rows(cache, 0, block, offset, rows[b])
        absorbed = decoder._absorbed(cfg, q, layer["w_ukv"], cache, 0, tables, jnp.tile(positions, (2, 1)), "cpu")
    assert expanded.shape == absorbed.shape == (2, 24, 4, 16)
    np.testing.assert_allclose(np.asarray(absorbed), np.asarray(expanded), atol=2e-5)


def test_masked_attention_takes_a_value_width_of_its_own():
    rs = np.random.RandomState(5)
    q, k, v = (jnp.asarray(rs.randn(2, 12, 4, d), jnp.float32) for d in (24, 24, 16))
    out = attention.masked_attention(q, k, v, jnp.asarray([12, 7]))
    assert out.shape == (2, 12, 4, 16)
    s = np.einsum("bqhd,bkhd->bhqk", np.asarray(q), np.asarray(k)) / np.sqrt(24)
    s = np.where(np.tril(np.ones((12, 12), bool))[None, None] & (np.arange(12) < 7)[None, None, None], s, -np.inf)
    p = np.exp(s - s.max(-1, keepdims=True))
    want = np.einsum("bhqk,bkhd->bqhd", p / p.sum(-1, keepdims=True), np.asarray(v))
    np.testing.assert_allclose(np.asarray(out)[1, :7], want[1, :7], atol=1e-5)


@pytest.mark.parametrize("prompt_len", [8, 24, 40, 64])
def test_prefill_then_decode_through_the_latent_cache_is_the_reference_s_full_forward(model, prompt_len):
    """The served path: an expanded prefill writes the rows, then 40
    greedy steps run the absorbed form over the paged latent cache:
    every step's choice is the argmax of the reference's full (expanded)
    forward over the same prefix, to 1e-3 of a logit (float32: the two
    forms differ by summation order only)."""
    cfg, params = model
    eng = GenerationEngine(params, cfg, max_batch_slots=2, block_size=8, prompt_buckets=[32, 64], max_seq_len=128)
    prompt = [int(t) for t in np.random.RandomState(prompt_len).randint(0, 512, size=prompt_len)]
    with jax.default_matmul_precision("highest"):
        out = eng.generate([prompt], SamplingParams(max_new_tokens=40))[0]
    logits = reference_logits(params, np.asarray([prompt + out], np.int32))[0, prompt_len - 1 : -1]
    gap = logits.max(-1) - logits[np.arange(len(out)), out]
    assert len(out) == 40 and float(gap.max()) < 1e-3
    stats = eng.latent_stats()
    assert stats["expanded_calls_total"] == 4 and stats["absorbed_calls_total"] == 4 * eng.step_counts["decode"]


def test_verify_step_agrees_with_the_reference_over_cached_rows(model):
    """A 6-token append window behind 30 cached positions (the shape of
    a suffix prefill behind a prefix hit) scores what the reference's
    full forward scores at those positions."""
    cfg, params = model
    bs, n = 8, 30
    tokens = np.random.RandomState(3).randint(0, 512, size=(1, n + 6)).astype(np.int32)
    table = jnp.arange(1, 9, dtype=jnp.int32)[None]
    with jax.default_matmul_precision("highest"):
        _, rows, _ = decoder.prefill(params, jnp.asarray(tokens[:, :n]), cfg=cfg)
        block, offset = slot_mapping(table[0], jnp.arange(n), bs)
        k, v = jnp.zeros((4, 16, bs, 128), jnp.float32), jnp.zeros((4, 16, bs, 0), jnp.float32)
        for li in range(4):
            k = decoder.write_rows(k, li, block, offset, rows[li, 0])
        positions = jnp.arange(n, n + 6)[None]
        logits, k2, v2 = decoder.verify_step(params, jnp.asarray(tokens[:, n:]), positions, k, v, table, cfg=cfg)
    assert k2.shape == k.shape and v2.shape == v.shape
    np.testing.assert_allclose(np.asarray(logits)[0], reference_logits(params, tokens)[0, n:], atol=ATOL)


def test_the_shares_add_up_to_the_uncut_layer():
    """16 experts over 4 chips: the 4 shares of one layer's routed sum,
    with the shared expert counted once, equal the layer with every
    expert held. Both from the reference and from the program."""
    whole = spec._merge(CONFIG, {"n_routed_experts": 16, "expert_share": {"chips": 1, "chip": 0}})
    params = joyai.cast_params(joyai.init_params(7, whole), jnp.float32)
    layer = params["layers"][2]
    v = jnp.asarray(np.random.RandomState(8).randn(1, 20, 64), jnp.float32)
    uncut = np.asarray(joyai.expert_layer(v, layer, whole, range(16)))
    shared = np.asarray(joyai._swiglu(v, layer["sw1"], layer["sw3"], layer["sw2"], {"dtype": jnp.dtype("float32")}))
    total_ref, total_prog = -3 * shared, shared[0]  # each share's reference counts the shared expert: once, not four times
    cfg = joyai.engine_config(whole, 128)
    for chip in range(4):
        held = tuple(range(4 * chip, 4 * chip + 4))
        part = dict(layer, **{k: layer[k][jnp.asarray(held)] for k in ("ew1", "ew3", "ew2")})
        total_ref = total_ref + np.asarray(joyai.expert_layer(v, part, whole, held))
        with jax.default_matmul_precision("highest"):
            routed, _ = decoder.expert_ffn(cfg, part, v[0], held=held)
        total_prog = total_prog + np.asarray(routed)
    np.testing.assert_allclose(total_ref, uncut, atol=1e-5)
    np.testing.assert_allclose(total_prog, uncut[0], atol=1e-4)


def test_a_share_leaves_out_what_absent_experts_would_add(model):
    """The program with 4 of 16 experts held is NOT the uncut model: the
    reference leaves the same part out (that they agree is the test
    above this one's neighbours), and the uncut reference differs."""
    cfg, params = model
    whole = spec._merge(CONFIG, {"n_routed_experts": 16, "expert_share": {"chips": 1, "chip": 0}})
    uncut = joyai.cast_params(joyai.init_params(5, whole), jnp.float32)
    assert uncut["layers"][1]["ew1"].shape[0] == 16
    tokens = np.random.RandomState(9).randint(0, 512, size=(1, 32)).astype(np.int32)
    assert np.abs(reference_logits(uncut, tokens, whole) - reference_logits(params, tokens)).max() > 1e-2


def test_the_counters_count_the_held_experts_and_once_the_tokens_routed_elsewhere(model):
    cfg, params = model
    eng = GenerationEngine(params, cfg, max_batch_slots=2, block_size=8, prompt_buckets=[32], max_seq_len=64)
    prompts = [[int(t) for t in np.random.RandomState(s).randint(0, 512, size=n)] for s, n in ((1, 20), (2, 28))]
    with jax.default_matmul_precision("highest"):
        outs = eng.generate(prompts, SamplingParams(max_new_tokens=6))
    stats = eng.expert_stats()
    assert stats["held"] == [0, 1, 2, 3] and stats["experts"] == 16 and len(stats["tokens_total"]) == 4
    # every token but a stream's last is run once, prompt or decode: the reference's count over the same sequences
    want = joyai.expert_tokens(params, CONFIG, [p + o[:-1] for p, o in zip(prompts, outs)])
    assert stats["tokens_total_by_layer"] == [[row[i] for i in stats["held"]] for row in want]
    tokens = sum(len(p) + len(o) - 1 for p, o in zip(prompts, outs))
    assert 0 < stats["unrouted_here_total"] <= 3 * tokens  # at most once a token a layer


@pytest.mark.parametrize("control", joyai.CONTROLS)
def test_each_control_is_another_model(model, control):
    """What the cell's controls compute, in the absorbed form and float32
    so that nothing but the control differs from the reference, lies
    tenths of a logit away at rehearsal size: none is a rounding. The
    stated form itself (no control) lies within the summation order."""
    _, params = model
    tokens = jnp.asarray(np.random.RandomState(1).randint(0, 512, size=(2, 96)), jnp.int32)
    at = jnp.tile(jnp.arange(96)[None], (2, 1))
    want = np.asarray(joyai.logits_at(params, tokens, at, CONFIG))
    s = dict(joyai.sizes(CONFIG), dtype=jnp.dtype("float32"), absorbed=True,
             bf16_sums=control == "bfloat16_sums", absorbed_scale=control == "absorbed_scale",
             no_shared_expert=control == "no_shared_expert")

    def run(rounded, s=s):
        with jax.default_matmul_precision("highest"):
            x = params["tok_embed"][tokens]
            for layer in params["layers"]:
                x = joyai.block(x, {k: rounded(k, a) for k, a in layer.items()}, s)
            x = joyai._rms(x, params["final_ln_g"], s)
            return np.asarray(x @ rounded("lm_head", params["lm_head"]))

    sound = run(lambda k, a: a, dict(s, bf16_sums=False, absorbed_scale=False, no_shared_expert=False))
    np.testing.assert_allclose(sound, want, atol=ATOL)
    got = run(joyai._int8 if control == "int8" else (lambda k, a: a))
    assert np.abs(got - want).max() > (0.02 if control in ("int8", "bfloat16_sums") else 0.1)


# ------------------------------------------------------------------ kernel
@pytest.mark.parametrize("window, max_blocks", [(1, 6), (1, 8), (2, 5), (3, 12)])
def test_the_latent_kernel_in_interpret_mode_is_the_xla_composition(window, max_blocks):
    """``paged_latent_attention`` (Pallas, interpreted) against
    ``reference_paged_latent_attention``: tables of 5-12 columns (folded in one grid step), an inactive row, a padding query."""
    b, h, rw, vw, bs, nb = 3, 4, 128, 32, 8, 40
    rs = np.random.RandomState(window * 10 + max_blocks)
    cache = jnp.asarray(rs.randn(2, nb, bs, rw), jnp.float32)
    q = jnp.asarray(rs.randn(b, window, h, rw), jnp.float32)
    tables = jnp.asarray(1 + rs.permutation(nb - 1)[: b * max_blocks].reshape(b, max_blocks), jnp.int32)
    last = np.asarray([[max_blocks * bs - 1], [17], [-1]])  # a full table, a short context, an inactive row
    positions = jnp.asarray(np.maximum(last - np.arange(window)[::-1][None], -1) * (last >= 0) - (last < 0), jnp.int32)
    assert da.latent_columns_per_step(max_blocks) == {6: 6, 8: 8, 5: 5, 12: 12}[max_blocks]
    got = da.paged_latent_attention(q, cache, 1, tables, positions, vw, 0.2, interpret=True)
    want = da.reference_paged_latent_attention(q, cache, 1, tables, positions, vw, 0.2)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)
    assert not np.asarray(got)[2].any()


def test_the_latent_call_has_a_pallas_name_of_its_own():
    q = jnp.zeros((2, 1, 4, 128), jnp.float32)
    cache = jnp.zeros((1, 9, 8, 128), jnp.float32)
    jaxpr = str(jax.make_jaxpr(lambda q, c, t, p: da.paged_latent_attention(q, c, 0, t, p, 32, 0.2, interpret=True))(
        q, cache, jnp.zeros((2, 4), jnp.int32), jnp.zeros((2, 1), jnp.int32)))
    assert "paged_latent_attention" in jaxpr and "paged_append_attention" not in jaxpr


def test_the_gate_sends_a_suffix_prefill_s_window_to_the_composition():
    assert da.latent_kernel_refusal(32, 640, 64, 2) is None  # the cell's decode call
    assert "query rows" in da.latent_kernel_refusal(2048 * 32, 640, 64, 2)
    cache = jax.ShapeDtypeStruct((20, 33, 64, 640), jnp.bfloat16)
    assert attention.latent_call_lowering(32, cache, backend="cpu", batch=48, max_blocks=48) == {"body": "reference", "group": 32}


@pytest.mark.parametrize("heads, dim", [(4, 192), (1, 576), (2, 160)])
def test_a_head_width_that_neither_packs_into_nor_fills_the_lanes_is_refused_readably(heads, dim):
    with pytest.raises(ValueError, match="neither packs into nor fills rows of 128 lanes"):
        da.cache_row_shape(heads, dim)
    with pytest.raises(ValueError, match="hold no K/V heads"):
        da.query_group(32, 192, (640,))
    assert da.latent_row_width(576) == 640 and da.latent_row_width(40) == 128 and da.cache_row_shape(4, 256) == (4, 256)
