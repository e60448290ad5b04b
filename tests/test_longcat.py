"""LongCat-Flash-Chat on the normal path, at rehearsal size on the CPU (PR
41): two latent-attention sub-layers and two dense SwiGLUs a published
layer, the routed experts on a shortcut around the second (their sum read
off the first sub-layer's feed-forward input and added behind the second's
feed-forward), a softmax router over real AND identity experts whose gates
are not renormalised, the two LoRA scales, and a latent prefill that
streams past the score bound: against the benchmark's plain float32
reference, logits not tokens."""
import dataclasses
import functools
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
from benchmark.reference import longcat_flash as longcat  # noqa: E402
from flexflow_tpu.generation import GenerationEngine, decoder  # noqa: E402
from flexflow_tpu.generation.cache import slot_mapping  # noqa: E402
from flexflow_tpu.generation.engine import SamplingParams  # noqa: E402
from flexflow_tpu.obs.capacity import ServingFlops  # noqa: E402
from flexflow_tpu.ops import attention, expert_product  # noqa: E402
from flexflow_tpu.ops.kernels import flash_attention  # noqa: E402

FILE = json.loads((ROOT / "benchmark/configs/longcat-flash-chat.json").read_text())
# hidden 64, 4 heads scoring at 16 + 8 and weighing 16, rows of 32 + 8; 2 published layers = 4 sub-layers, each with a
# dense SwiGLU of 96; 4 of 16 real experts held beside 8 identity experts, top-3 of the 24 outputs, gates x 6
CONFIG = spec._merge(FILE, FILE["rehearsal"])
# logits of a 4-sub-layer float32 model summed in another order (absorbed against expanded, one head and one expert at
# a time against all at once): errors of 1e-5; a missing scale, term or renormalisation moves them by tenths
ATOL = 2e-4


@pytest.fixture(scope="module")
def model():
    params = longcat.cast_params(longcat.init_params(5, CONFIG), jnp.float32)
    return longcat.engine_config(CONFIG, 128), params


def reference_logits(params, tokens, config=CONFIG):
    at = jnp.tile(jnp.arange(tokens.shape[1])[None], (tokens.shape[0], 1))
    return np.asarray(longcat.logits_at(params, jnp.asarray(tokens), at, config))


def test_the_rehearsal_preset_is_sub_layers_with_a_shortcut_branch_on_every_second(model):
    cfg, params = model
    assert cfg.num_layers == 4 and cfg.layer_types == ("latent",) * 4 and cfg.latent_layers == (0, 1, 2, 3)
    assert cfg.shortcut_experts == 2 and cfg.expert_layers == (0, 2) and [cfg.ffn_kind(l) for l in range(4)] == ["swiglu"] * 4
    assert (cfg.num_experts, cfg.zero_experts, cfg.router_outputs, cfg.experts_held, cfg.experts_per_token) == (16, 8, 24, (0, 1, 2, 3), 3)
    assert cfg.router == "softmax" and cfg.router_softmax_bias and not cfg.router_renormalise and cfg.routed_scaling_factor == 6
    assert cfg.latent_q_scale == pytest.approx((64 / 48) ** 0.5) and cfg.latent_kv_scale == pytest.approx(2 ** 0.5)
    assert cfg.expert_count_columns == 4 + 1 + 1 + 4  # held, nowhere, identity picks, tokens by 0..3 real experts
    first, second = params["layers"][0], params["layers"][1]
    assert first["router"].shape == (64, 24) and first["router_bias"].shape == (24,) and first["ew1"].shape == (4, 64, 24)
    assert first["w1"].shape == (64, 96) and "router" not in second and second["w2"].shape == (96, 64)
    # the program's own initialiser makes the same pytree
    own = decoder.init_decoder_params(jax.random.key(0), cfg)
    assert jax.tree.map(lambda a: a.shape, own) == jax.tree.map(lambda a: a.shape, params)
    # the real size: every published width, and the weights the file counts
    real = longcat.engine_config(FILE, 4608)
    assert (real.num_layers, real.hidden_size, real.num_heads, real.ff_size, real.moe_ff_size) == (8, 6144, 64, 12288, 2048)
    assert (real.router_outputs, real.experts_per_token, len(real.experts_held), real.vocab_size) == (768, 12, 16, 16384)
    assert (real.latent_q_scale, real.latent_kv_scale) == (2.0, pytest.approx(12 ** 0.5)) and real.rope_theta == 1e7
    shapes = jax.eval_shape(lambda k: decoder.init_decoder_params(k, real), jax.random.key(0))
    assert 10.3e9 < sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(shapes)) < 10.4e9


def test_a_configuration_that_cannot_carry_the_branch_is_refused():
    base = dict(num_layers=4, hidden_size=64, num_heads=4, ff_size=96, seq_length=64, vocab_size=128, causal=True)
    with pytest.raises(ValueError, match="shortcut expert branch"):
        decoder.DecoderConfig(**base, shortcut_experts=2, block="parallel")
    with pytest.raises(ValueError, match="shortcut expert branch"):
        decoder.DecoderConfig(**base, shortcut_experts=3)  # no whole periods
    with pytest.raises(ValueError, match="shortcut expert branch"):
        decoder.DecoderConfig(**base, shortcut_experts=2, num_dense_layers=1)  # beside DENSE feed-forwards
    with pytest.raises(ValueError, match="softmax router's"):
        decoder.DecoderConfig(**base, router_softmax_bias=True)


# ---------------------------------------------------------------- forwards
def test_forward_full_is_the_reference(model):
    cfg, params = model
    tokens = np.random.RandomState(1).randint(0, 512, size=(2, 96)).astype(np.int32)
    with jax.default_matmul_precision("highest"):
        got = np.asarray(decoder.forward_full(params, jnp.asarray(tokens), cfg=cfg))
    np.testing.assert_allclose(got, reference_logits(params, tokens), atol=ATOL)


def test_padded_prefill_is_the_unpadded_forward_and_hands_over_one_row_a_sub_layer(model):
    cfg, params = model
    tokens = np.random.RandomState(2).randint(0, 512, size=(1, 64)).astype(np.int32)
    counts = []
    with jax.default_matmul_precision("highest"):
        logits, rows, vs = decoder.prefill(params, jnp.asarray(tokens), jnp.asarray([40]), cfg=cfg, counts=counts)
    assert rows.shape == (4, 1, 64, 128) and vs.shape == (4, 1, 64, 0)  # 40 values at 128 lanes; V has no width
    np.testing.assert_allclose(np.asarray(logits)[:, :40], reference_logits(params, tokens[:, :40]), atol=ATOL)
    # one counter row a routed branch, over the 40 live tokens alone
    assert len(counts) == 2 and all(int(jnp.sum(c[-4:])) == 40 for c in counts)


@pytest.mark.parametrize("prompt_len", [8, 24, 40, 64])
def test_prefill_then_decode_through_the_latent_cache_is_the_reference_s_full_forward(model, prompt_len):
    """The served path: an expanded prefill writes the rows, then 40 greedy
    steps run the absorbed form over the paged latent cache, the branch's
    sum carried across the second sub-layer in every one: every step's
    choice is the argmax of the reference's full forward over the same
    prefix, to 1e-3 of a logit."""
    cfg, params = model
    eng = GenerationEngine(params, cfg, max_batch_slots=2, block_size=8, prompt_buckets=[32, 64], max_seq_len=128)
    prompt = [int(t) for t in np.random.RandomState(prompt_len).randint(0, 512, size=prompt_len)]
    with jax.default_matmul_precision("highest"):
        out = eng.generate([prompt], SamplingParams(max_new_tokens=40))[0]
    logits = reference_logits(params, np.asarray([prompt + out], np.int32))[0, prompt_len - 1 : -1]
    gap = logits.max(-1) - logits[np.arange(len(out)), out]
    assert len(out) == 40 and float(gap.max()) < 1e-3
    stats = eng.latent_stats()
    assert stats["layers"] == 4 and stats["expanded_calls_total"] == 4 and stats["absorbed_calls_total"] == 4 * eng.step_counts["decode"]


def test_verify_step_agrees_with_the_reference_over_cached_rows(model):
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


def test_the_cached_row_carries_the_kv_scale_and_the_queries_theirs(model):
    """``[c, k_r]`` as the reference states it: ``c`` normed THEN scaled by
    sqrt(hidden / kv_lora_rank), ``k_r`` rotated and not scaled; with both
    scales at 1 the row and the queries are the unscaled layer's."""
    cfg, params = model
    sub = params["layers"][1]
    h = jnp.asarray(np.random.RandomState(4).randn(2, 24, 64), jnp.float32)
    positions = jnp.arange(24)[None]
    with jax.default_matmul_precision("highest"):
        q, row = decoder._latent_qkv(cfg, sub, h, positions)
        plain = dataclasses.replace(cfg, latent_q_scale=1.0, latent_kv_scale=1.0)
        q1, row1 = decoder._latent_qkv(plain, sub, h, positions)
    np.testing.assert_allclose(np.asarray(row[..., :40]), np.asarray(longcat.cache_rows(h, sub, CONFIG)), atol=1e-5)
    np.testing.assert_allclose(np.asarray(row[..., :32]), 2 ** 0.5 * np.asarray(row1[..., :32]), rtol=1e-6)
    np.testing.assert_array_equal(np.asarray(row[..., 32:]), np.asarray(row1[..., 32:]))  # k_r and the fill
    np.testing.assert_allclose(np.asarray(q), (64 / 48) ** 0.5 * np.asarray(q1), rtol=1e-5, atol=1e-6)


# ----------------------------------------------------- the latent prefill
def test_the_streamed_latent_prefill_is_masked_attention(monkeypatch):
    """Score width 24 beside value width 16, a padded sequence: the form a
    call past the bound takes (on the CPU the XLA scan over query chunks,
    which is also what the chip's gate sends a latent call to) against the
    materialised one, at a size both take."""
    rs = np.random.RandomState(5)
    q, k, v = (jnp.asarray(rs.randn(2, 64, 4, d), jnp.float32) for d in (24, 24, 16))
    lens = jnp.asarray([64, 37])
    want = attention.masked_attention(q, k, v, lens)
    assert attention.prefill_call_lowering(q.shape, k.shape, 4, "cpu")["form"] == "materialised"
    monkeypatch.setattr(attention, "STREAM_SCORE_BYTES", 1 << 10)
    low = attention.prefill_call_lowering(q.shape, k.shape, 4, "cpu")
    assert low == {"form": "streamed", "kernel": "xla_chunks", "refused": None}
    got = attention.prefill_attention(q, k, v, lens)
    assert got.shape == (2, 64, 4, 16)
    np.testing.assert_allclose(np.asarray(got)[1, :37], np.asarray(want)[1, :37], atol=1e-5)
    np.testing.assert_allclose(np.asarray(got)[0], np.asarray(want)[0], atol=1e-5)
    chunked = flash_attention.reference_prefill_stream_attention(q, k, v, lens, chunk=16)
    np.testing.assert_allclose(np.asarray(chunked)[0], np.asarray(want)[0], atol=1e-5)


def test_the_expanded_form_streams_past_the_bound_and_only_there(model, monkeypatch):
    """``_expanded`` goes through ``prefill_attention``: under the bound
    the program is ``masked_attention``'s letter for letter, past it no
    ``[H, S, S]`` value exists in the program at all."""
    cfg, params = model
    sub = params["layers"][0]
    h = jnp.asarray(np.random.RandomState(6).randn(1, 64, 64), jnp.float32)
    lens = jnp.asarray([50])
    q, rows = decoder._latent_qkv(cfg, sub, h, jnp.arange(64)[None])

    def text():
        return str(jax.make_jaxpr(lambda q, r: decoder._expanded(cfg, q, r, sub["w_ukv"], lens))(q, rows))

    under = text()
    want = decoder._expanded(cfg, q, rows, sub["w_ukv"], lens)
    assert "f32[1,4,64,64]" in under and "scan" not in under
    monkeypatch.setattr(attention, "STREAM_SCORE_BYTES", 1 << 10)
    past = text()
    assert "f32[1,4,64,64]" not in past and "scan" in past
    got = decoder._expanded(cfg, q, rows, sub["w_ukv"], lens)
    np.testing.assert_allclose(np.asarray(got)[0, :50], np.asarray(want)[0, :50], atol=1e-5)


LATENT_CALLS = [
    # cell, bucket, heads, the lowering on the CPU backend, on a TPU
    ("long-gen", 1536, 32, "materialised", "materialised"),
    ("long-gen", 2048, 32, "materialised", "materialised"),
    ("agent-turns", 2048, 64, "materialised", "materialised"),  # exactly 1 GiB of scores: not past the bound
    ("agent-turns", 3072, 64, "xla_chunks", "prefill_stream_attention"),
    ("agent-turns", 4096, 64, "xla_chunks", "prefill_stream_attention"),
]


@pytest.mark.parametrize("cell,bucket,heads,on_cpu,on_chip", LATENT_CALLS)
def test_the_cells_latent_calls_by_what_their_shapes_show(cell, bucket, heads, on_cpu, on_chip, monkeypatch):
    """``long-gen`` (32 heads x 2,048) keeps materialised scores; this
    model's buckets (64 heads past 2,048) stream: on a TPU through the
    Pallas call (score width 192, value width 128, one query head a K/V
    head: PR 42), on the CPU backend through the XLA chunks, never back
    to materialised scores."""
    shapes = ((1, bucket, heads, 192), (1, bucket, heads, 192))
    low = functools.partial(attention.prefill_call_lowering, *shapes, 2, v_shape=(1, bucket, heads, 128))
    named = {"materialised": {"form": "materialised", "kernel": "masked_attention", "refused": None},
             "xla_chunks": {"form": "streamed", "kernel": "xla_chunks", "refused": None},
             "prefill_stream_attention": {"form": "streamed", "kernel": "prefill_stream_attention", "refused": None}}
    assert low(backend="cpu") == named[on_cpu]
    monkeypatch.setattr(attention, "on_tpu", lambda: True)
    assert low(backend="tpu") == named[on_chip] and low(backend="cpu") == named[on_cpu]
    assert flash_attention.prefill_stream_refusal(*shapes, 2, (1, bucket, heads, 128)) is None


def test_the_engine_names_the_kernel_for_the_cells_buckets_on_a_tpu(model, monkeypatch):
    """The engine asks the rule with the value's width beside the score's
    (``prefill_lowering`` reads the configuration's widths and the
    backend): holding the cell's real widths on a TPU backend, the
    ``programs`` of ``/v2/stats`` name the Pallas call for both buckets
    past the bound and count no refusal."""
    cfg, params = model
    eng = GenerationEngine(params, cfg, max_batch_slots=2, block_size=8, prompt_buckets=[32], max_seq_len=64)
    assert eng.prefill_lowering(32)["kernel"] == "masked_attention"
    monkeypatch.setattr(attention, "on_tpu", lambda: True)
    eng.backend, eng.dcfg, eng.buckets = "tpu", longcat.engine_config(FILE, 4608), [2048, 3072, 4096]
    eng._prefill_lowerings.clear()
    stats = eng.prefill_attention_stats()
    assert stats["programs"]["prefill[2048]"]["form"] == "materialised"
    for bucket in (3072, 4096):
        assert stats["programs"][f"prefill[{bucket}]"] == {"form": "streamed", "kernel": "prefill_stream_attention", "refused": None}
    eng._count_prefill_attention(4096, 3000)
    assert eng.prefill_attention_stats()["streamed_calls_total"] == 8 and not eng.prefill_attention_stats()["refused_total"]


def test_the_engine_counts_latent_prefill_calls_by_form_and_keeps_no_prefix_index_past_the_bound(model, monkeypatch):
    cfg, params = model
    eng = GenerationEngine(params, cfg, max_batch_slots=2, block_size=8, prompt_buckets=[32], max_seq_len=64)
    assert eng.prefix_cache.enabled and "prefix_reuse" not in eng.unsupported
    eng.generate([[1, 2, 3, 4, 5]], SamplingParams(max_new_tokens=2))
    stats = eng.prefill_attention_stats()
    assert stats["calls_total"] == 4 and stats["materialised_calls_total"] == 4 and stats["streamed_calls_total"] == 0
    assert stats["programs"]["prefill[32]"]["kernel"] == "masked_attention"
    assert eng.kernel_stats() == {"latent": {"body": "reference", "group": 4}}
    # past the bound: the calls stream, and a hit's suffix prefill (which has no streamed form) is never taken
    from flexflow_tpu.generation import engine as engine_module
    monkeypatch.setattr(attention, "STREAM_SCORE_BYTES", 1 << 10)
    monkeypatch.setattr(engine_module, "STREAM_SCORE_BYTES", 1 << 10)
    eng = GenerationEngine(params, cfg, max_batch_slots=2, block_size=8, prompt_buckets=[32], max_seq_len=64)
    assert not eng.prefix_cache.enabled and "suffix prefill" in eng.unsupported["prefix_reuse"]
    eng.generate([[1, 2, 3, 4, 5]], SamplingParams(max_new_tokens=2))
    stats = eng.prefill_attention_stats()
    assert stats["streamed_calls_total"] == 4 and stats["materialised_calls_total"] == 0
    assert stats["programs"]["prefill[32]"] == {"form": "streamed", "kernel": "xla_chunks", "refused": None}


# ------------------------------------------------------------- the router
def test_the_bias_moves_the_choice_and_never_the_gate_and_the_gates_are_not_renormalised(model):
    cfg, params = model
    sub = params["layers"][0]
    v = jnp.asarray(np.random.RandomState(7).randn(50, 64), jnp.float32)
    p = np.asarray(jax.nn.softmax(jnp.dot(v, sub["router"], precision="highest"), axis=-1))
    gates, chosen = decoder.route(cfg, sub, v)
    gates, chosen = np.asarray(gates), np.asarray(chosen)
    assert gates.shape == (50, 24) and chosen.shape == (50, 3)
    np.testing.assert_array_equal(np.sort(chosen, 1), np.sort(np.argsort(-(p + np.asarray(sub["router_bias"])), 1)[:, :3], 1))
    picked = np.take_along_axis(p, chosen, 1)
    np.testing.assert_allclose(np.take_along_axis(gates, chosen, 1), 6 * picked, rtol=1e-6)  # 6 p_j: the bias is not in it
    np.testing.assert_allclose(gates.sum(1), 6 * picked.sum(1), rtol=1e-5)
    assert np.all(gates.sum(1) < 5.0)  # ... and not 6: three picks of 24 hold a fraction of the mass
    # a bias large enough moves every choice to the outputs it favours, and still no gate
    moved = dict(sub, router_bias=jnp.zeros(24).at[jnp.asarray([5, 17, 23])].set(10.0))
    gates2, chosen2 = decoder.route(cfg, moved, v)
    assert set(np.asarray(chosen2).ravel()) == {5, 17, 23}
    np.testing.assert_allclose(np.asarray(gates2)[:, [5, 17, 23]], 6 * p[:, [5, 17, 23]], rtol=1e-6)
    renormalised = decoder.route(dataclasses.replace(cfg, router_renormalise=True), sub, v)[0]
    np.testing.assert_allclose(np.asarray(renormalised).sum(1), 6.0, rtol=1e-5)
    # the reference's routing is the same gates
    s = dict(longcat.sizes(CONFIG), dtype=jnp.dtype("float32"))
    np.testing.assert_allclose(np.asarray(longcat.routing(v, sub, s)), gates, atol=1e-6)


# ------------------------------------------------- the routed sum's forms
def interpreted():
    return functools.partial(expert_product.grouped_matmul, interpret=True)


@pytest.mark.parametrize("case", ["all-rows", "padding-rows", "a-row-that-picks-none-of-the-held", "the-whole-buffer-form", "bfloat16"])
def test_dense_and_grouped_forms_agree_with_identity_picks(model, case):
    """One sum, its lowerings: the dense product, the grouped tile loop
    (this shape's: fewer than one row in three lands a pair) and the
    whole-buffer grouped form, with identity picks in every row's mix."""
    cfg, params = model
    dtype = jnp.bfloat16 if case == "bfloat16" else jnp.float32
    sub = {k: a if k.startswith("router") else a.astype(dtype) for k, a in params["layers"][0].items()}
    # TWO of the 16 real experts held: 3 x 2 / 24 of a pair a row lands, a quarter, as at the real size (12 x 16 / 768)
    held = (0, 1)
    sub = dict(sub, **{k: sub[k][:2] for k in ("ew1", "ew3", "ew2")})
    cfg = dataclasses.replace(cfg, experts_held=held)
    v = jnp.asarray(np.random.RandomState(8).randn(1200, 64), dtype)
    if case == "a-row-that-picks-none-of-the-held":
        # a bias that sends every pick to absent real experts (2-15) or identity ones (16-23)
        sub = dict(sub, router_bias=jnp.zeros(24).at[:2].set(-10.0))
    gates, chosen = decoder.route(cfg, sub, v)
    assert bool(jnp.any(chosen >= 16)) and (case != "a-row-that-picks-none-of-the-held" or not bool(jnp.any(chosen < 2)))
    live = jnp.arange(1200) < 811 if case == "padding-rows" else None
    want, want_gates = decoder.expert_ffn(cfg, sub, v, held=cfg.experts_held, live=live)
    identity = jnp.sum(gates[:, 16:], axis=1)
    assert expert_product.sparse(2, 3, 24)  # the loop; over two passes of it, or (no pair landed) none
    assert int(jnp.sum(chosen < 2)) == 0 if "none" in case else int(jnp.sum(chosen[:811] < 2)) > 128
    loop = expert_product._LOOP_PAIRS
    try:
        expert_product._LOOP_PAIRS = 128
        if case == "the-whole-buffer-form":  # what a router with more landing pairs takes: same sum
            expert_product.sparse, keep = (lambda *shape: False), expert_product.sparse
        got = expert_product.grouped_expert_sum(v, gates, chosen, sub["ew1"], sub["ew3"], sub["ew2"], held=cfg.experts_held,
                                                live=live, product=interpreted(), identity=identity)
    finally:
        expert_product._LOOP_PAIRS = loop
        if case == "the-whole-buffer-form":
            expert_product.sparse = keep
    np.testing.assert_array_equal(np.asarray(want_gates), np.asarray(gates))
    f32 = lambda a: np.asarray(a.astype(jnp.float32))  # noqa: E731
    rows = slice(0, 811) if case == "padding-rows" else slice(None)
    np.testing.assert_allclose(f32(got)[rows], f32(want)[rows], atol=1e-5, rtol=2.0 ** -7 if case == "bfloat16" else 0)
    # the identity term is in both: without it the sum is another
    bare = expert_product.grouped_expert_sum(v, gates, chosen, sub["ew1"], sub["ew3"], sub["ew2"], held=cfg.experts_held,
                                             live=live, product=interpreted())
    assert float(jnp.abs(f32(bare)[rows] - f32(want)[rows]).max()) > 0.05
    np.testing.assert_allclose(f32(bare)[rows] + f32(identity[:, None] * v.astype(jnp.float32))[rows], f32(want)[rows],
                               atol=1e-5 if case != "bfloat16" else 0.1)


ACCEPTED = {  # (held, k, outputs) and every row count of the four accepted expert cells: buckets and the decode step
    "lfm2-8b-a1b.gen-batch": ((32, 4, 32), {64: "dense", 128: "dense", 256: "dense", 512: "dense"}),
    "mellum2-12b.code-gen": ((64, 8, 64), {48: "dense", 1024: "grouped", 1536: "grouped", 2048: "grouped"}),
    "joyai-llm-flash.long-gen": ((16, 8, 256), {32: "dense", 1536: "dense", 2048: "dense"}),
    "command-a-plus.long-doc": ((16, 8, 128), {16: "dense", 5120: "grouped", 6144: "grouped"}),
}


@pytest.mark.parametrize("cell", sorted(ACCEPTED))
def test_the_rule_answers_for_the_accepted_cells_as_it_did(cell):
    """``expert_form`` asks the router's outputs now; for every program of
    the four accepted expert cells it answers what it answered when it
    asked ``held >= 2 k`` (the parent's rule, written out), none of them is
    ``sparse`` (so none takes the tile loop), and the cell's own deployment
    file has no row count this list lacks."""
    (held, k, outputs), forms = ACCEPTED[cell]
    deployment = json.loads((ROOT / f"benchmark/workloads/{cell}.json").read_text())["deployment"]
    assert {deployment["slots"], *deployment["prompt_buckets"]} <= set(forms)
    for rows, form in forms.items():
        parent = "grouped" if rows >= 1024 and held >= 2 * k and rows * held >= 8192 * k else "dense"
        assert expert_product.expert_form(rows, held, k, outputs) == parent == form
        assert expert_product.expert_form(rows, held, k) == parent  # (as the callers that name no outputs asked)
        assert not expert_product.sparse(held, k, outputs)
        assert 4 * rows * k * {"command-a-plus.long-doc": 4096}.get(cell, 2304) <= expert_product._PAIR_BYTES


def test_the_rule_takes_the_tile_loop_for_this_share_at_every_row_count():
    """16 held of 768 outputs at k 12: a quarter of the ROWS land a pair,
    which ``held >= 2 k`` (16 < 24) read as "nothing to skip". The tile
    loop costs what landed and reads the experts some row picked: ahead of
    the dense form from a decode step's rows on (the table in
    ``expert_form``)."""
    form = functools.partial(expert_product.expert_form, held=16, k=12, outputs=768)
    assert [form(rows) for rows in (1, 16, 24, 32, 64, 512, 1024, 3072, 4096)] == ["grouped"] * 9
    assert expert_product.sparse(16, 12, 768) and not expert_product.sparse(16, 8, 256)
    assert expert_product.expert_form(4096, 16, 12) == "dense"  # without the outputs: the parent's answer


# ------------------------------------------------------------- the shares
def test_the_shares_and_the_identity_term_once_add_up_to_the_uncut_layer():
    """16 real experts over 4 chips: the 4 shares' held sums plus the
    identity experts' term counted ONCE equal the routed branch with every
    expert held. From the reference and from the program."""
    whole = spec._merge(CONFIG, {"n_routed_experts": 16, "expert_share": {"chips": 1, "chip": 0}})
    params = longcat.cast_params(longcat.init_params(7, whole), jnp.float32)
    sub = params["layers"][2]
    u = jnp.asarray(np.random.RandomState(8).randn(1, 20, 64), jnp.float32)
    uncut = longcat.shortcut_parts(u, sub, whole, range(16))
    uncut = np.asarray(uncut["routed"] + uncut["zero"])
    zero = np.asarray(longcat.shortcut_parts(u, sub, whole, range(16))["zero"])
    assert np.abs(zero).max() > 0.05
    total_ref, total_prog = zero.copy(), -3 * zero[0]  # each share of the program computes the term: once, not four times
    cfg = longcat.engine_config(whole, 128)
    for chip in range(4):
        held = tuple(range(4 * chip, 4 * chip + 4))
        part = dict(sub, **{k: sub[k][jnp.asarray(held)] for k in ("ew1", "ew3", "ew2")})
        total_ref = total_ref + np.asarray(longcat.shortcut_parts(u, part, whole, held)["routed"])
        with jax.default_matmul_precision("highest"):
            routed, _ = decoder.expert_ffn(cfg, part, u[0], held=held)
        total_prog = total_prog + np.asarray(routed)
    np.testing.assert_allclose(total_ref, uncut, atol=1e-5)
    np.testing.assert_allclose(total_prog, uncut[0], atol=1e-4)


def test_the_counters_count_held_experts_identity_picks_and_real_experts_a_token(model):
    cfg, params = model
    eng = GenerationEngine(params, cfg, max_batch_slots=2, block_size=8, prompt_buckets=[32], max_seq_len=64)
    prompts = [[int(t) for t in np.random.RandomState(s).randint(0, 512, size=n)] for s, n in ((1, 20), (2, 28))]
    with jax.default_matmul_precision("highest"):
        outs = eng.generate(prompts, SamplingParams(max_new_tokens=6))
    stats = eng.expert_stats()
    assert stats["layers"] == [0, 2] and stats["held"] == [0, 1, 2, 3] and stats["experts"] == 16 and stats["zero_experts"] == 8
    # every token but a stream's last is run once, prompt or decode: the reference's count over the same sequences
    picks, real = longcat.router_picks(params, CONFIG, [p + o[:-1] for p, o in zip(prompts, outs)])
    assert stats["tokens_total_by_layer"] == [row[:4] for row in picks]
    assert stats["zero_picks_total_by_layer"] == [sum(row[16:]) for row in picks]
    assert stats["real_experts_per_token_total"] == [sum(col) for col in zip(*real)]
    tokens = sum(len(p) + len(o) - 1 for p, o in zip(prompts, outs))
    assert sum(stats["real_experts_per_token_total"]) == 2 * tokens  # once a token a routed branch
    assert stats["zero_pick_share"] == pytest.approx(stats["zero_picks_total"] / (3 * 2 * tokens)) and 0.1 < stats["zero_pick_share"] < 0.6
    assert 0 < stats["unrouted_here_total"] <= 2 * tokens


def test_speculation_the_wire_and_tensor_parallelism_are_refused_by_name(model):
    cfg, params = model
    eng = GenerationEngine(params, cfg, max_batch_slots=2, block_size=8, prompt_buckets=[32], max_seq_len=64)
    assert {"speculation", "kv_handoff", "tensor_parallel"} <= set(eng.unsupported)
    assert all("shortcut expert branch" in eng.unsupported[p] for p in ("speculation", "kv_handoff", "tensor_parallel"))
    with pytest.raises(NotImplementedError, match="shortcut expert branch"):
        GenerationEngine(params, cfg, max_batch_slots=2, block_size=8, prompt_buckets=[32], max_seq_len=64, tp_degree=2)


def test_serving_flops_counts_the_block():
    """Two attentions and two dense SwiGLUs a layer, the router over 768
    outputs, the picks that land on a held expert (12 x 16 / 768 a token)
    and the identity picks (12 x 256 / 768) at 2 E flops each."""
    real = longcat.engine_config(FILE, 4608)
    flops = ServingFlops.from_config(real, dtype=real.dtype)
    e = 6144
    attention_ = e * 1536 + 1536 * 64 * 192 + e * 576 + 512 * 64 * 256 + 64 * 128 * e
    branch = int(0.25 * 3 * e * 2048 + 4 * e) + e * 768
    assert flops.per_token_flops == 2 * (e * 16384 + 8 * (attention_ + 3 * e * 12288) + 4 * branch)
    assert flops.param_count == 2 * 16384 * e + 8 * (attention_ + 3 * e * 12288) + 4 * (16 * 3 * e * 2048 + e * 768)
    assert 10.3e9 < flops.param_bytes < 10.4e9 and flops.kv_bytes_per_pos == 8 * 640 * 2


# ----------------------------------------------------------- the controls
@pytest.mark.parametrize("control", longcat.CONTROLS)
def test_each_control_is_another_model(model, control):
    """What the cell's controls compute, in the absorbed form and float32
    so that nothing but the control differs from the reference, lies
    tenths of a logit away at rehearsal size: none is a rounding. The
    stated form itself (no control) lies within the summation order."""
    _, params = model
    tokens = jnp.asarray(np.random.RandomState(1).randint(0, 512, size=(2, 96)), jnp.int32)
    at = jnp.tile(jnp.arange(96)[None], (2, 1))
    want = np.asarray(longcat.logits_at(params, tokens, at, CONFIG))
    sound = dict(longcat.sizes(CONFIG), dtype=jnp.dtype("float32"), absorbed=True)
    s = dict(sound, bf16_sums=control == "bfloat16_sums", no_zero_experts=control == "no_zero_experts",
             renormalised_gates=control == "renormalised_gates")

    def run(rounded, s):
        with jax.default_matmul_precision("highest"):
            x = params["tok_embed"][tokens]
            subs = [{k: rounded(k, a) for k, a in sub.items()} for sub in params["layers"]]
            for first, second in zip(subs[0::2], subs[1::2]):
                x = longcat.block(x, first, second, s)
            x = longcat._rms(x, params["final_ln_g"], s)
            return np.asarray(x @ rounded("lm_head", params["lm_head"]))

    np.testing.assert_allclose(run(lambda k, a: a, sound), want, atol=ATOL)
    got = run(longcat._int8 if control == "int8" else (lambda k, a: a), s)
    assert np.abs(got - want).max() > (0.02 if control in ("int8", "bfloat16_sums") else 0.1)


def test_computing_in_bfloat16_where_the_configuration_says_float32_fails_the_tolerance(model):
    """The rehearsal configuration states float32: the program on bfloat16
    weights and activations lies hundredths of a logit from the reference,
    a hundred times the tolerance the float32 program passes."""
    cfg, params = model
    tokens = np.random.RandomState(1).randint(0, 512, size=(2, 96)).astype(np.int32)
    coarse_cfg = dataclasses.replace(cfg, dtype=decoder.DataType.BFLOAT16)
    coarse = jax.tree.map(lambda a: a.astype(jnp.bfloat16), params)
    for sub, was in zip(coarse["layers"], params["layers"]):
        sub.update({k: a for k, a in was.items() if k.startswith("router")})  # (the router is float32 in every arithmetic)
    got = np.asarray(decoder.forward_full(coarse, jnp.asarray(tokens), cfg=coarse_cfg)).astype(np.float32)
    assert np.abs(got - reference_logits(params, tokens)).max() > 50 * ATOL
