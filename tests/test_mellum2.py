"""Mellum2-12B-A2.5B on the normal path, at rehearsal size on the CPU
(PR 31): the block's new choices (window layers beside full ones, YaRN
by attention kind, a softmax router) against the benchmark's plain
float32 reference, logits not tokens; the four forwards agree across
the window; the paged kernel with a window in interpret mode; the
cell's controls fail the cell's limits."""
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
from flexflow_tpu.generation import GenerationEngine, decoder  # noqa: E402
from flexflow_tpu.generation.cache import slot_mapping  # noqa: E402
from flexflow_tpu.generation.engine import SamplingParams  # noqa: E402
from flexflow_tpu.ops.kernels import decode_attention as da  # noqa: E402

FILE = json.loads((ROOT / "benchmark/configs/mellum2-12b.json").read_text())
CONFIG = spec._merge(FILE, FILE["rehearsal"])  # hidden 64, 4 / 2 heads of 16, 8 experts top-2, S S S F S S S F, window 16
WINDOW = 16


@pytest.fixture(scope="module")
def model():
    params = mellum2.cast_params(mellum2.init_params(5, CONFIG), jnp.float32)
    return CONFIG, mellum2.engine_config(CONFIG, 128), params


def reference_logits(params, tokens):
    at = jnp.tile(jnp.arange(tokens.shape[1])[None], (tokens.shape[0], 1))
    return np.asarray(mellum2.logits_at(params, jnp.asarray(tokens), at, CONFIG))


def test_the_rehearsal_preset_is_the_one_the_issue_named(model):
    _, cfg, _ = model
    assert cfg.layer_types == ("window", "window", "window", "attention") * 2 and cfg.window == WINDOW
    assert (cfg.hidden_size, cfg.num_heads, cfg.kv_heads, cfg.dim_per_head) == (64, 4, 2, 16)
    assert (cfg.num_experts, cfg.experts_per_token, cfg.router) == (8, 2, "softmax")
    assert cfg.rope_parameters["attention"]["original_max_position_embeddings"] == 32  # contexts of 96 cross both
    assert "factor" not in cfg.rope_parameters["window"] and cfg.kv_index[3] == ("attention", 0)
    assert cfg.window_layers == (0, 1, 2, 4, 5, 6) and cfg.full_layers == (3, 7)


def test_forward_full_is_the_reference_on_both_sides_of_the_window(model):
    _, cfg, params = model
    tokens = np.random.RandomState(0).randint(0, 512, size=(2, 96)).astype(np.int32)  # 6 windows, 3 original contexts
    with jax.default_matmul_precision("highest"):
        got = np.asarray(decoder.forward_full(params, jnp.asarray(tokens), cfg=cfg))
    np.testing.assert_allclose(got, reference_logits(params, tokens), atol=2e-4)


@pytest.mark.parametrize("control", mellum2.CONTROLS)
def test_each_control_is_another_model(model, control):
    """What the cell's controls compute differs from the reference by
    tenths of a logit at rehearsal size: none is a rounding."""
    _, _, params = model
    tokens = jnp.asarray(np.random.RandomState(1).randint(0, 512, size=(2, 96)), jnp.int32)
    at = jnp.tile(jnp.arange(96)[None], (2, 1))
    want = np.asarray(mellum2.logits_at(params, tokens, at, CONFIG))
    got = np.asarray(mellum2.logits_at(params, tokens, at, CONFIG, "float32", control))
    assert np.abs(got - want).max() > 0.1


def test_padded_prefill_is_the_unpadded_forward(model):
    _, cfg, params = model
    tokens = np.random.RandomState(2).randint(0, 512, size=(1, 64)).astype(np.int32)
    with jax.default_matmul_precision("highest"):
        logits, ks, vs = decoder.prefill(params, jnp.asarray(tokens), jnp.asarray([40]), cfg=cfg)
    assert ks.shape == (8, 1, 64, 2, 16)  # both kinds, in layer order
    np.testing.assert_allclose(np.asarray(logits)[:, :40], reference_logits(params, tokens[:, :40]), atol=2e-4)


@pytest.mark.parametrize("prompt_len", [8, 16, 24, 64])  # 0.5, 1, 1.5 and 4 windows
def test_prefill_then_decode_through_the_cache_is_the_reference_s_full_forward(model, prompt_len):
    """The served path: a prompt of 0.5 to 4 windows prefilled into both
    pools, then 40 greedy steps through the window kernel's reference
    lowering and the released tables: every step's choice is the
    argmax of the reference's full forward over the same prefix."""
    _, cfg, params = model
    eng = GenerationEngine(params, cfg, max_batch_slots=2, block_size=8, prompt_buckets=[32, 64], max_seq_len=128)
    prompt = [int(t) for t in np.random.RandomState(prompt_len).randint(0, 512, size=prompt_len)]
    with jax.default_matmul_precision("highest"):
        out = eng.generate([prompt], SamplingParams(max_new_tokens=40))[0]
    seq = np.asarray([prompt + out], np.int32)
    logits = reference_logits(params, seq)[0, prompt_len - 1 : -1]
    gap = logits.max(-1) - logits[np.arange(len(out)), out]
    assert len(out) == 40 and float(gap.max()) < 1e-3
    assert eng.window_held_peak <= -(-WINDOW // 8) + 1


def test_verify_step_agrees_with_decode_steps_across_the_window(model):
    """A 5-token append window at positions around the window's edge
    scores what 5 decode steps score: both through the two pools."""
    _, cfg, params = model
    bs, n = 8, 40
    rs = np.random.RandomState(3)
    tokens = rs.randint(0, 512, size=(1, n + 5)).astype(np.int32)
    shape = lambda layers: (layers, 16, bs, 2, 16)  # noqa: E731
    zeros = lambda layers: jnp.zeros(shape(layers), jnp.float32)  # noqa: E731
    table = jnp.arange(1, 9, dtype=jnp.int32)[None]  # blocks 1..8: every position held, first 0
    first = jnp.zeros((1,), jnp.int32)
    with jax.default_matmul_precision("highest"):
        _, ks, vs = decoder.prefill(params, jnp.asarray(tokens[:, :n]), cfg=cfg)
        pos = jnp.arange(n)
        block, offset = slot_mapping(table[0], pos, bs)
        k, v, wk, wv = zeros(2), zeros(2), zeros(6), zeros(6)
        for ai, (kind, at) in enumerate(cfg.kv_index):
            if kind == "window":
                wk, wv = decoder.write_rows(wk, at, block, offset, ks[ai, 0]), decoder.write_rows(wv, at, block, offset, vs[ai, 0])
            else:
                k, v = decoder.write_rows(k, at, block, offset, ks[ai, 0]), decoder.write_rows(v, at, block, offset, vs[ai, 0])
        window = {"k": wk, "v": wv, "tables": table, "first": first}
        positions = (n + jnp.arange(5))[None]
        got = decoder.verify_step(params, jnp.asarray(tokens[:, n:]), positions, k, v, table, cfg=cfg, window=window)[0]
        steps = []
        for j in range(5):
            logits, k, v, w = decoder.decode_step(
                params, jnp.asarray(tokens[:, n + j]), jnp.asarray([n + j]), k, v, table, jnp.asarray([n + j + 1]),
                cfg=cfg, window=window,
            )
            window = dict(window, k=w["k"], v=w["v"])
            steps.append(np.asarray(logits)[0])
    np.testing.assert_allclose(np.asarray(got)[0], np.stack(steps), atol=2e-4)
    np.testing.assert_allclose(np.stack(steps), reference_logits(params, tokens)[0, n:], atol=2e-4)


def test_expert_shares_add_up_to_the_whole_layer_under_the_softmax_router(model):
    _, cfg, params = model
    layer = params["layers"][0]
    rows = jnp.asarray(np.random.RandomState(5).standard_normal((24, 64)), jnp.float32)
    whole, gates = decoder.expert_ffn(cfg, layer, rows)
    parts = []
    for share in ([0, 1], [2, 3], [4, 5], [6, 7]):
        held = dict(layer, **{k: layer[k][jnp.asarray(share)] for k in ("ew1", "ew3", "ew2")})
        parts.append(decoder.expert_ffn(cfg, held, rows, held=share)[0])
    np.testing.assert_allclose(np.asarray(sum(parts)), np.asarray(whole), atol=1e-5)
    s = dict(mellum2.sizes(CONFIG), dtype=jnp.dtype("float32"))
    with jax.default_matmul_precision("highest"):
        np.testing.assert_allclose(np.asarray(whole), np.asarray(mellum2._experts(rows, layer, s)), atol=1e-5)
    np.testing.assert_allclose(np.asarray(gates.sum(-1)), 1.0, atol=1e-6)  # renormalised, no 1e-6, no bias
    assert int((gates > 0).sum()) == 24 * 2 and "router_bias" not in layer


def test_yarn_scales_the_slow_dimensions_and_both_tables(model):
    _, cfg, _ = model
    x = jnp.ones((1, 1, 1, 16), jnp.float32)
    pos = jnp.asarray([[40]])
    plain = np.asarray(decoder._rope(x, pos, 500000.0))
    yarn = np.asarray(decoder._rope(x, pos, 500000.0, cfg.rope_parameters["attention"]))
    inv, factor = mellum2.inverse_frequencies(dict(mellum2.sizes(CONFIG)), "full_attention")
    ang = 40.0 * np.asarray(inv)
    want = factor * (np.concatenate([np.cos(ang), np.cos(ang)]) + np.concatenate([-np.sin(ang), np.sin(ang)]))
    np.testing.assert_allclose(yarn[0, 0, 0], want, rtol=1e-5)
    assert not np.allclose(yarn, plain, atol=1e-2) and abs(factor - (0.1 * np.log(16) + 1)) < 1e-12


# ------------------------------------------------ the kernel with a window
def paged_case(window, w, ctx_lens, heads=32, kv_heads=4, d=128, bs=16, cols=6, seed=0):
    """A window of ``w`` queries a sequence ending at ``ctx_lens``
    positions, over a table of ``cols`` columns whose column 0 is the
    first block the window can reach."""
    rs = np.random.RandomState(seed)
    b = len(ctx_lens)
    n_blocks = 1 + b * cols
    r, lw = da.cache_row_shape(kv_heads, d)
    k_cache = jnp.asarray(rs.standard_normal((2, n_blocks, bs, r, lw)), jnp.float32)
    v_cache = jnp.asarray(rs.standard_normal((2, n_blocks, bs, r, lw)), jnp.float32)
    q = jnp.asarray(rs.standard_normal((b, w, heads, d)), jnp.float32)
    qpos = np.stack([np.arange(c - w, c) for c in ctx_lens]).astype(np.int32)
    first = np.maximum(0, qpos[:, 0] - window + 1) // bs * bs
    tables = (1 + np.arange(b * cols).reshape(b, cols)).astype(np.int32)
    return q, k_cache, v_cache, jnp.asarray(tables), jnp.asarray(qpos), jnp.asarray(first.astype(np.int32))


def dense_window_attention(q, k_cache, v_cache, layer, tables, qpos, first, window):
    """The masked softmax written out, position by position."""
    b, w, h, d = q.shape
    bs = k_cache.shape[2]
    kv = k_cache.shape[3] * k_cache.shape[4] // d
    out = np.zeros((b, w, h, d), np.float32)
    for i in range(b):
        keys = np.asarray(k_cache[layer, tables[i]]).reshape(-1, kv, d)
        vals = np.asarray(v_cache[layer, tables[i]]).reshape(-1, kv, d)
        pos = int(first[i]) + np.arange(keys.shape[0])
        for j in range(w):
            t = int(qpos[i, j])
            seen = (pos <= t) & (pos > t - window)
            for head in range(h):
                s = keys[seen, head // (h // kv)] @ np.asarray(q[i, j, head]) / np.sqrt(d)
                p = np.exp(s - s.max())
                out[i, j, head] = (p / p.sum()) @ vals[seen, head // (h // kv)]
    return out


@pytest.mark.parametrize("w", [1, 5])
@pytest.mark.parametrize("kv_splits", [1, 4])
def test_paged_window_attention_at_heads_of_128_in_groups_of_8(w, kv_splits):
    """Interpret mode, 32 query heads over 4 K/V heads of 128 (the plain
    row layout, ``sw = 1``), a window of 40 positions: contexts inside
    the window, at its edge and far past it; the table starts at the
    first block the window reaches; the kernel, its split form and the
    XLA lowering all give the written-out softmax."""
    window = 40
    case = paged_case(window, w, ctx_lens=[12, 40, 45, 83])
    q, k_cache, v_cache, tables, qpos, first = case
    want = dense_window_attention(q, k_cache, v_cache, 1, np.asarray(tables), np.asarray(qpos), np.asarray(first), window)
    ref = da.reference_paged_append_attention(q, k_cache, v_cache, 1, tables, qpos, None, window, first)
    np.testing.assert_allclose(np.asarray(ref), want, atol=2e-5)
    got = da.paged_append_attention(
        q, k_cache, v_cache, 1, tables, qpos, interpret=True, kv_splits=kv_splits, window=window, first_positions=first,
    )
    np.testing.assert_allclose(np.asarray(got), want, atol=2e-5)


def test_the_window_call_has_a_name_of_its_own_and_skips_what_lies_behind():
    window = 40
    q, k_cache, v_cache, tables, qpos, first = paged_case(window, 1, ctx_lens=[83, 45])
    call = lambda **kw: (lambda *a: da.paged_append_attention(*a[:3], 0, *a[3:], interpret=True, **kw))  # noqa: E731
    text = str(jax.make_jaxpr(call(window=window, first_positions=first))(q, k_cache, v_cache, tables, qpos))
    plain = str(jax.make_jaxpr(call())(q, k_cache, v_cache, tables, qpos))
    assert "paged_window_attention" in text and "paged_window_attention" not in plain
    # poison every block wholly behind the window (and scratch): never read
    bad = np.asarray(k_cache).copy()
    for i in range(2):
        lo = (int(qpos[i, 0]) - window + 1 - int(first[i])) // 16
        bad[:, np.asarray(tables)[i, :max(lo, 0)]] = np.nan
    bad[:, 0] = np.nan
    got = da.paged_append_attention(
        q, jnp.asarray(bad), v_cache, 0, tables, qpos, interpret=True, window=window, first_positions=first
    )
    want = da.paged_append_attention(q, k_cache, v_cache, 0, tables, qpos, interpret=True, window=window, first_positions=first)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-6)


def test_a_padding_query_emits_zeros_and_holds_no_window_open():
    window = 40
    q, k_cache, v_cache, tables, qpos, first = paged_case(window, 5, ctx_lens=[83, 45])
    qpos = qpos.at[1, 3:].set(-1)
    for kv_splits in (1, 4):
        got = da.paged_append_attention(
            q, k_cache, v_cache, 0, tables, qpos, interpret=True, kv_splits=kv_splits, window=window, first_positions=first,
        )
        ref = da.reference_paged_append_attention(q, k_cache, v_cache, 0, tables, qpos, None, window, first)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=2e-5)
        assert not np.asarray(got)[1, 3:].any()


# ------------------------------------------------ the cell's limits, rehearsed
def test_the_cell_s_limits_fail_its_controls_at_rehearsal_size(model):
    """Window ignored, plain rotary in the full layers and int8 weights
    each read over the cell's pooled limit at rehearsal size, the two
    mechanism controls over its request limit too (float32 weights: the
    sound program reads 0)."""
    _, _, params = model
    cell = json.loads((ROOT / "benchmark/workloads/mellum2-12b.code-gen.json").read_text())
    rs = np.random.RandomState(7)
    prompts = [[int(t) for t in rs.randint(0, 512, size=n)] for n in (24, 40, 56, 33)]
    outs = [[int(t) for t in rs.randint(0, 512, size=40)] for _ in prompts]  # any continuation: judged given its prefix
    lay = mellum2.layout(prompts, outs, pad_to=96, max_new=40)
    arms = {a: mellum2.choices(params, CONFIG, lay["tokens"], lay["at"], a)
            for a in ("bfloat16", "window_ignored", "plain_rotary", "int8")}
    judged = mellum2.judge(params, CONFIG, lay["tokens"], lay["at"], arms, lay["valid"])
    for control in ("window_ignored", "plain_rotary", "int8"):
        assert mellum2.gap_ratio(judged[control], judged["bfloat16"]) > cell["gap_ratio_limit"], control
    for control in ("window_ignored",):  # (at this size plain rotary reads 3.5-4.5 pooled, 21-70 at the cell's: its excess too)
        worst = mellum2.worst_request_excess(judged[control], judged["bfloat16"], lay["valid"])
        assert worst["excess"] > cell["request_excess_limit"], (control, worst)
    # a request whose stated sum is a hundredth of the others': its ratio explodes, its excess does not
    own, ref = {"gap": np.asarray([1.0, 1.1, 0.02])}, {"gap": np.asarray([1.0, 1.0, 0.01])}
    one_each = np.eye(3, dtype=bool)
    assert mellum2.worst_request_ratio(own, ref, one_each) == pytest.approx(2.0)
    assert mellum2.worst_request_excess(own, ref, one_each)["excess"] == pytest.approx(0.1 / 0.67)
