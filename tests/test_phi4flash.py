"""Phi-4-mini-flash-reasoning on the normal path, at rehearsal size on the
CPU (PR 57): a decoder-hybrid-decoder whose Mamba-1 layers' state lives per
slot, whose window layers have a pool of their own, whose ONE full K/V the
cross layers read again, whose Gated Memory Units read another layer's
intermediate, all under differential attention; against the benchmark's plain
float32 reference, logits not tokens; the update kernel in interpret mode; the
padded-query form against the equations; the refusals by name."""
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
from benchmark.reference import phi4flash  # noqa: E402
from flexflow_tpu.generation import GenerationEngine, decoder  # noqa: E402
from flexflow_tpu.generation.engine import SamplingParams  # noqa: E402
from flexflow_tpu.obs.capacity import ServingFlops  # noqa: E402
from flexflow_tpu.ops import ssm  # noqa: E402
from flexflow_tpu.ops.attention import masked_attention  # noqa: E402

FILE = json.loads((ROOT / "benchmark/configs/phi-4-mini-flash-reasoning.json").read_text())
# hidden 64, 8 layers: Mamba-1 at 0, 2, 4 (inner 128, state 16, dt rank 4, chunks of 8), a window of 16 at 1, 3, the one
# full K/V at 5, a GMU at 6, a cross layer at 7; 4 query heads over 2 K/V heads of 16 (ONE stored pair of 32)
CONFIG = spec._merge(FILE, FILE["rehearsal"])
# logits of an 8-layer float32 model summed in another order (a cache against a full forward, padded queries against
# paired heads): errors of 1e-5. A state rounded to bfloat16 moves them by 5e-3 and a missing lambda a2 by tenths
# (test_a_bfloat16_state_and_a_missing_lambda_fail_the_tolerance)
ATOL = 2e-4


@pytest.fixture(scope="module")
def model():
    params = phi4flash.cast_params(phi4flash.init_params(7, CONFIG), jnp.float32)
    return phi4flash.engine_config(CONFIG, 128), params


def reference_logits(params, tokens, control="", dtype="float32"):
    at = jnp.tile(jnp.arange(tokens.shape[1])[None], (tokens.shape[0], 1))
    return np.asarray(phi4flash.logits_at(params, jnp.asarray(tokens), at, CONFIG, dtype, control))


@pytest.fixture(scope="module")
def engine(model):
    """ONE engine for the tests that serve through it (its programs compile once); each takes it reset."""
    cfg, params = model
    return GenerationEngine(params, cfg, max_batch_slots=3, block_size=8, prompt_buckets=[16, 32], max_seq_len=64)


@pytest.fixture
def eng(engine):
    engine.reset()
    return engine


def test_the_rehearsal_preset_is_the_published_pattern(model):
    cfg, params = model
    assert cfg.layer_types == ("mamba", "window", "mamba", "window", "mamba", "attention", "gmu", "cross")
    assert phi4flash.kinds(32)[:4] == ("mamba", "window", "mamba", "window") and phi4flash.kinds(32)[15:20] == ("window", "mamba", "attention", "gmu", "cross")
    assert cfg.ssm_layers == cfg.mamba_layers == (0, 2, 4) and cfg.window_layers == (1, 3) and cfg.full_layers == (5,)
    # a layer that attends and a layer that stores K/V are two properties now
    assert cfg.attention_layers == (1, 3, 5, 7) and cfg.kv_layers == (1, 3, 5) and cfg.cross_layers == (7,) and cfg.gmu_layers == (6,)
    assert cfg.kv_index == (("window", 0), ("window", 1), ("attention", 0), ("cross", 0)) and cfg.stored_index == cfg.kv_index[:3]
    assert (cfg.kv_source, cfg.memory_source, cfg.cross_from) == (5, 4, 6)
    assert (cfg.cache_kv_heads, cfg.cache_head_dim, cfg.ssm_inner, cfg.ssm_conv_width, cfg.dt_rank) == (1, 32, 128, 128, 4)
    own = decoder.init_decoder_params(jax.random.key(0), cfg)  # the program's own initialiser makes the same pytree
    assert jax.tree.map(lambda a: (a.shape, a.dtype), own) == jax.tree.map(lambda a: (a.shape, a.dtype), params)
    assert "wk" not in params["layers"][7] and params["layers"][0]["ssm_a_log"].shape == (128, 16)
    step = np.asarray(jax.nn.softplus(own["layers"][0]["ssm_dt_bias"]))
    assert np.all((step >= 1e-3 - 1e-6) & (step <= 0.1 + 1e-6)) and np.all(own["layers"][0]["ssm_d"] == 1)


def test_the_parameter_count_is_the_published_one_recomputed_from_the_file():
    n = phi4flash.parameter_counts(FILE)
    assert round(n["whole"] / 1e9, 2) == 3.85 and n["held"] == n["whole"] and FILE["reduced"] == []  # nothing is cut
    assert [round(n[k] / 1e6, 1) for k in ("mamba_layer", "attention_layer", "gmu_layer", "cross_layer")] == [119.9, 98.3, 104.9, 91.8]
    assert round(2 * n["whole"] / 1e9, 2) == 7.71 and 2 * n["whole"] / 16.9e9 > 0.25
    cfg = phi4flash.engine_config(FILE, 2304)
    assert (cfg.num_layers, cfg.vocab_size, cfg.ssm_inner, cfg.ssm_state_size, cfg.dt_rank, cfg.window) == (32, 200064, 5120, 16, 160, 512)
    assert len(cfg.mamba_layers) == 9 and len(cfg.window_layers) == 8 and cfg.full_layers == (17,) and len(cfg.cross_layers) == len(cfg.gmu_layers) == 7
    # 10 pairs of 128 are stored as 16 rows (a block of the cache is copied by 1, 2, 4 or a multiple of 8 rows), 6 of them zero
    assert (cfg.kv_heads // 2, cfg.cache_kv_heads, cfg.cache_head_dim, cfg.attend_heads) == (10, 16, 128, 64)
    flops = ServingFlops.from_config(cfg, dtype=cfg.dtype)
    assert flops.param_count == pytest.approx(n["whole"], rel=1e-4) and flops.state_bytes_per_seq == 9 * 4 * 5120 * 16
    # one write and eight reads of the ONE K/V a context token: 5,120 B stored, 40,960 B read
    assert flops.kv_bytes_per_pos == 5120 and flops.kv_read_bytes_per_pos == 40960
    # a prefill runs 14 of 32 layers and the head on one row: well under half of what every row through every layer costs
    every_row = 1024 * flops.per_token_flops
    assert 0.5 * every_row < flops.prefill_flops(1024) - flops.per_ctx_flops * 1024 * 1025 // 2 < 0.62 * every_row


def test_forward_full_is_the_reference(model):
    cfg, params = model
    tokens = np.random.RandomState(1).randint(0, 512, size=(2, 45)).astype(np.int32)
    with jax.default_matmul_precision("highest"):
        got = np.asarray(decoder.forward_full(params, jnp.asarray(tokens), cfg=cfg))
    np.testing.assert_allclose(got, reference_logits(params, tokens), atol=ATOL)


def test_a_bfloat16_state_and_a_missing_lambda_fail_the_tolerance(model):
    cfg, params = model
    tokens = np.random.RandomState(1).randint(0, 512, size=(2, 45)).astype(np.int32)
    want = reference_logits(params, tokens)
    for control, least in (("bfloat16_state", 10 * ATOL), ("no_lambda", 0.1)):
        # (the control upon float32 arithmetic: what it changes is all that differs)
        off = np.abs(reference_logits(params, tokens, control=control) - want).max()
        assert off > least, (control, off)


def test_the_prefill_that_skips_the_cross_decoder_gives_the_last_row_s_logits(model):
    cfg, params = model
    tokens = jnp.asarray(np.random.RandomState(2).randint(0, 512, size=(2, 32)).astype(np.int32))
    lens = jnp.asarray([32, 19])
    full = jax.jit(lambda p, t, n: decoder.prefill(p, t, n, cfg))(params, tokens, lens)
    last = jax.jit(lambda p, t, n: decoder.prefill(p, t, n, cfg, last_only=True))(params, tokens, lens)
    assert last[0].shape == (2, 1, 512)
    np.testing.assert_allclose(np.asarray(last[0][:, 0]), np.asarray(full[0][jnp.arange(2), lens - 1]), atol=1e-5)
    # K/V of the three storing layers and the state of the three Mamba layers are the same numbers either way
    for a, b in zip(jax.tree.leaves(last[1:]), jax.tree.leaves(full[1:])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert last[1].shape[0] == 3 and last[3]["state"].shape == (3, 2, 128, 16)


@pytest.mark.parametrize("length", [8, 13, 16, 21, 32])  # on a chunk's end, inside one, on a bucket's end, past it, the last bucket's
def test_prefill_then_decode_through_the_three_caches_is_the_full_forward(model, eng, length):
    """The engine's own programs: a prefill at a bucket, the hand-over of
    the slot's state, decode steps through the state, the window pool (past
    the window of 16) and the one full K/V, into a slot another sequence
    used."""
    cfg, params = model
    rs = np.random.RandomState(length)
    first, prompt = rs.randint(0, 512, 11).tolist(), rs.randint(0, 512, length).tolist()
    eng.generate([first], SamplingParams(max_new_tokens=5, temperature=0.0))  # takes slot 0 first, and leaves its state behind
    assert float(jnp.abs(eng.cache.state["ssm"][:, 0]).max()) > 0
    out = eng.generate([prompt], SamplingParams(max_new_tokens=24, temperature=0.0))[0]
    tokens = np.asarray([prompt + list(out)], np.int32)
    want = reference_logits(params, np.pad(tokens, ((0, 0), (0, 56 - tokens.shape[1]))))[0]  # (one shape: one compile)
    assert list(out) == np.argmax(want[length - 1 : length + 23], -1).tolist()


def test_the_logits_step_by_step_through_the_caches_a_prefill_left(model):
    """decoder.prefill (the cross-decoder on the last row alone), the
    hand-over as the engine makes it, then decoder.decode_step through the
    state, the window layers' arrays and the one full K/V: LOGITS row by
    row against the reference's full forward, past the window of 16."""
    cfg, params = model
    length, steps = 13, 12
    tokens = np.random.RandomState(0).randint(0, 512, size=(1, length + steps)).astype(np.int32)
    want = reference_logits(params, tokens)[0]
    logits, ks, vs, left = jax.jit(lambda p, t: decoder.prefill(p, t, None, cfg, last_only=True))(params, jnp.asarray(tokens[:, :length]))
    np.testing.assert_allclose(np.asarray(logits[0, 0]), want[length - 1], atol=ATOL)
    state = {"ssm_conv": jax.vmap(lambda z: decoder.state_at(z, jnp.asarray([length]), 4))(left["xbc"]),
             "ssm": jnp.swapaxes(left["state"], -1, -2)}
    table = jnp.arange(1, 5, dtype=jnp.int32)[None]
    block, offset = jnp.arange(length) // 8 + 1, jnp.arange(length) % 8
    k, v = jnp.zeros((1, 5, 8, 1, 32), jnp.float32), jnp.zeros((1, 5, 8, 1, 32), jnp.float32)
    window = {"k": jnp.zeros((2, 5, 8, 1, 32), jnp.float32), "v": jnp.zeros((2, 5, 8, 1, 32), jnp.float32)}
    for li, (kind, at) in enumerate(cfg.stored_index):  # the prefill returned K/V for the three storing layers alone
        if kind == "window":
            window = {"k": decoder.write_rows(window["k"], at, block, offset, ks[li, 0]), "v": decoder.write_rows(window["v"], at, block, offset, vs[li, 0])}
        else:
            k, v = decoder.write_rows(k, at, block, offset, ks[li, 0]), decoder.write_rows(v, at, block, offset, vs[li, 0])
    step = jax.jit(lambda p, t, pos, k, v, st, w: decoder.decode_step(p, t, pos, k, v, table, pos + 1, cfg=cfg, ssm=st, window=w))
    for i in range(steps):
        pos = length + i
        w = dict(window, tables=table, first=jnp.zeros((1,), jnp.int32))  # (a table that holds every column: the kernel's own bound is the window)
        got, k, v, state, window = step(params, jnp.asarray(tokens[:, pos]), jnp.asarray([pos]), k, v, state, w)
        np.testing.assert_allclose(np.asarray(got[0]), want[pos], atol=ATOL)


def test_a_dead_slot_keeps_its_state_bit_for_bit(model, eng):
    cfg, params = model
    tokens = np.random.RandomState(3).randint(0, 512, size=(2, 32)).astype(np.int32)
    eng.generate([tokens[0, :20].tolist(), tokens[1, :9].tolist()], SamplingParams(max_new_tokens=2, temperature=0.0))
    before = {k: np.asarray(v) for k, v in eng.cache.state.items()}
    state = {name: eng.cache.state[name] for name in ("ssm_conv", "ssm")}
    window = {"k": eng.cache.state["wk"], "v": eng.cache.state["wv"], "tables": jnp.zeros((3, eng.window_columns), jnp.int32),
              "first": jnp.zeros((3,), jnp.int32)}
    live = jnp.asarray([1, 0, 0], jnp.int32)
    out = jax.jit(lambda p, ck, cv, st, w: decoder.decode_step(
        p, jnp.asarray([5, 6, 7]), jnp.asarray([22, 0, 0]), ck, cv,
        jnp.zeros((3, eng.max_blocks_per_seq), jnp.int32), live * 23, cfg=cfg, ssm=st, window=w)[3])(params, eng.cache.k, eng.cache.v, state, window)
    for name, part in out.items():
        np.testing.assert_array_equal(np.asarray(part[:, 1:]), before[name][:, 1:])
        assert not np.array_equal(np.asarray(part[:, 0]), before[name][:, 0])


@pytest.mark.parametrize("length, chunk", [(37, 8), (64, 16), (5, 8)])
def test_the_prefill_scan_is_the_recurrence_position_by_position(length, chunk):
    k = jax.random.split(jax.random.key(length), 5)
    b_, d, n = 2, 128, 16
    x, dt = jax.random.normal(k[0], (b_, length, d)), jax.nn.softplus(jax.random.normal(k[1], (b_, length, d)) - 3)
    dt = dt.at[1, length - 3 :].set(0.0)  # rows behind a sequence's length pass the state on
    x = x.at[1, length - 3 :].set(0.0)
    a = -jnp.broadcast_to(jnp.arange(1, n + 1, dtype=jnp.float32), (d, n))
    b, c = jax.random.normal(k[2], (b_, length, n)), jax.random.normal(k[3], (b_, length, n))
    y0, s0 = ssm.selective_recurrence(x, dt, a, b, c)
    y1, s1 = ssm.selective_scan(x, dt, a, b, c, chunk)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y0), atol=1e-5)
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s0), atol=1e-6)
    _, before = ssm.selective_recurrence(x[1:, : length - 3], dt[1:, : length - 3], a, b[1:, : length - 3], c[1:, : length - 3])
    np.testing.assert_array_equal(np.asarray(s0[1]), np.asarray(before[0]))


def test_the_update_kernel_interpreted_is_its_xla_composition_and_the_recurrence():
    k = jax.random.split(jax.random.key(0), 6)
    slots, d, n = 4, 256, 16
    a = -jnp.broadcast_to(jnp.arange(1, n + 1, dtype=jnp.float32), (d, n))
    live = jnp.asarray([1.0, 0.0, 1.0, 1.0])[:, None]
    x, dt = jax.random.normal(k[0], (slots, d)) * live, jax.nn.softplus(jax.random.normal(k[1], (slots, d)) - 3) * live
    b, c = jax.random.normal(k[2], (slots, n)), jax.random.normal(k[3], (slots, n))
    old = jax.random.normal(k[4], (slots, d, n))
    state = jnp.zeros((3, slots, n, d)).at[1].set(jnp.swapaxes(old, 1, 2))
    y_ref, s_ref = ssm.selective_update_reference(state, 1, x, dt, a, b, c)
    y_ker, s_ker = ssm.selective_update(state, 1, x, dt, a, b, c, interpret=True)
    y_rec, s_rec = ssm.selective_recurrence(x[:, None], dt[:, None], a, b[:, None], c[:, None], old)
    np.testing.assert_allclose(np.asarray(y_ker), np.asarray(y_ref), atol=1e-5)
    np.testing.assert_allclose(np.asarray(s_ker), np.asarray(s_ref), atol=1e-6)
    np.testing.assert_allclose(np.asarray(y_ker), np.asarray(y_rec[:, 0]), atol=1e-5)
    np.testing.assert_allclose(np.asarray(jnp.swapaxes(s_ker[1], 1, 2)), np.asarray(s_rec), atol=1e-6)
    # a slot that is not live keeps its state bit for bit, and the other layers are untouched
    np.testing.assert_array_equal(np.asarray(s_ker[1, 1]), np.asarray(state[1, 1]))
    assert not np.asarray(s_ker[0]).any() and not np.asarray(s_ker[2]).any()


def test_the_padded_query_form_of_differential_attention_is_the_equations(model):
    """``_diff_qkv`` + a plain grouped attention call + ``_diff_out`` against
    the reference's paired-head equations, for one layer's weights."""
    cfg, params = model
    layer, index = params["layers"][5], 5
    u = jax.random.normal(jax.random.key(4), (2, 21, 64))
    s = dict(phi4flash.sizes(CONFIG), dtype=jnp.dtype("float32"))
    with jax.default_matmul_precision("highest"):
        want, _ = phi4flash._attention(u, layer, s, phi4flash.lambda_init(index), 0)
        q, k, v = decoder._diff_qkv(cfg, layer, u)
        assert q.shape == (2, 21, 4, 32) and k.shape == v.shape == (2, 21, 1, 32)
        # query head 2i holds [q1 | 0], head 2i + 1 [0 | q2]: half of every padded query is zero
        assert not np.asarray(q[:, :, 0::2, 16:]).any() and not np.asarray(q[:, :, 1::2, :16]).any()
        ctx = masked_attention(q, k, v, jnp.asarray([21, 21]))  # the call's own scale, 1 / sqrt(32)
        got = decoder._diff_out(cfg, index, layer, ctx)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)


def test_the_three_kinds_of_state_live_in_one_manager_and_nothing_indexes_a_prefix(model, eng):
    cfg, params = model
    assert eng.cache_config.num_layers == 1 and eng.window_config.num_layers == 2 and eng.slot_state.num_layers == 3
    assert eng.cache.k.shape[3:] == (1, 32) and eng.cache.state["wk"].shape[3:] == (1, 32)  # ONE stored pair of 2 x 16
    assert eng.cache.state["ssm"].shape == (3, 3, 16, 128) and eng.cache.state["ssm"].dtype == jnp.float32
    assert eng.cache.state["ssm_conv"].shape == (3, 3, 3, 128)
    assert not eng.prefix_cache.enabled and set(eng.unsupported) >= {"speculation", "kv_handoff", "tensor_parallel", "prefix_reuse"}
    assert all("decoder-hybrid-decoder" in eng.unsupported[k] or "Mamba-1" in eng.unsupported[k] for k in ("speculation", "kv_handoff", "tensor_parallel", "prefix_reuse"))
    with pytest.raises(NotImplementedError, match="cross layers"):
        decoder.verify_step(params, jnp.zeros((3, 2), jnp.int32), jnp.zeros((3, 2), jnp.int32), eng.cache.k, eng.cache.v,
                            jnp.zeros((3, eng.max_blocks_per_seq), jnp.int32), cfg=cfg)
    with pytest.raises(NotImplementedError, match="tp_degree"):
        GenerationEngine(params, cfg, max_batch_slots=2, block_size=8, prompt_buckets=[16], max_seq_len=32, tp_degree=2)
    prompts = [np.random.RandomState(i).randint(0, 512, 9 + 7 * i).tolist() for i in range(3)]
    before = eng.prefill_stats()
    eng.generate(prompts, SamplingParams(max_new_tokens=4, temperature=0.0))
    stats = eng.cache_stats()
    assert stats["shared_kv"] == {"producer_layers": [5], "reader_layers": [7], "bytes_per_token": 2 * 32 * 4, "bytes_saved_per_token": 2 * 32 * 4}
    assert stats["ssm"]["layers"] == 3 and stats["ssm"]["state_bytes_per_slot"] == 3 * 16 * 128 * 4 and stats["window"]["layers"] == 2
    # a prefill ran its cross-decoder on ONE row a prompt, and spared it the bucket's others
    after = eng.prefill_stats()
    assert after["cross_layers"] == 2 and after["cross_rows_run_total"] - before["cross_rows_run_total"] == 3
    assert after["cross_rows_skipped_total"] - before["cross_rows_skipped_total"] == 15 + 15 + 31
    # the hand-over (the prompt's K/V rows and the slot's parts, donated) is a program a bucket, traced once each
    assert eng.trace_counts["state_install[16]"] == eng.trace_counts["state_install[32]"] == 1 and not eng.recompiles()


def test_configurations_that_are_not_written_down_are_refused_by_name(model):
    cfg, _ = model
    import dataclasses
    with pytest.raises(ValueError, match="reads what layer"):
        dataclasses.replace(cfg, kv_source=1)  # a window layer's K/V is not every position's
    with pytest.raises(ValueError, match="reads what layer"):
        dataclasses.replace(cfg, memory_source=6)
    with pytest.raises(ValueError, match="Mamba-2"):
        dataclasses.replace(cfg, layer_types=("ssm",) + cfg.layer_types[1:], ssm_heads=8, ssm_head_dim=16)
    with pytest.raises(ValueError, match="differential"):
        dataclasses.replace(cfg, qk_norm=True)


def test_pairs_that_no_block_copy_takes_are_stored_padded_and_read_the_same_logits():
    """3 K/V pairs (the published model has 10, stored as 16 rows) are stored
    as 4 rows, the queries as 16 heads of which 12 are real: a forward, and a
    prefill then decode steps through the engine's caches (past the window),
    against the reference."""
    config = spec._merge(CONFIG, {"num_attention_heads": 12, "num_key_value_heads": 6})
    params = phi4flash.cast_params(phi4flash.init_params(5, config), jnp.float32)
    cfg = phi4flash.engine_config(config, 128)
    assert (cfg.kv_heads // 2, cfg.cache_kv_heads, cfg.attend_heads) == (3, 4, 16)
    tokens = np.random.RandomState(1).randint(0, 512, size=(1, 40)).astype(np.int32)
    at = jnp.arange(40)[None]
    want = np.asarray(phi4flash.logits_at(params, jnp.asarray(tokens), at, config))
    with jax.default_matmul_precision("highest"):
        got = np.asarray(decoder.forward_full(params, jnp.asarray(tokens), cfg=cfg))
    np.testing.assert_allclose(got, want, atol=ATOL)
    engine = GenerationEngine(params, cfg, max_batch_slots=2, block_size=8, prompt_buckets=[16], max_seq_len=48)
    assert engine.cache.k.shape[3:] == (1, 128)  # (4 stored pairs of 32 values fill one row of 128 lanes)
    out = engine.generate([tokens[0, :11].tolist()], SamplingParams(max_new_tokens=20, temperature=0.0))[0]
    seq = np.asarray([tokens[0, :11].tolist() + list(out)], np.int32)
    again = np.asarray(phi4flash.logits_at(params, jnp.asarray(np.pad(seq, ((0, 0), (0, 40 - seq.shape[1])))), at, config))[0]
    assert list(out) == np.argmax(again[10:30], -1).tolist()
