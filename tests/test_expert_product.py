"""The routed experts' two lowerings (PR 35): the sum over (row, chosen
expert) pairs grouped by expert (``flexflow_tpu/ops/expert_product.py``)
against the dense product ``decoder.expert_ffn`` has always run, at
rehearsal widths on the CPU with the published routings (softmax top-8
of 64, sigmoid + bias top-4 of 32, 16 held of 256 beside a shared
expert), the Pallas grouped product in interpret mode; the rule that
picks a form from a call's shapes alone; the counter.

Tolerance 1e-5 in float32: both forms compute the same float32 equations
and differ in the order of the sum over a row's experts (inside one
product's accumulation against k partial results added afterwards). In
bfloat16 both round at the same points and the last cast can land one
step apart: 2**-7 of the value.
"""
import functools
import hashlib
import json
import os
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
from benchmark.reference import joyai, lfm2, mellum2  # noqa: E402
from flexflow_tpu.generation import ContinuousBatchingScheduler, GenerationEngine, SamplingParams, decoder  # noqa: E402
from flexflow_tpu.ops import expert_product  # noqa: E402

pytestmark = pytest.mark.generation

# the three configurations' routings, at a hidden size of 64 and experts 32 wide
ROUTINGS = {
    "softmax-top8-of-64": dict(router="softmax", num_experts=64, experts_per_token=8),
    "sigmoid-bias-top4-of-32": dict(router="sigmoid", num_experts=32, experts_per_token=4),
    "16-held-of-256-shared": dict(router="sigmoid", num_experts=256, experts_per_token=8, routed_scaling_factor=2.5,
                                  num_shared_experts=1, experts_held=tuple(range(3, 256, 16))),
}
ROWS = 40


def routed_layer(routing: str, dtype=jnp.float32):
    cfg = decoder.DecoderConfig(
        num_layers=1, hidden_size=64, num_heads=4, ff_size=128, seq_length=64, vocab_size=128, causal=True,
        norm="rmsnorm", positions="rotary", ffn="swiglu", num_dense_layers=0, moe_ff_size=32,
        dtype=decoder.DataType.FLOAT if dtype == jnp.float32 else decoder.DataType.BFLOAT16, **ROUTINGS[routing])
    layer = decoder.init_decoder_params(jax.random.key(3), cfg)["layers"][0]
    layer = {k: a if k.startswith("router") else a.astype(dtype) for k, a in layer.items()}
    rows = jnp.asarray(np.random.RandomState(5).standard_normal((ROWS, 64)), dtype)
    return cfg, layer, rows


def share_of(layer, held, stacked):
    """The layer holding the experts ``held`` alone (``stacked``: the experts the layer's weights stack, in order)."""
    at = jnp.asarray([stacked.index(i) for i in held])
    return dict(layer, **{k: layer[k][at] for k in ("ew1", "ew3", "ew2")})


def take_grouped(monkeypatch):
    """Every expert layer takes the grouped form, its Pallas product
    interpreted: the CPU backend takes the dense form whatever the rule
    says, so the tests steer it here (no option of the program does)."""
    monkeypatch.setattr(expert_product, "on_tpu", lambda: True)
    monkeypatch.setattr(expert_product, "expert_form", lambda *shape: "grouped")
    monkeypatch.setattr(expert_product, "grouped_matmul", functools.partial(expert_product.grouped_matmul, interpret=True))
    return monkeypatch


@pytest.fixture
def grouped(monkeypatch):
    return take_grouped(monkeypatch)


def dense_ffn(cfg, layer, rows, held=None):
    assert expert_product.expert_lowering(rows.shape[0], layer["ew1"].shape[0], cfg.experts_per_token) == "dense"
    return decoder.expert_ffn(cfg, layer, rows, held=held)


CASES = ["all-rows", "padding-rows", "a-row-none-of-whose-experts-is-held", "unvisited-rows-poisoned",
         "disjoint-shares-add-up", "counters", "bfloat16"]


@pytest.mark.parametrize("case", CASES)
@pytest.mark.parametrize("routing", sorted(ROUTINGS))
def test_the_grouped_form_is_the_dense_form(routing, case, monkeypatch):
    """One sum, two lowerings: results equal within float32 accumulation,
    the gates the same array, rows without a group exact zeros."""
    dtype = jnp.bfloat16 if case == "bfloat16" else jnp.float32
    cfg, layer, rows = routed_layer(routing, dtype)
    stacked = list(cfg.experts_held or range(cfg.num_experts))
    held = cfg.experts_held or None
    interpreted = functools.partial(expert_product.grouped_matmul, interpret=True)

    def grouped_sum(layer, held, live=None, product=interpreted):
        gates, chosen = decoder.route(cfg, layer, rows)
        return expert_product.grouped_expert_sum(
            rows, gates, chosen, layer["ew1"], layer["ew3"], layer["ew2"], held=held, live=live, product=product), gates

    want, want_gates = dense_ffn(cfg, layer, rows, held)
    assert float(jnp.abs(want.astype(jnp.float32)).max()) > 1e-3
    close = functools.partial(np.testing.assert_allclose, atol=1e-5, rtol=2.0 ** -7 if case == "bfloat16" else 0)
    f32 = lambda a: np.asarray(a.astype(jnp.float32))  # noqa: E731
    if case in ("all-rows", "bfloat16"):
        got, gates = grouped_sum(layer, held)
        close(f32(got), f32(want))
        np.testing.assert_array_equal(np.asarray(gates), np.asarray(want_gates))
        assert got.dtype == want.dtype == dtype
    elif case == "padding-rows":
        live = jnp.arange(ROWS) < 27
        got, _ = grouped_sum(layer, held, live)
        close(f32(got)[:27], f32(want)[:27])
        assert not np.any(f32(got)[27:])  # zeros by a `where`: nothing reads them, nothing multiplied them
    elif case == "a-row-none-of-whose-experts-is-held":
        few = stacked[: max(2, len(stacked) // 8)]
        part = share_of(layer, few, stacked)
        want, gates = dense_ffn(cfg, part, rows, few)
        nowhere = np.asarray(~jnp.any(gates[:, jnp.asarray(few)] > 0, axis=1))
        assert 0 < nowhere.sum() < ROWS
        got, _ = grouped_sum(part, few)
        close(f32(got), f32(want))
        assert not np.any(f32(got)[nowhere]) and np.all(np.any(f32(got)[~nowhere] != 0, axis=1))
    elif case == "unvisited-rows-poisoned":
        def poisoned(lhs, rhs, sizes):
            visited = jnp.arange(lhs.shape[0]) < jnp.sum(sizes)
            return jnp.where(visited[:, None], interpreted(lhs, rhs, sizes), jnp.nan)
        live = jnp.arange(ROWS) < 33
        got, _ = grouped_sum(layer, held, live, product=poisoned)
        assert np.all(np.isfinite(f32(got))) and not np.any(f32(got)[33:])
        close(f32(got)[:33], f32(want)[:33])
    elif case == "disjoint-shares-add-up":
        shares = [stacked[i::4] for i in range(4)]
        parts = [grouped_sum(share_of(layer, s, stacked), s)[0] for s in shares]
        close(f32(sum(parts)), f32(want))
        assert not np.allclose(f32(parts[0]), f32(want), atol=1e-4)
    else:  # the layer through `_ffn`, its counters beside it, under either form
        live = jnp.arange(ROWS) < 31
        x = rows.reshape(1, ROWS, 64)
        dense_counts, grouped_counts = [], []
        dense_out = decoder._ffn(cfg, 0, layer, x, live[None], dense_counts)
        take_grouped(monkeypatch)
        grouped_out = decoder._ffn(cfg, 0, layer, x, live[None], grouped_counts)
        np.testing.assert_array_equal(np.asarray(grouped_counts[0]), np.asarray(dense_counts[0]))
        assert int(dense_counts[0][: len(stacked)].sum()) == (31 * cfg.experts_per_token if held is None else int(
            jnp.sum((want_gates[:31, jnp.asarray(stacked)] > 0))))
        close(f32(grouped_out)[0, :31], f32(dense_out)[0, :31])


# a whole prefill ---------------------------------------------------------
MODELS = {
    "lfm2-8b-a1b": (lfm2, 11),
    "mellum2-12b": (mellum2, 5),
    "joyai-llm-flash": (joyai, 5),
}


def rehearsal_model(name):
    reference, seed = MODELS[name]
    body = json.loads((ROOT / f"benchmark/configs/{name}.json").read_text())
    config = spec._merge(body, body["rehearsal"])
    return reference.engine_config(config, 64), reference.cast_params(reference.init_params(seed, config), jnp.float32)


@pytest.mark.parametrize("name", sorted(MODELS))
def test_a_grouped_prefill_is_the_dense_prefill_on_every_live_row(name, grouped):
    """``prefill[32]`` of a 21-token prompt: the live rows' logits, the
    K/V (or latent rows) and convolution rows the engine would write,
    and the per-expert counters equal the dense form's; what lies behind
    the prompt's length is read by nothing (and differs: zeros for the
    routed sum there)."""
    cfg, params = rehearsal_model(name)
    tokens = jnp.asarray(np.random.RandomState(2).randint(0, cfg.vocab_size, size=(1, 32)), jnp.int32)
    lengths = jnp.asarray([21], jnp.int32)
    grouped_counts, dense_counts = [], []
    got = decoder.prefill(params, tokens, lengths, cfg, counts=grouped_counts)
    grouped.undo()
    want = decoder.prefill(params, tokens, lengths, cfg, counts=dense_counts)
    assert len(got) == len(want) and len(grouped_counts) == len(cfg.expert_layers) > 0
    for g, w in zip(grouped_counts, dense_counts):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
    np.testing.assert_allclose(np.asarray(got[0])[:, :21], np.asarray(want[0])[:, :21], atol=2e-4)
    for g, w in zip(got[1:3], want[1:3]):  # [n, B, S, ...]: positions behind the length go to the scratch block
        np.testing.assert_allclose(np.asarray(g)[:, :, :21], np.asarray(w)[:, :, :21], atol=2e-5)
    for g, w in zip(got[3:], want[3:]):  # padded convolution rows [n, B, S + K - 1, E]: row t + K - 1 is z_t
        np.testing.assert_allclose(np.asarray(g)[:, :, : 21 + cfg.conv_kernel - 1], np.asarray(w)[:, :, : 21 + cfg.conv_kernel - 1], atol=2e-5)
    assert not np.allclose(np.asarray(got[0])[:, 21:], np.asarray(want[0])[:, 21:], atol=1e-3)


# the rule ----------------------------------------------------------------
# (held experts, k, the cell's slots): the three expert cells of BENCHMARK.json
CELLS = {
    "lfm2-8b-a1b": (32, 4, 64),
    "mellum2-12b": (64, 8, 48),
    "joyai-llm-flash": (16, 8, 32),
}


class _NoEnvironment(dict):
    def _refuse(self, *a, **k):
        raise AssertionError("the rule read the environment")
    __getitem__ = get = __contains__ = _refuse


@pytest.mark.parametrize("name", sorted(CELLS))
def test_the_form_is_chosen_by_the_shapes_alone(name, monkeypatch):
    """A decode step and a verify window at the cell's slots, and the
    buckets up to 512, keep the dense product; the two buckets of
    Mellum2's cell (1,536 and 2,048) take the grouped one; JoyAI's 16
    held experts, two for each a row chooses, keep the dense one there
    (the two forms read even on the chip); nothing but the shapes is
    asked."""
    held, k, slots = CELLS[name]
    monkeypatch.setattr(os, "environ", _NoEnvironment())
    form = functools.partial(expert_product.expert_form, held=held, k=k)
    for rows in (1, slots, slots * 5, 128, 256, 512):  # (a verify window: 1 + 4 drafts a slot)
        assert form(rows) == "dense", rows
    assert form(1536) == form(2048) == ("dense" if name == "joyai-llm-flash" else "grouped")
    monkeypatch.undo()
    # and on this backend the program takes the dense form whatever the rule says
    assert expert_product.expert_lowering(2048, held, k) == "dense"
    body = json.loads((ROOT / f"benchmark/configs/{name}.json").read_text())
    assert body["num_experts_per_tok"] == k and (body.get("num_experts") or body["n_routed_experts"]) == held


def decode_program(name, monkeypatch):
    """The StableHLO of the engine's own decode program, at rehearsal
    widths and 3 slots, taken at its first call."""
    cfg, params = rehearsal_model(name)
    eng = GenerationEngine(params, cfg, max_batch_slots=3, max_seq_len=64, block_size=8, prompt_buckets=(16, 32, 64))
    real, texts = eng._decode_jit, []

    def spy(*args, **kwargs):
        if not texts:
            texts.append(real.lower(*args, **kwargs).as_text())
        return real(*args, **kwargs)

    monkeypatch.setattr(eng, "_decode_jit", spy)
    eng.generate([[5, 9, 2, 77, 13]], SamplingParams(max_new_tokens=3))
    return texts[0]


# sha256 of the decode programs as ISSUE 40 left them (the same helper). They are the programs of commit d49c0a9
# (92f90174..., 63acac12..., d6aaf48a..., pinned here until then) plus 4 lines each and two more results: the positions
# and the counts advanced by the 0 / 1 active mask (two `add`s), and from them the two vectors the host used to send,
# as products with the mask (two `multiply`s: the context lengths, the positions with the inactive slots' at 0); with
# the values numbered anew, every other line is the parent's (CHANGES.md, PR 40)
PARENT_DECODE = {
    "lfm2-8b-a1b": "5229345364afea572e7f7e9d408f52a5029da564e69b6ba1e1010252b0318ca8",
    "mellum2-12b": "eb6d0db61fe504ffd6ace9ea3ae892925e53d561d9daac88ecf3ec7980bfb6c7",
    "joyai-llm-flash": "0483a28320e40537a595d837b4db4888ac3c2c8aa5be721642eff7468d322211",
}


@pytest.mark.parametrize("name", sorted(MODELS))
def test_the_decode_program_is_the_parent_s_text(name, monkeypatch):
    """Below the rule's row count ``expert_ffn`` emits the program it
    emitted before there was a second form: letter for letter."""
    text = decode_program(name, monkeypatch)
    assert "ragged" not in text and "custom_call" not in text.replace("custom_call @Sharding", "")
    assert hashlib.sha256(text.encode()).hexdigest() == PARENT_DECODE[name]


# the counter -------------------------------------------------------------
def test_grouped_calls_total_counts_the_grouped_prefills_and_no_decode_step(grouped):
    """Buckets 16 / 32 / 64 under a rule that groups from 32 rows: a
    prompt of 7 tokens is a dense prefill, those of 18 and 40 grouped
    ones, a decode step (3 rows) never; ``/v2/stats`` carries the count
    and the form per program, and the streams are the dense engine's."""
    cfg, params = rehearsal_model("mellum2-12b")
    prompts = [[int(t) for t in np.random.RandomState(s).randint(0, 512, size=n)] for s, n in ((1, 7), (2, 18), (3, 40))]
    make = lambda: GenerationEngine(params, cfg, max_batch_slots=3, max_seq_len=64, block_size=8,  # noqa: E731
                                    prompt_buckets=(16, 32, 64), prefix_cache=False)
    grouped.setattr(expert_product, "expert_form", lambda rows, *shape: "grouped" if rows >= 32 else "dense")
    eng = make()
    sched = ContinuousBatchingScheduler(eng)
    assert eng.expert_stats()["grouped_calls_total"] == 0
    outs = eng.generate(prompts, SamplingParams(max_new_tokens=5))
    stats = sched.stats.snapshot()["experts"]
    assert stats["prefill_calls_total"] == 3 and stats["decode_calls_total"] == eng.step_counts["decode"] > 0
    assert stats["grouped_calls_total"] == 2
    assert stats["forms"] == {"decode": "dense", "prefill[16]": "dense", "prefill[32]": "grouped", "prefill[64]": "grouped"}
    grouped.undo()
    dense = make()
    assert dense.generate(prompts, SamplingParams(max_new_tokens=5)) == outs
    assert dense.expert_stats()["grouped_calls_total"] == 0 and set(dense.expert_stats()["forms"].values()) == {"dense"}
    assert dense.expert_stats()["tokens_total_by_layer"] == eng.expert_stats()["tokens_total_by_layer"]
