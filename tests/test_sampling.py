"""The sampling transform (ISSUE 28): a step does the work its batch's
sampling parameters ask for, decided inside the one compiled program.

  * every branch returns what the formula it replaced returns, token
    for token and value for value (the formula is kept here VERBATIM as
    the oracle)
  * in the lowered decode program every sort of the vocabulary sits in
    a conditional's branch, and a change of branch retraces nothing
  * ``/v2/stats`` ``sampling`` counts decode steps by branch
"""
import json
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flexflow_tpu.generation import (
    ContinuousBatchingScheduler,
    GenerationEngine,
    SamplingParams,
    init_decoder_params,
)
from flexflow_tpu.generation import engine as engine_mod
from flexflow_tpu.generation.engine import (
    NEG_INF,
    _sample,
    derive_keys,
    derive_window_keys,
    sampling_branch,
    topk_scaled_logits,
)
from flexflow_tpu.generation.speculative import sampling as spec_sampling
from flexflow_tpu.models.transformer import TransformerConfig

from conftest import FakeClock  # noqa: E402

pytestmark = pytest.mark.generation

K = 64  # a filter as wide as clients send
V = K + 40
B, W = 6, 3


# -- the oracle: engine.py's transform as it stood before ISSUE 28 -----------


def oracle_topk_scaled_logits(logits, temps, top_ks):
    v = logits.shape[-1]
    safe_t = jnp.where(temps <= 0.0, 1.0, temps)
    scaled = logits / safe_t[..., None]
    k = jnp.where(top_ks <= 0, v, jnp.clip(top_ks, 1, v)).astype(jnp.int32)
    sorted_desc = jnp.flip(jnp.sort(scaled, axis=-1), axis=-1)
    thresh = jnp.take_along_axis(sorted_desc, k[..., None] - 1, axis=-1)
    return jnp.where(scaled >= thresh, scaled, NEG_INF)


def oracle_sample(logits, temps, top_ks, keys):
    v = logits.shape[-1]
    greedy = temps <= 0.0
    masked = oracle_topk_scaled_logits(logits, temps, top_ks)
    gumbel = jax.vmap(lambda key: jax.random.gumbel(key, (v,)))(keys)
    sampled = jnp.argmax(masked + gumbel, axis=-1)
    return jnp.where(greedy, jnp.argmax(logits, axis=-1), sampled).astype(jnp.int32)


NEW_VALUES, OLD_VALUES = jax.jit(topk_scaled_logits), jax.jit(oracle_topk_scaled_logits)
NEW_TOKENS, OLD_TOKENS = jax.jit(_sample), jax.jit(oracle_sample)
ACCEPT = jax.jit(lambda *a: spec_sampling.speculative_accept(*a))


def _logits(rows: int, poison: bool) -> np.ndarray:
    """Logits on a grid of tenths, so that every row holds exact ties
    (at its threshold too, whatever k); row 0 ties its three best, row 1
    carries a grammar mask and, with ``poison``, row 2 is NaN in places
    and row 3 everywhere (the fault plan's bias: ``ok`` false)."""
    rs = np.random.RandomState(rows)
    x = np.round(rs.randn(rows, V) * 2.0, 1).astype(np.float32)
    x[0, [5, 50, 90]] = x[0].max() + 1.0
    if rows > 1:
        x[1, 20:120] = NEG_INF
    if poison and rows > 3:
        x[2, 7:11] = np.nan
        x[3, :] = np.nan
    return x


def _temps(kind: str) -> np.ndarray:
    sampled = np.asarray([0.7, 1.0, 1.3, 0.4, 2.0, 0.9], np.float32)
    return {
        "greedy": np.zeros(B, np.float32),
        "sampled": sampled,
        "mixed": np.where(np.arange(B) % 2 == 0, 0.0, sampled).astype(np.float32),
    }[kind]


def _all(k: int) -> np.ndarray:
    return np.full(B, k, np.int32)


# name -> (temps, top_ks, poisoned row?, the branch the step takes)
CASES = {
    "all_greedy": (_temps("greedy"), _all(0), False, "greedy"),
    "all_greedy_with_top_k": (_temps("greedy"), _all(5), False, "greedy"),
    "all_temperature": (_temps("sampled"), _all(0), False, "plain"),
    "mixed_greedy_temperature": (_temps("mixed"), _all(0), False, "plain"),
    "top_k_1": (_temps("sampled"), _all(1), False, "sort"),
    "top_k_K-1": (_temps("sampled"), _all(K - 1), False, "sort"),
    "top_k_K": (_temps("sampled"), _all(K), False, "sort"),
    "top_k_K+1": (_temps("sampled"), _all(K + 1), False, "sort"),
    "top_k_V": (_temps("sampled"), _all(V), False, "sort"),
    "top_k_over_V": (_temps("sampled"), _all(V + 9), False, "sort"),
    "mixed_top_k_within_K": (_temps("mixed"), np.asarray([3, 0, 40, K, 1, 0], np.int32), False, "sort"),
    "mixed_top_k_beyond_K": (_temps("mixed"), np.asarray([3, 0, 40, K + 1, 1, -2], np.int32), False, "sort"),
    "ties_at_threshold": (_temps("sampled"), _all(2), False, "sort"),
    "nan_row_plain": (_temps("sampled"), _all(0), True, "plain"),
    "nan_row_top_k": (_temps("sampled"), np.asarray([3, 9, 2, 5, 6, K], np.int32), True, "sort"),
    "nan_row_sort": (_temps("mixed"), np.asarray([3, 9, 2, 5, 6, V], np.int32), True, "sort"),
    "nan_row_greedy": (_temps("greedy"), _all(0), True, "greedy"),
}


def _equal(a, b) -> bool:
    return bool(np.array_equal(np.asarray(a), np.asarray(b), equal_nan=True))


@pytest.mark.parametrize("case", list(CASES))
@pytest.mark.parametrize("shape", ["BV", "1V", "BWV"])
def test_transform_equals_the_formula_it_replaced(case, shape, monkeypatch):
    temps, top_ks, poison, branch = CASES[case]
    if shape == "1V":
        # the prefill programs' one row: each row of the case in turn
        for row in range(B):
            x = jnp.asarray(_logits(B, poison)[row:row + 1])
            t, k = jnp.asarray(temps[row:row + 1]), jnp.asarray(top_ks[row:row + 1])
            keys = derive_keys(jnp.asarray([row + 3], jnp.uint32), jnp.asarray([row], jnp.int32))
            assert _equal(NEW_VALUES(x, t, k), OLD_VALUES(x, t, k)), row
            assert _equal(NEW_TOKENS(x, t, k, keys), OLD_TOKENS(x, t, k, keys)), row
        return
    assert sampling_branch(temps, top_ks) == branch
    t, k = jnp.asarray(temps), jnp.asarray(top_ks)
    if shape == "BV":
        x = jnp.asarray(_logits(B, poison))
        keys = derive_keys(jnp.arange(B, dtype=jnp.uint32) + 3, jnp.arange(B, dtype=jnp.int32))
        assert _equal(NEW_VALUES(x, t, k), OLD_VALUES(x, t, k))
        assert _equal(NEW_TOKENS(x, t, k, keys), OLD_TOKENS(x, t, k, keys))
        return
    # the verify window: the values, and what acceptance makes of them
    x = jnp.asarray(np.stack([_logits(B, poison), _logits(B, False)[::-1], _logits(B, poison) * 0.5], axis=1))
    tw, kw = jnp.broadcast_to(t[:, None], (B, W)), jnp.broadcast_to(k[:, None], (B, W))
    assert _equal(NEW_VALUES(x, tw, kw), OLD_VALUES(x, tw, kw))
    keys = derive_window_keys(jnp.arange(B, dtype=jnp.uint32) + 3, jnp.arange(B, dtype=jnp.int32), W)
    drafts = jnp.argmax(x[:, : W - 1], axis=-1).astype(jnp.int32)
    n_draft = jnp.asarray([0, 1, 2, 2, 0, 1], jnp.int32)
    new = ACCEPT(x, drafts, n_draft, t, k, keys)
    monkeypatch.setattr(spec_sampling, "topk_scaled_logits", oracle_topk_scaled_logits)
    old = jax.jit(lambda *a: spec_sampling.speculative_accept(*a))(x, drafts, n_draft, t, k, keys)
    assert _equal(new[0], old[0]) and _equal(new[1], old[1])


def test_speculation_imports_the_engines_transform():
    assert spec_sampling.topk_scaled_logits is engine_mod.topk_scaled_logits


# -- the lowered decode program ----------------------------------------------

CFG = TransformerConfig(
    num_layers=2, hidden_size=32, num_heads=4, ff_size=64,
    seq_length=64, vocab_size=V, causal=True,
)


@pytest.fixture(scope="module")
def params():
    return init_decoder_params(jax.random.key(0), CFG)


def _engine(params, slots=3):
    return GenerationEngine(params, CFG, max_batch_slots=slots, block_size=8, prompt_buckets=(8, 16))


SORTS = ("stablehlo.sort", "chlo.top_k")  # however a later edit finds the k-th
BRANCHES = ("stablehlo.case", "stablehlo.if")


def _unconditional_sorts(module) -> list:
    """Sorts of the lowered module that a call of ``main`` reaches
    without entering a conditional's branch."""
    funcs = {f.name.value: f for f in module.body.operations}
    found, seen = [], set()

    def walk(op, func_name):
        for region in op.regions:
            for block in region.blocks:
                for inner in block.operations:
                    name = inner.operation.name
                    if name in SORTS:
                        found.append(f"{name} in @{func_name}")
                    elif name == "func.call":
                        visit(str(inner.operation.attributes["callee"]).lstrip("@"))
                    elif name not in BRANCHES:
                        walk(inner.operation, func_name)

    def visit(func_name):
        if func_name not in seen:
            seen.add(func_name)
            walk(funcs[func_name].operation, func_name)

    visit("main")
    return found


def test_decode_program_sorts_only_inside_a_conditional(params):
    from tests.test_generation import _step_arguments

    eng = _engine(params)
    args = _step_arguments(eng, "decode", eng.cache.k, lambda dt, *s: np.zeros(s, dt))
    module = eng._decode_jit.lower(eng.params, *args).compiler_ir("stablehlo")
    text = str(module)
    assert "stablehlo.sort" in text  # the branch is there ...
    assert _unconditional_sorts(module) == []  # ... and nowhere else
    # the walk sees a sort that no branch guards
    plain = jax.jit(lambda x: jnp.sort(x, axis=-1)).lower(np.zeros((2, 8), np.float32))
    assert _unconditional_sorts(plain.compiler_ir("stablehlo")) != []


def test_one_decode_program_whatever_the_sampling_mix(params):
    eng = _engine(params)
    mixes = [
        SamplingParams(max_new_tokens=3),
        SamplingParams(max_new_tokens=3, temperature=0.8, seed=1),
        SamplingParams(max_new_tokens=3, temperature=0.8, top_k=5, seed=2),
        SamplingParams(max_new_tokens=3),
    ]
    seen = dict(eng.sampling_steps)
    for sp, branch in zip(mixes, ["greedy", "plain", "sort", "greedy"]):
        eng.generate([[1, 2, 3], [4, 5, 6, 7]], sp)
        grew = {b for b, n in eng.sampling_steps.items() if n > seen[b]}
        assert grew == {branch}, (sp, eng.sampling_steps)
        seen = dict(eng.sampling_steps)
        assert eng.trace_counts["decode"] == 1 and eng.recompiles() == {}, eng.trace_counts
    assert sum(eng.sampling_steps.values()) == eng.step_counts["decode"]


# -- /v2/stats "sampling" ----------------------------------------------------


def test_sampling_totals_follow_the_batch(params):
    """A sampled request joins a greedy batch and leaves it again: the
    steps move to its branch and back, the totals only grow and add up
    to the decode steps issued."""
    eng = _engine(params)
    sched = ContinuousBatchingScheduler(eng, clock=FakeClock())
    section = lambda: sched.stats.snapshot()["sampling"]
    assert section() == {f"{b}_steps_total": 0 for b in ("greedy", "plain", "sort")}
    history = [section()]

    def step_until(done, limit=40):
        for _ in range(limit):
            if done():
                return
            sched.step()
            history.append(section())
            assert sum(history[-1].values()) == eng.step_counts["decode"]
        raise AssertionError("the scheduler made no progress")

    long_greedy = sched.submit([1, 2, 3], SamplingParams(max_new_tokens=24))
    step_until(lambda: len(long_greedy._request.generated) >= 3)
    alone = section()
    assert alone["greedy_steps_total"] > 0 and sum(alone.values()) == alone["greedy_steps_total"]

    sampled = sched.submit([4, 5], SamplingParams(max_new_tokens=4, temperature=0.9, top_k=7, seed=5))
    step_until(sampled.done)
    joined = section()
    assert joined["sort_steps_total"] >= 3 and joined["plain_steps_total"] == 0

    step_until(lambda: len(long_greedy._request.generated) >= 12)
    plain = sched.submit([7], SamplingParams(max_new_tokens=3, temperature=0.9, seed=7))
    step_until(plain.done)
    left = section()
    assert left["plain_steps_total"] >= 2 and left["sort_steps_total"] == joined["sort_steps_total"]
    step_until(long_greedy.done)
    after = section()
    assert {b for b in after if after[b] > left[b]} <= {"greedy_steps_total"}
    for a, b in zip(history, history[1:]):
        assert all(b[key] >= a[key] for key in a), (a, b)
    assert sum(after.values()) == eng.step_counts["decode"]


def test_http_stats_carry_the_sampling_section(params):
    from flexflow_tpu.serving import InferenceServer
    from flexflow_tpu.serving.generation import GenerationModel

    srv = InferenceServer(port=0)
    srv.register_generation(GenerationModel(_engine(params, slots=2), name="lm"))
    srv.start()
    try:
        base = f"http://127.0.0.1:{srv.port}"
        post = lambda body: json.load(urllib.request.urlopen(urllib.request.Request(
            f"{base}/v2/models/lm/generate", data=json.dumps(body).encode(),
            headers={"Content-Type": "application/json"}), timeout=60))
        read = lambda: json.load(urllib.request.urlopen(f"{base}/v2/stats", timeout=30))["generation"]["lm"]["sampling"]
        post({"prompt": [1, 2, 3], "max_new_tokens": 5})
        first = read()
        assert first["greedy_steps_total"] >= 4 and sum(first.values()) == first["greedy_steps_total"]
        post({"prompt": [1, 2, 3], "max_new_tokens": 5, "temperature": 0.8, "seed": 3})
        second = read()
        assert second["plain_steps_total"] >= 4 and second["greedy_steps_total"] == first["greedy_steps_total"]
    finally:
        srv.stop()
