"""The control of the decoder cells' ``correct``, at a size a test run
can hold (the cells' ``rehearsal`` groups, on the CPU), by the cells'
own statistic and their own limit: the program's tokens read under the
limit, and the plain reference in a lower precision, put in the
program's place (``benchmark/reference/control.py``), reads over it.

``benchmark/tools/control_check.py`` takes the same readings at the
cells' own size on the chip; those, and how the limit was set from them,
are in the cells' files (``correct``) and in PERF.md §2. At this size
the CPU multiplies float32 in float32, so the program reads exactly 0;
bfloat16 reads 1.1e-3 to 2.3e-3 and int8 weights 2.7e-3 to 5.1e-3 (my
CPU runs, PR 26), against limits of 1e-4 and 8e-5.
"""
import pathlib
import sys

import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from benchmark import spec  # noqa: E402


@pytest.fixture(scope="module")
def row():
    from benchmark.tools import control_check

    cache = {}

    def read(cell, seed):
        if (cell, seed) not in cache:
            cache[cell, seed] = control_check.readings(spec.load_cell(cell, rehearsal=True), seed)
        return cache[cell, seed]

    return read


@pytest.mark.parametrize("cell, seed", [("gpt2-medium.chat-steady", 1), ("gpt2-medium.chat-steady", 2600007930),
                                        ("gpt2-medium.prompt-batch", 3)])
@pytest.mark.parametrize("arm, correct", [("program", True), ("bfloat16", False), ("int8", False)])
def test_a_lower_precision_in_the_program_s_place_is_not_correct(row, cell, seed, arm, correct):
    got = row(cell, seed)
    assert got["limit"] == spec.load_cell(cell).workload["near_tie_gap_limit"]  # the cell's own limit, not one for the test
    read = got[arm]
    assert read["tokens"] >= 90 and read["near_ties"] >= 5
    assert (read["near_tie_gap"] <= got["limit"]) == correct, got
    if not correct:
        assert read["near_tie_gap"] > 3 * got["limit"] and read["off_argmax"] > 0, got


def test_reading_sums_every_gap_and_divides_by_the_near_ties():
    from benchmark.reference import decoder

    judged = {"gap": np.asarray([0.0, 0.002, 0.0, 0.0, 0.3]), "margin": np.asarray([0.5, 0.002, 0.009, 0.011, 0.2])}
    read = decoder.reading(judged)
    assert read["tokens"] == 5 and read["near_ties"] == 2 and read["off_argmax"] == 2
    assert read["near_tie_gap"] == pytest.approx(0.302 / 2) and read["worst_gap"] == pytest.approx(0.3)
    # no near-tie at all: the sum stands undivided, and a wrong token still shows
    assert decoder.reading({"gap": np.asarray([0.0, 0.2]), "margin": np.asarray([0.5, 0.4])})["near_tie_gap"] == pytest.approx(0.2)


def test_the_benchmark_s_weights_come_from_the_seed_alone():
    from benchmark.reference import decoder

    sizes = spec.load_cell("gpt2-medium.chat-steady", rehearsal=True).config
    a, b, c = (decoder.init_params(s, sizes) for s in (2600007930, 2600007930, 2600007931))
    same = lambda x, y: all(bool((p == q).all()) for p, q in zip(*map(__import__("jax").tree.leaves, (x, y))))  # noqa: E731
    assert same(a, b) and not same(a, c)
    assert a["tok_embed"].shape == (sizes["vocab_size"], sizes["n_embd"]) and len(a["layers"]) == sizes["n_layer"]
    assert a["layers"][0]["wq"].shape == (sizes["n_embd"], sizes["n_head"], sizes["n_embd"] // sizes["n_head"])
    lim = (6.0 / (2 * sizes["n_embd"])) ** 0.5
    assert float(abs(a["layers"][1]["wo"]).max()) <= lim and str(a["lm_head"].dtype) == "float32"


def test_int8_weights_sit_on_255_levels_per_output_channel():
    import jax.numpy as jnp

    from benchmark.reference import control

    rs = np.random.RandomState(0)
    params = {"tok_embed": jnp.asarray(rs.randn(10, 8), jnp.float32), "ln_g": jnp.ones(8),
              "layers": [{"wq": jnp.asarray(rs.randn(8, 2, 4), jnp.float32)}]}
    q = control.cast_params(params, "int8")
    assert q["ln_g"].dtype == jnp.bfloat16 and bool(jnp.all(q["ln_g"] == 1))
    wq = np.asarray(params["layers"][0]["wq"])
    levels = np.asarray(q["layers"][0]["wq"], np.float32) / (np.abs(wq).max(axis=0, keepdims=True) / 127.0)
    assert np.abs(levels - np.round(levels)).max() < 0.3 and np.abs(np.round(levels)).max() == 127  # bf16 holds 127 steps to ~0.4
    rows = np.asarray(q["tok_embed"], np.float32) / (np.abs(np.asarray(params["tok_embed"])).max(axis=1, keepdims=True) / 127.0)
    assert np.abs(np.round(rows)).max(axis=1).tolist() == [127.0] * 10  # a scale per row of an embedding table
