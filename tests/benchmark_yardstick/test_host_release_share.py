"""``host_release_share`` (PR 38): the seconds of the ``ff.sched.release``
spans, the drop of a consumed decode step's handle, over the window. On a
hand-worked pair of snapshots, None on a program without the span (the
parent of PR 37), asked for in the five serving cells, and found by a
rehearsal of the real program through the real harness. CPU only.
"""
import json
import pathlib
import re
import subprocess
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from benchmark import layer_metrics  # noqa: E402

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAMES = ["host_release_share.itl", "host_release_share.served"]


def _ctx(release=True):
    """A window of 50 s over which everything doubled: pipelined decode
    iterations that drop a handle each, admissions that drain one."""
    def phases(k):
        out = {"decode.dispatch": {"count": 100 * k, "total_s": 2.0 * k}, "decode.bookkeep": {"count": 100 * k, "total_s": 0.5 * k},
               "admit.dispatch": {"count": 10 * k, "total_s": 1.0 * k}}
        if release:
            out.update({"decode.release": {"count": 100 * k, "total_s": 1.25 * k}, "admit.release": {"count": 10 * k, "total_s": 0.25 * k}})
        return out
    return {"window": (100.0, 150.0), "stats_open": {"step_phases": phases(1)}, "stats_close": {"step_phases": phases(2)}}


@pytest.mark.parametrize("name", NAMES)
def test_the_share_is_the_growth_of_the_release_spans_of_every_kind_over_the_window(name):
    assert layer_metrics.read(name, _ctx()) == pytest.approx(100.0 * (1.25 + 0.25) / 50.0, rel=1e-12)


@pytest.mark.parametrize("name", NAMES)
@pytest.mark.parametrize("ctx", [
    {},
    _ctx(release=False),
    {"window": (100.0, 150.0), "stats_open": {"step_phases": None}, "stats_close": {"step_phases": None}},
], ids=["empty", "before-the-span", "dead-section"])
def test_a_program_without_the_span_gives_nothing_and_does_not_raise(name, ctx):
    assert layer_metrics.read(name, ctx) is None


def test_a_span_that_did_not_grow_is_a_share_of_nothing():
    still = dict(_ctx(), stats_close=_ctx()["stats_open"])
    assert layer_metrics.read("host_release_share.served", still) == 0.0


def test_it_is_no_part_of_the_shares_that_were_there():
    """``release`` is a host-lane phase of its own: the scheduler's and the
    dispatch's shares read what they read without it."""
    with_it, without = _ctx(), _ctx(release=False)
    for name in ("host_sched_share.served", "host_dispatch_share.served"):
        assert layer_metrics.read(name, with_it) == layer_metrics.read(name, without)


def test_benchmark_json_asks_for_it_in_the_five_serving_cells():
    mine = [m for m in BENCH["per_layer"] if m["name"].split(".")[0] == "host_release_share"]
    assert [m["name"] for m in mine] == NAMES
    judged = {m["name"]: set(m.get("workloads", [])) for m in BENCH["end_to_end"]}
    for m in mine:
        assert (m["unit"], m["better"], m["source"], m["layer"]) == ("%", "lower", "program_span", "scheduler")
        assert m["moves"] == {"itl": "itl_p50_ms", "served": "served_tokens_per_s"}[m["name"].split(".")[1]]
        assert set(m["workloads"]) == judged[m["moves"]]
    served = {w["name"] for w in BENCH["workloads"] if w["config"] != "bert-large"}
    assert set().union(*(m["workloads"] for m in mine)) == served
    assert (ROOT / "benchmark/layer_metrics/host_release_share.py").exists()


def test_a_rehearsal_of_gen_batch_finds_it():
    """``--rehearse --trace 1``: the real scheduler at tiny widths on the
    CPU through the real harness, so the key the reader looks for is the
    key the program writes."""
    out = subprocess.run(
        [sys.executable, str(ROOT / "benchmark" / "run.py"), "--workload", "lfm2-8b-a1b.gen-batch", "--seed",
         "3800000041", "--seconds", "3", "--trace", "1", "--rehearse"],
        cwd=ROOT, capture_output=True, text=True, timeout=300,
    )
    assert out.returncode == 0, out.stdout[-3000:] + out.stderr[-3000:]
    found = re.search(r"readers that found something: (\[.*\])", out.stdout)
    assert found, out.stdout[-3000:]
    assert "host_release_share.served" in set(re.findall(r"'([^']+)'", found.group(1)))
