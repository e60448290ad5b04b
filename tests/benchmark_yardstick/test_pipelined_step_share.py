"""``pipelined_step_share`` (PR 30): the share of a window's decode steps
that the scheduler's overlap pipeline dispatched, from the ``pipeline``
section of ``/v2/stats``. On a hand-worked pair of snapshots, None on a
program without the section (the parent of PR 30), and found by a
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


def _pipeline(decode, pipelined, reclaims=0, **drains):
    reasons = dict.fromkeys(("nonsteady", "finish", "pressure", "idle"), 0)
    return {"decode_steps_total": decode, "pipelined_steps_total": pipelined, "reclaims_total": reclaims,
            "drains_total": dict(reasons, **drains)}


def _ctx(opened, closed):
    return {"window": (100.0, 150.0), "stats_open": {"pipeline": opened}, "stats_close": {"pipeline": closed}}


@pytest.mark.parametrize("opened, closed, want", [
    # the lead-in's 800 steps (200 pipelined) are out: 750 of the window's 1,000
    (_pipeline(800, 200, 90, pressure=500), _pipeline(1800, 950, 4000, pressure=500, finish=210), 75.0),
    (_pipeline(10, 10), _pipeline(110, 10), 0.0),       # a window stepped sequentially throughout
    (_pipeline(0, 0), _pipeline(64, 64), 100.0),
], ids=["three-quarters", "none", "all"])
def test_the_share_is_the_growth_of_pipelined_over_the_growth_of_decode_steps(opened, closed, want):
    ctx = _ctx(opened, closed)
    assert layer_metrics.read("pipelined_step_share.served", ctx) == pytest.approx(want, rel=1e-12)
    assert layer_metrics.read("pipelined_step_share.itl", ctx) == pytest.approx(want, rel=1e-12)


@pytest.mark.parametrize("ctx", [
    {},
    {"window": (100.0, 150.0), "stats_open": {"preemptions": 1}, "stats_close": {"preemptions": 3}},  # the parent
    {"stats_open": {"pipeline": None}, "stats_close": {"pipeline": None}},  # the section died in a scrape
    _ctx(_pipeline(500, 100), _pipeline(500, 100)),  # no decode step in the window: no share to give
], ids=["empty", "no-section", "dead-section", "no-steps"])
def test_a_program_without_the_section_gives_nothing_and_does_not_raise(ctx):
    assert layer_metrics.read("pipelined_step_share.served", ctx) is None


def test_benchmark_json_asks_for_it_in_the_three_serving_cells():
    mine = {m["name"]: m for m in BENCH["per_layer"] if m["name"].startswith("pipelined_step_share.")}
    assert sorted(mine) == ["pipelined_step_share.itl", "pipelined_step_share.served"]
    assert [m["name"] for m in BENCH["per_layer"][-2:]] == sorted(mine)  # appended, nothing moved
    judged = {m["name"]: set(m.get("workloads", [])) for m in BENCH["end_to_end"]}
    for m in mine.values():
        assert (m["unit"], m["better"], m["source"], m["layer"]) == ("%", "higher", "program_counter", "scheduler")
        assert set(m["workloads"]) == judged[m["moves"]]
    served = {w["name"] for w in BENCH["workloads"] if w["config"] != "bert-large"}
    assert set().union(*(m["workloads"] for m in mine.values())) == served


def test_a_rehearsal_of_prompt_batch_finds_it():
    """``--rehearse --trace 1``: the real scheduler at tiny widths on the
    CPU through the real harness, so the section the reader looks for is
    the section the program writes."""
    out = subprocess.run(
        [sys.executable, str(ROOT / "benchmark" / "run.py"), "--workload", "gpt2-medium.prompt-batch", "--seed", "5",
         "--seconds", "3", "--trace", "1", "--rehearse"],
        cwd=ROOT, capture_output=True, text=True, timeout=300,
    )
    assert out.returncode == 0, out.stdout[-3000:] + out.stderr[-3000:]
    found = re.search(r"readers that found something: (\[.*\])", out.stdout)
    assert found, out.stdout[-3000:]
    assert "pipelined_step_share.served" in re.findall(r"'([^']+)'", found.group(1))
    # the window's line says how much of its list the closed loop used (the rehearsal's list: 8 + 400 x 4 = 1,608)
    used = re.search(r"(\d+) sent of 1608 listed \((\d+) %\)", out.stdout)
    assert used and int(used.group(2)) == round(100 * int(used.group(1)) / 1608), out.stdout[-3000:]
