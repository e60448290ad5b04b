"""The five readers of PR 37 (``host_unspanned_share``,
``dispatch_upload_share``, ``dispatch_call_share``,
``dispatch_offcpu_share``, ``trace_record_share``): the scheduler
thread's conserved account, from ``step_phases`` and section ``loop`` of
``/v2/stats``. On a hand-worked pair of snapshots, None on a program
without the section or the keys (the parent of PR 37, which the driver
runs under these files), and found by a rehearsal of the real program
through the real harness. CPU only.
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
READERS = ["host_unspanned_share", "dispatch_upload_share", "dispatch_call_share", "dispatch_offcpu_share",
           "trace_record_share"]


def _phases(k):
    """``step_phases`` after ``k`` hundred iterations: a second lane of
    admit iterations, so that a reader has kinds to sum over."""
    lane = lambda n, s: {"count": 100 * n * k, "total_s": s * k}  # noqa: E731
    return {
        "decode.dispatch": lane(1, 2.0), "decode.dispatch.args": lane(1, 0.25), "decode.dispatch.upload": lane(1, 0.75),
        "decode.dispatch.call": lane(1, 0.5), "decode.post": lane(1, 0.125), "decode.stage": lane(1, 0.125),
        "decode.observe": lane(1, 0.0625), "decode.unspanned": lane(1, 0.25), "decode.execute": lane(1, 3.0),
        "admit.dispatch": lane(0.5, 1.0), "admit.dispatch.upload": lane(0.5, 0.25), "admit.dispatch.call": lane(0.5, 0.5),
        "admit.observe": lane(0.5, 0.0625), "admit.unspanned": lane(0.5, 0.25),
    }


def _loop(k, own_loop=True):
    out = {"working_total_s": 6.0 * k, "working_iterations_total": 150 * k, "empty_total_s": 0.5 * k,
           "empty_iterations_total": 40 * k, "cpu_total_s": 0.3 * k, "cpu_wall_total_s": 0.4 * k,
           "decode_dispatch_wall_total_s": 2.0 * k, "decode_dispatch_cpu_total_s": 1.5 * k}
    if own_loop:
        out.update(wall_total_s=10.0 * k, idle_wait_total_s=3.25 * k)
    return out


def _ctx(own_loop=True):
    """A window of 50 s between snapshot 1 and snapshot 3: everything grew by twice its unit."""
    engine = lambda k: {"phase_time_s": {"decode": {"dispatch": 8.0 * k, "execute": 20.0 * k, "readback": 1.0 * k}}}  # noqa: E731
    return {"window": (100.0, 150.0), "engine_open": engine(1), "engine_close": engine(3),
            "stats_open": {"step_phases": _phases(1), "loop": _loop(1, own_loop)},
            "stats_close": {"step_phases": _phases(3), "loop": _loop(3, own_loop)}}


@pytest.mark.parametrize("reader, seconds", [
    # unspanned of both kinds (0.5 + 0.5) and the loop's own: 20 - 12 - 1 - 6.5
    ("host_unspanned_share", 1.0 + 0.5),
    ("dispatch_upload_share", 1.5 + 0.5),
    ("dispatch_call_share", 1.0 + 1.0),
    # the sampled dispatches were off the CPU for a quarter of their wall (1 - 3.0 / 4.0): a quarter of all 16 s
    ("dispatch_offcpu_share", 0.25 * 16.0),
    ("trace_record_share", 0.125 + 0.125),
])
def test_each_share_is_the_growth_of_its_seconds_over_the_window(reader, seconds):
    for suffix in (".itl", ".served"):
        assert layer_metrics.read(reader + suffix, _ctx()) == pytest.approx(100.0 * seconds / 50.0, rel=1e-12)


def test_without_a_loop_of_its_own_the_remainder_is_the_iterations_alone():
    assert layer_metrics.read("host_unspanned_share.served", _ctx(own_loop=False)) == pytest.approx(100.0 * 1.0 / 50.0)


def test_a_child_is_not_mistaken_for_a_host_lane_phase():
    """``inside.phase_seconds`` matches on what follows the first dot:
    ``decode.dispatch.upload`` is no ``dispatch``, and the readers that
    were there read what they read."""
    ctx = _ctx()
    assert layer_metrics.read("host_dispatch_share.served", ctx) == pytest.approx(100.0 * (4.0 + 2.0) / 50.0)
    bare = _ctx()
    for snap in (bare["stats_open"], bare["stats_close"]):
        snap["step_phases"] = {k: v for k, v in snap["step_phases"].items() if k in ("decode.dispatch", "admit.dispatch")}
    assert layer_metrics.read("host_dispatch_share.served", bare) == layer_metrics.read("host_dispatch_share.served", ctx)
    assert layer_metrics.read("host_sched_share.served", ctx) == layer_metrics.read("host_sched_share.served", bare) == 0.0


def _parent():
    """What the parent of PR 37 leaves: ``step_phases`` with the host-lane
    keys it had, and no ``loop``."""
    old = lambda k: {"decode.dispatch": {"count": 100 * k, "total_s": 0.4 * k},  # noqa: E731
                     "decode.bookkeep": {"count": 100 * k, "total_s": 0.1 * k}}
    return {"window": (100.0, 150.0), "stats_open": {"step_phases": old(1)}, "stats_close": {"step_phases": old(2)}}


@pytest.mark.parametrize("reader", READERS)
@pytest.mark.parametrize("ctx", [
    {},
    _parent(),
    {"window": (100.0, 150.0), "stats_open": {"loop": None, "step_phases": None},
     "stats_close": {"loop": None, "step_phases": None}},  # the sections died in a scrape
], ids=["empty", "the-parent", "dead-sections"])
def test_a_program_without_the_account_gives_nothing_and_does_not_raise(reader, ctx):
    assert layer_metrics.read(reader + ".served", ctx) is None


def test_a_window_without_a_sampled_dispatch_has_no_fraction_to_give():
    still = dict(_ctx(), stats_close=_ctx()["stats_open"])
    assert layer_metrics.read("dispatch_offcpu_share.itl", still) is None
    assert layer_metrics.read("dispatch_upload_share.itl", still) == 0.0  # a span that did not grow is a share of nothing


def test_benchmark_json_asks_for_them_in_the_five_serving_cells():
    mine = [m for m in BENCH["per_layer"] if m["name"].split(".")[0] in READERS]
    assert [m["name"] for m in mine] == [f"{r}{s}" for r in READERS for s in (".itl", ".served")]
    assert BENCH["per_layer"][-10:] == mine  # appended, nothing moved
    judged = {m["name"]: set(m.get("workloads", [])) for m in BENCH["end_to_end"]}
    layers = {"host_unspanned_share": "scheduler", "trace_record_share": "scheduler"}
    for m in mine:
        reader, suffix = m["name"].split(".")
        assert (m["unit"], m["better"], m["source"]) == ("%", "lower", "program_span")
        assert m["layer"] == layers.get(reader, "engine")
        assert m["moves"] == {"itl": "itl_p50_ms", "served": "served_tokens_per_s"}[suffix]
        assert set(m["workloads"]) == judged[m["moves"]]
        assert (ROOT / "benchmark/layer_metrics" / f"{reader}.py").exists()
    served = {w["name"] for w in BENCH["workloads"] if w["config"] != "bert-large"}
    assert set().union(*(m["workloads"] for m in mine)) == served


def test_a_rehearsal_of_chat_steady_finds_all_five():
    """``--rehearse --trace 1``: the real scheduler at tiny widths on the
    CPU through the real harness, so the keys the readers look for are the
    keys the program writes."""
    out = subprocess.run(
        [sys.executable, str(ROOT / "benchmark" / "run.py"), "--workload", "gpt2-medium.chat-steady", "--seed",
         "3000000037", "--seconds", "3", "--trace", "1", "--rehearse"],
        cwd=ROOT, capture_output=True, text=True, timeout=300,
    )
    assert out.returncode == 0, out.stdout[-3000:] + out.stderr[-3000:]
    found = re.search(r"readers that found something: (\[.*\])", out.stdout)
    assert found, out.stdout[-3000:]
    assert {r + ".itl" for r in READERS} <= set(re.findall(r"'([^']+)'", found.group(1)))
