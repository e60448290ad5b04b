"""The readers of what the program counts from inside (PR 23): each on a
hand-worked ``ctx``, None where its counters are absent (a commit from
before the counters), and rehearsals of a serving and a training cell in
which every such reader finds something. CPU only.
"""
import importlib
import pathlib
import re
import subprocess
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from benchmark import inside, layer_metrics  # noqa: E402


def _window(count, total):
    return {"count": count, "sum_s": total, "p50_s": 0.0, "p95_s": 9.9, "count_total": count, "sum_total_s": total}


def _ctx():
    """A 10 s window. Between its two snapshots: 4 requests admitted
    after waiting 0.2 s together; their 4 ingress spans took 8 ms and
    their 4 first writes 2 ms; 3 admissions held running streams 0.3 s
    together; evictions took 0.5 s and swap-ins (first seen inside the
    window) 0.25 s; the step anatomy grew as the comments say."""
    phases = lambda k: {  # noqa: E731
        "decode.dispatch": {"count": 100 * k, "total_s": 0.4 * k},
        "admit.dispatch": {"count": 10 * k, "total_s": 0.1 * k},   # dispatch: +0.5 s = 5 %
        "decode.readback": {"count": 100 * k, "total_s": 0.2 * k},  # readback: +0.2 s = 2 %
        "decode.block": {"count": 100 * k, "total_s": 7.0 * k},     # not the host's work
        "decode.execute": {"count": 100 * k, "total_s": 8.0 * k},
        "decode.schedule": {"count": 100 * k, "total_s": 0.05 * k},
        "decode.bookkeep": {"count": 100 * k, "total_s": 0.05 * k},
        "decode.housekeep": {"count": 100 * k, "total_s": 0.1 * k},
        "admit.admit": {"count": 10 * k, "total_s": 0.3 * k},
        "admit.prefix_plan": {"count": 10 * k, "total_s": 0.2 * k},  # scheduler: +0.7 s = 7 %
    }
    return {
        "window": (100.0, 110.0),
        "stats_open": {
            "queue_time": _window(6, 0.6), "http_ingress": _window(6, 0.012),
            "http_first_write": _window(6, 0.003), "admit_stall": _window(2, 0.1),
            "cache_offload": _window(5, 1.0), "step_phases": phases(1),
        },
        "stats_close": {
            "queue_time": _window(10, 0.8), "http_ingress": _window(10, 0.020),
            "http_first_write": _window(10, 0.005), "admit_stall": _window(5, 0.4),
            "cache_offload": _window(9, 1.5), "cache_restore": _window(2, 0.25),
            "step_phases": phases(2),
        },
    }


@pytest.mark.parametrize("reader, want", [
    ("queue_wait_mean_ms", 50.0),           # 0.2 s over 4 requests
    ("frontend_inside_mean_ms", 2.5),       # (8 + 2) ms over 4 requests
    ("admit_stall_mean_ms", 100.0),         # 0.3 s over 3 admissions
    ("host_dispatch_share", 5.0),
    ("host_readback_share", 2.0),
    ("host_sched_share", 7.0),
    ("cache_offload_share", 7.5),           # (0.5 + 0.25) s of 10 s
])
def test_inside_reader_arithmetic(reader, want):
    mod = importlib.import_module(f"benchmark.layer_metrics.{reader}")
    assert mod.__doc__ and mod.read(_ctx()) == pytest.approx(want, rel=1e-9)


def _before_the_counters():
    """What a commit from before PR 23 hands the readers: rolling
    windows without totals, no ``step_phases``, no new windows."""
    ctx = _ctx()
    for snap in (ctx["stats_open"], ctx["stats_close"]):
        for name in list(snap):
            if name == "queue_time":
                snap[name] = {"count": 10, "sum_s": 0.8, "p95_s": 0.25}
            else:
                del snap[name]
    return ctx


@pytest.mark.parametrize("reader", [
    "queue_wait_mean_ms", "frontend_inside_mean_ms", "admit_stall_mean_ms", "host_dispatch_share",
    "host_readback_share", "host_sched_share", "cache_offload_share",
])
@pytest.mark.parametrize("ctx", [dict, _before_the_counters], ids=["empty", "before-the-counters"])
def test_inside_readers_find_nothing_without_their_counters(reader, ctx):
    assert layer_metrics.read(reader, ctx()) is None


def test_a_window_first_observed_inside_the_run_counts_from_zero():
    ctx = _ctx()
    assert inside.window_delta(ctx, "cache_restore") == (2, 0.25)
    assert inside.window_delta(ctx, "cache_offload") == (4, 0.5)
    assert inside.window_delta(ctx, "never_observed") is None
    # nothing admitted in the window: no mean to give
    ctx["stats_close"]["queue_time"] = ctx["stats_open"]["queue_time"]
    assert layer_metrics.read("queue_wait_mean_ms", ctx) is None


INSIDE = {
    # (whether one of a few seconds' open-loop admissions finds a stream
    # decoding is the seed's and the machine's to say: admit_stall is
    # asked of the closed loop, where every admission does)
    "gpt2-medium.chat-steady": {
        "queue_wait_mean_ms", "frontend_inside_mean_ms", "host_dispatch_share.itl", "host_readback_share.itl",
        "host_sched_share.itl",
    },
    "gpt2-medium.prompt-batch": {
        "admit_stall_mean_ms.served", "host_dispatch_share.served", "host_readback_share.served",
        "host_sched_share.served", "cache_offload_share.served",
    },
    # no new reader in a training cell: the rehearsal is there for the
    # trainer's and the loader's spans, opened under the harness's trace
    "bert-large.mlm-s512": set(),
}


@pytest.mark.parametrize("cell", sorted(INSIDE))
def test_a_rehearsal_finds_every_inside_reader(cell):
    """``--rehearse --trace 1``: the real program at tiny widths on the
    CPU, through the real harness, so the keys the readers look for are
    the keys the program writes."""
    out = subprocess.run(
        [sys.executable, str(ROOT / "benchmark" / "run.py"), "--workload", cell, "--seed", "3",
         "--seconds", "3", "--trace", "1", "--rehearse"],
        cwd=ROOT, capture_output=True, text=True, timeout=300,
    )
    assert out.returncode == 0, out.stdout[-3000:] + out.stderr[-3000:]
    found = re.search(r"readers that found something: (\[.*\])", out.stdout)
    assert found, out.stdout[-3000:]
    names = set(re.findall(r"'([^']+)'", found.group(1)))
    assert INSIDE[cell] <= names, sorted(INSIDE[cell] - names)
