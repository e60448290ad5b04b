"""The ten readers of PR 50 that ``move`` ``setup_s`` (``setup_import_s``,
``setup_trace_lower_s``, ``setup_compile_s``, ``setup_cache_load_s``,
``setup_cache_misses``, ``setup_engine_build_s``, ``search_calibrate_s``,
``search_unity_s``, ``search_init_s``, ``setup_spanned_share``) over the
program's start-up account (``benchmark/startup.py``): on a hand-made
account, None on a ``ctx`` without one (the parent of PR 50, which the
driver runs under these files), the cut at ``setup_s`` where the account
is read in the program's own process, the three ``search_*`` summing to
``search_s`` on a tiny ``FFModel.compile``, and found by rehearsals of
the real program through the real harness. CPU only.
"""
import json
import pathlib
import re
import subprocess
import sys
import time

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from benchmark import layer_metrics, startup  # noqa: E402

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
EVERY_CELL = ["setup_import_s", "setup_trace_lower_s", "setup_compile_s", "setup_cache_load_s", "setup_cache_misses"]
SERVING = ["setup_engine_build_s"]
TRAINING = ["search_calibrate_s", "search_unity_s", "search_init_s"]
READERS = EVERY_CELL + SERVING + TRAINING + ["setup_spanned_share"]


def _program(**parts):
    base = {"calls": 1, "trace_s": 0.0, "lower_s": 0.0, "compile_s": 0.0, "cache_load_s": 0.0, "cache_hit": None,
            "run_s": None, "at_s": 20.0}
    return dict(base, **parts)


def _account():
    """A process of 80 s of set-up: imported in 6 s, the runtime up in 4,
    a search of 12 s (0.5 calibrating, 9 searching, 1.5 on candidates, 1
    of its own), mesh 0.25, executor 0.75, parameters 5, an engine built
    in 3; three programs, one of which the cache did not hold."""
    phase = lambda total, kids=0.0, n=1: {"count": n, "total_s": total, "self_s": total - kids}  # noqa: E731
    return {
        "origin": "process_start", "now_s": 80.0,
        "phases": {
            "import": phase(6.0), "backend": phase(4.0), "engine_build": phase(3.0),
            "search": phase(12.0, 11.0), "search.calibrate": phase(0.5), "search.unity": phase(9.0),
            "search.candidates": phase(1.5), "mesh": phase(0.25), "executor": phase(0.75), "param_init": phase(5.0),
        },
        "programs": {
            "decode": _program(trace_s=4.0, lower_s=3.0, cache_load_s=1.5, cache_hit=True, run_s=0.5),
            "prefill[512]": _program(calls=2, trace_s=2.0, lower_s=1.0, compile_s=20.0, cache_hit=False, run_s=1.0),
            "init_params": _program(trace_s=0.25, lower_s=0.25, cache_load_s=0.5, cache_hit=True),
        },
        "programs_dropped": 0,
        "cache": {"requests": 12, "hits": 9, "misses": 3, "missed": ["jit__prefill_impl"]},
        "spanned_s": 60.0, "spans": [],
    }


def _ctx():
    return {"setup_s": 80.0, "stats_open": {"startup": _account()}}


@pytest.mark.parametrize("reader, want", [
    ("setup_import_s", 6.0 + 4.0),
    ("setup_trace_lower_s", (4.0 + 3.0) + (2.0 + 1.0) + (0.25 + 0.25)),
    ("setup_compile_s", 20.0),
    ("setup_cache_load_s", 1.5 + 0.5),
    ("setup_cache_misses", 12 - 9),
    ("setup_engine_build_s", 3.0),
    ("search_calibrate_s", 0.5),
    ("search_unity_s", 12.0 - 0.5),  # the search less its calibration: unity, candidates, its own lines
    ("search_init_s", 0.25 + 0.75 + 5.0),
    ("setup_spanned_share", 100.0 * 60.0 / 80.0),
])
def test_each_reader_over_a_hand_made_account(reader, want):
    assert layer_metrics.read(reader, _ctx()) == pytest.approx(want, rel=1e-12)


def test_the_search_readers_are_the_parts_of_the_search_and_its_neighbours():
    ctx, ph = _ctx(), _account()["phases"]
    parts = sum(layer_metrics.read(r, ctx) for r in TRAINING)
    assert parts == pytest.approx(sum(ph[k]["total_s"] for k in ("search", "mesh", "executor", "param_init")))


def test_a_warm_run_compiles_nothing_exactly_when_nothing_missed():
    warm = _ctx()
    acct = warm["stats_open"]["startup"]
    acct["programs"]["prefill[512]"].update(compile_s=0.0, cache_load_s=2.0, cache_hit=True)
    acct["cache"].update(hits=12, misses=0, missed=[])
    assert layer_metrics.read("setup_compile_s", warm) == 0.0 and layer_metrics.read("setup_cache_misses", warm) == 0
    assert layer_metrics.read("setup_compile_s", _ctx()) > 0.0 and layer_metrics.read("setup_cache_misses", _ctx()) > 0


def test_a_phase_the_process_never_opened_reads_nothing_and_an_empty_one_zero():
    ctx = _ctx()
    for name in ("search", "search.calibrate", "search.unity", "search.candidates", "backend"):
        del ctx["stats_open"]["startup"]["phases"][name]
    assert layer_metrics.read("search_calibrate_s", ctx) is None and layer_metrics.read("search_unity_s", ctx) is None
    assert layer_metrics.read("setup_import_s", ctx) == 6.0  # rehearsals list their devices outside the package
    ctx["stats_open"]["startup"]["programs"] = {}
    assert layer_metrics.read("setup_trace_lower_s", ctx) == 0.0


@pytest.mark.parametrize("reader", READERS)
@pytest.mark.parametrize("ctx", [
    {},
    {"setup_s": 80.0, "stats_open": {"step_phases": {}, "loop": {}}},  # a served parent: stats, no such section
    {"setup_s": 80.0, "stats_open": {"startup": None}},  # the section died in a scrape
    {"setup_s": 80.0, "stats_open": None},
], ids=["empty", "the-parent", "dead-section", "no-stats"])
def test_a_program_without_the_account_gives_nothing_and_does_not_raise(reader, ctx):
    assert layer_metrics.read(reader, ctx) is None


def test_a_trainer_s_parent_has_no_account_to_import(monkeypatch):
    from flexflow_tpu.obs import steptrace

    monkeypatch.delattr(steptrace, "GLOBAL_STARTUP")  # the module of a commit before PR 50
    ctx = {"setup_s": 40.0, "train": {"search_s": 9.0}}
    assert startup.account(ctx) is None
    assert [layer_metrics.read(r, ctx) for r in READERS] == [None] * len(READERS)


def test_in_the_program_s_own_process_the_account_is_cut_at_the_window_s_opening(monkeypatch):
    """The trainer's ``ctx`` carries no stats: its readers read the
    process's account, less what came after ``setup_s`` (the reference's
    own compiles, whatever ran in the window)."""
    from flexflow_tpu.obs import steptrace

    t = time.perf_counter()
    acct = steptrace.StartupAccount(origin=t)
    monkeypatch.setattr(steptrace, "GLOBAL_STARTUP", acct)
    acct.note_span("import", t, t + 5.0)
    acct.note_span("search", t + 6.0, t + 16.0)
    acct.note_span("search.calibrate", t + 6.0, t + 7.0, parent="search")
    acct.note_span("param_init", t + 16.0, t + 20.0)
    acct.note_span("executor", t + 45.0, t + 50.0)  # a second model, built for the reference: after the window opened
    rec = lambda name, at, **parts: dict(  # noqa: E731
        {"name": name, "at_s": at, "end_s": at + 2.0, "trace_s": 0.0, "lower_s": 0.0, "compile_s": 0.0,
         "cache_load_s": 0.0, "cache_hit": True, "lump_s": None, "run_s": None}, **parts)
    acct.add_program(rec("train_step", 25.0, trace_s=1.0, lower_s=0.5, cache_load_s=0.5))
    acct.add_program(rec("reference", 100.0, trace_s=3.0, lower_s=1.0, compile_s=30.0, cache_hit=False))
    acct.note_cache("requests", "jit_train_step")
    acct.note_cache("hits", "jit_train_step")
    ctx = {"setup_s": 40.0, "train": {"search_s": 14.0}}
    assert set(startup.account(ctx)["phases"]) == {"import", "search", "search.calibrate", "param_init"}
    assert layer_metrics.read("setup_import_s", ctx) == 5.0
    assert layer_metrics.read("setup_trace_lower_s", ctx) == 1.5 and layer_metrics.read("setup_compile_s", ctx) == 0.0
    assert layer_metrics.read("setup_cache_load_s", ctx) == 0.5
    assert layer_metrics.read("search_calibrate_s", ctx) == 1.0 and layer_metrics.read("search_unity_s", ctx) == 9.0
    assert layer_metrics.read("search_init_s", ctx) == 4.0
    assert layer_metrics.read("setup_engine_build_s", ctx) is None  # no engine in a trainer's process
    assert layer_metrics.read("setup_spanned_share", ctx) == pytest.approx(100.0 * (5.0 + 10.0 + 4.0 + 2.0) / 40.0)
    assert layer_metrics.read("setup_cache_misses", ctx) == 0


def test_on_a_tiny_ffmodel_compile_the_three_search_readers_sum_to_search_s():
    from flexflow_tpu import ActiMode, FFConfig, FFModel, LossType, SGDOptimizer
    from flexflow_tpu.obs.steptrace import GLOBAL_STARTUP

    model = FFModel(FFConfig(batch_size=8, epochs=1, search_budget=2, only_data_parallel=False))
    x = model.create_tensor((8, 10))
    model.softmax(model.dense(model.dense(x, 20, ActiMode.RELU), 5))
    before = GLOBAL_STARTUP.snapshot()["phases"]
    t0 = time.monotonic()
    model.compile(optimizer=SGDOptimizer(lr=0.1), loss_type=LossType.SPARSE_CATEGORICAL_CROSSENTROPY)
    search_s = time.monotonic() - t0  # as benchmark/drivers/train.py takes it
    after = GLOBAL_STARTUP.snapshot()
    zero = {"count": 0, "total_s": 0.0, "self_s": 0.0}
    grew = {k: {f: v[f] - before.get(k, zero)[f] for f in v} for k, v in after["phases"].items()}
    ctx = {"setup_s": after["now_s"], "stats_open": {"startup": dict(after, phases={k: v for k, v in grew.items() if v["count"]})},
           "train": {"search_s": search_s}}
    parts = [layer_metrics.read(r, ctx) for r in TRAINING]
    assert all(p is not None and p >= 0.0 for p in parts)
    assert sum(parts) <= layer_metrics.read("search_s", ctx) + 1e-6
    assert layer_metrics.read("search_s", ctx) - sum(parts) < 0.5  # FFModel.compile's own lines between its spans


def test_benchmark_json_asks_for_them_where_they_are_read():
    names = [m["name"] for m in BENCH["per_layer"]]
    first = names.index(READERS[0])
    assert names[first - 1] == "ssm_prefill_ms_per_ktoken.served"  # appended after what PR 48 left, nothing moved
    assert names[first:first + len(READERS)] == READERS
    cells = [w["name"] for w in BENCH["workloads"]][:11]  # the cells of PR 50's day
    want = {**{r: cells for r in EVERY_CELL + ["setup_spanned_share"]},
            **{r: [c for c in cells if not c.startswith("bert-large")] for r in SERVING},
            **{r: [c for c in cells if c.startswith("bert-large")] for r in TRAINING}}
    for m in BENCH["per_layer"][first:first + len(READERS)]:
        assert (m["moves"], m["layer"]) == ("setup_s", "start-up"), m["name"]
        assert m["workloads"][:len(want[m["name"]])] == want[m["name"]], m["name"]
        assert (m["unit"], m["better"], m["source"]) == {
            "setup_cache_misses": ("count", "lower", "program_counter"), "setup_spanned_share": ("%", "higher", "program_span"),
        }.get(m["name"], ("s", "lower", "program_span")), m["name"]
        assert (ROOT / "benchmark/layer_metrics" / f"{m['name']}.py").exists()
    assert next(m for m in BENCH["per_layer"] if m["name"] == "search_s")["layer"] == "search"  # stays as it is
    setup = next(m for m in BENCH["end_to_end"] if m["name"] == "setup_s")
    assert "workloads" not in setup and setup["bound"] == 0.1


@pytest.mark.parametrize("cell, mine", [
    ("gpt2-medium.chat-steady", EVERY_CELL + SERVING + ["setup_spanned_share"]),
    ("bert-large.mlm-s512", EVERY_CELL + TRAINING + ["setup_spanned_share"]),
])
def test_a_rehearsal_finds_the_cell_s_readers(cell, mine):
    """``--rehearse --trace 1``: the real program at tiny widths on the
    CPU through the real harness, so the keys the readers look for are
    the keys the program writes."""
    out = subprocess.run(
        [sys.executable, str(ROOT / "benchmark" / "run.py"), "--workload", cell, "--seed", "3000000050", "--seconds", "3",
         "--trace", "1", "--rehearse"],
        cwd=ROOT, capture_output=True, text=True, timeout=300,
    )
    assert out.returncode == 0, out.stdout[-2000:] + out.stderr[-2000:]
    found = re.search(r"readers that found something: (\[.*\])", out.stdout)
    assert found, out.stdout[-2000:]
    found = set(json.loads(found.group(1).replace("'", '"')))
    assert set(mine) <= found and not (set(READERS) - set(mine)) & found
