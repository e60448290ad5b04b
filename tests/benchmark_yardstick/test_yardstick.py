"""Tests of the benchmark's yardstick: CPU only, no chip, fast.

The reduction from a trace to numbers, the percentile and open-loop
arithmetic, the kernels' operation and byte counts, the traffic
generators, the load generator against a stub server, and the rule that
every file the harness finds by name loads and obeys the contract's
character rules.
"""
import http.server
import importlib
import json
import pathlib
import re
import subprocess
import sys
import threading
import time

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from benchmark import kernel_model, layer_metrics, run as bench_run, spec, stats, trace_reduce as tr, traffic  # noqa: E402

DATA = pathlib.Path(__file__).resolve().parent / "data"
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")

# ------------------------------------------------------- trace arithmetic


@pytest.mark.parametrize("intervals, want", [
    ([], []),
    ([(0, 1)], [(0, 1)]),
    ([(0, 2), (1, 3)], [(0, 3)]),
    ([(5, 6), (0, 1), (1, 2)], [(0, 2), (5, 6)]),
    ([(0, 10), (2, 3)], [(0, 10)]),
    ([(3, 3), (4, 2)], []),  # empty and inverted intervals cover nothing
])
def test_union(intervals, want):
    assert tr.union(intervals) == want


@pytest.mark.parametrize("a, b, want", [
    ([(0, 10)], [], [(0, 10)]),
    ([(0, 10)], [(2, 3), (5, 7)], [(0, 2), (3, 5), (7, 10)]),
    ([(0, 4), (6, 10)], [(3, 7)], [(0, 3), (7, 10)]),
    ([(0, 4)], [(0, 4)], []),
    ([(2, 4)], [(0, 10)], []),
])
def test_subtract(a, b, want):
    assert tr.subtract(a, b) == want


def test_busy_union_idle_share_and_gaps():
    ops = [("%a = x", 10, 20), ("%b = x", 15, 30), ("%c = x", 50, 60)]
    busy = tr.union((a, b) for _, a, b in ops)
    assert tr.total(busy) == 30
    assert tr.gaps(busy, 0, 100) == [(0, 10), (30, 50), (60, 100)]
    trace = tr.Trace([tr.DeviceTrace(0, ops, [])], [], (0.0, 100.0))
    r = tr.reduce_trace(trace)
    assert r["busy_s"] == pytest.approx(30e-9)
    assert r["idle_share"] == pytest.approx(0.70)
    assert r["window_s"] == pytest.approx(100e-9)


def test_worst_device_is_reported_and_busy_is_the_mean():
    d0 = tr.DeviceTrace(0, [("%a = x", 0, 80)], [])
    d1 = tr.DeviceTrace(1, [("%a = x", 0, 40)], [])
    r = tr.reduce_trace(tr.Trace([d0, d1], [], (0.0, 100.0)))
    assert r["idle_share"] == pytest.approx(0.60)
    assert r["busy_s"] == pytest.approx(60e-9)
    assert [d["idle_share"] for d in r["devices"]] == pytest.approx([0.2, 0.6])


def test_self_time_takes_nested_instructions_out():
    events = [("%while.1 = x", 0, 100), ("%fusion.1 = x", 10, 30), ("%fusion.2 = x", 40, 90)]
    assert tr.self_times(events) == [30, 20, 50]


@pytest.mark.parametrize("events, want", [
    # the collective runs alone for its whole 20
    ([("%fusion.1 = x", 0, 10), ("%all-reduce.3 = x", 10, 30)], (20, 20)),
    # half of it is under another instruction (an async pair around compute)
    ([("%all-gather-start.1 = x", 0, 20), ("%fusion.2 = x", 10, 40)], (20, 10)),
    # wholly hidden
    ([("%reduce-scatter.1 = x", 10, 20), ("%fusion.9 = x", 0, 30)], (10, 0)),
    # no collective
    ([("%fusion.9 = x", 0, 30)], (0, 0)),
    # a name that only contains the word is no collective
    ([("%my_all-reduce_fusion = x", 0, 30)], (0, 0)),
])
def test_exposed_collective(events, want):
    assert tr.exposed_collective(events) == want


def test_gap_labelling():
    host = [("main", 0, 1000), ("PjitFunction(step)", 100, 300), ("np.asarray(jax.Array)", 310, 390)]
    assert tr.label_gap((120, 280), host) == "PjitFunction(step)"  # innermost frame covering it
    assert tr.label_gap((300, 400), host) == "np.asarray(jax.Array)"
    assert tr.label_gap((500, 600), host) == "main"
    assert tr.label_gap((2000, 2100), host) == "unattributed"


# the scheduler's loop as the program's spans nest it, with the runtime's
# own events inside the innermost and a handler thread's span beside them
NESTED = [
    ("ff.sched.device_step", 0, 1000), ("ff.engine.decode.dispatch", 100, 400), ("PjitFunction(_decode_impl)", 150, 350),
    ("$pjit.py:77 cache_miss", 160, 200), ("ff.engine.decode.readback", 600, 900), ("np.asarray(jax.Array)", 610, 890),
    ("ff.http.ingress", 1200, 1300), ("bench.window", 0, 5000),
]


@pytest.mark.parametrize("gap, want", [
    ((160, 340), "ff.engine.decode.dispatch"),  # not PjitFunction, the shortest event that covers it
    ((620, 880), "ff.engine.decode.readback"),  # not np.asarray
    ((420, 580), "ff.sched.device_step"),  # between the phases: the span around them
    ((50, 950), "ff.sched.device_step"),  # no inner span covers half of it
    ((1210, 1290), "ff.http.ingress"),
    ((1100, 1400), "bench.window"),  # an ff. span covers a third: the old rule decides
    ((2000, 3000), "bench.window"),
    ((6000, 6100), "unattributed"),
])
def test_a_gap_is_labelled_by_the_innermost_program_span_that_covers_it(gap, want):
    assert tr.label_gap(gap, NESTED) == want
    assert tr.label_gap(gap, NESTED[::-1]) == want  # whatever order the trace lists them in


def test_labels_change_no_number_of_the_reduced_trace():
    """The recorded v5e trace (from before the program had spans, so the
    old rule labels every gap) reduced again with a program span laid
    over the window: every number is the same, and only the names under
    ``idle_gaps`` and ``longest_gaps`` differ."""
    trace = tr.read_xplane(str(DATA / "v5e_one_prefill_one_decode.xplane.pb"))
    with_ff = tr.Trace(trace.devices, trace.host + [("ff.sched.device_step", 69.0e6, 84.0e6)], trace.window_ns)
    a = tr.reduce_trace(trace, ["paged_append_attention"], window_ns=(69.0e6, 84.0e6))
    b = tr.reduce_trace(with_ff, ["paged_append_attention"], window_ns=(69.0e6, 84.0e6))
    for key in set(a) - {"idle_gaps", "longest_gaps"}:
        assert a[key] == b[key], key
    assert sum(s for _, s in a["idle_gaps"]) == pytest.approx(sum(s for _, s in b["idle_gaps"]))
    assert sorted(s for _, s in a["longest_gaps"]) == sorted(s for _, s in b["longest_gaps"])
    assert {n for n, _ in b["idle_gaps"]} <= {"ff.sched.device_step", "between instructions (< 20 us each)"}


@pytest.mark.parametrize("text, name, family", [
    ("%fusion.12 = bf16[8,128]{1,0} fusion(bf16[8,128] %p), kind=kLoop", "fusion.12", "fusion"),
    ("%paged_append_attention.2 = f32[8,1,16,64] custom-call(s32[8,64] %a)", "paged_append_attention.2", "paged_append_attention"),
    ("%copy-done = f32[4] copy-done(%x)", "copy-done", "copy-done"),
])
def test_op_names(text, name, family):
    assert tr.op_name(text) == name
    assert tr.op_family(name) == family


def test_kernels_are_matched_longest_name_first():
    ops = [
        ("%paged_append_attention_split.1 = x custom-call()", 0, 10),
        ("%paged_append_attention.2 = x custom-call()", 10, 15),
        ("%transpose_jvp_flash_attention_bwd_dq__.3 = x custom-call()", 20, 27),
    ]
    kernels = ["paged_append_attention", "paged_append_attention_split", "flash_attention_bwd_dq"]
    r = tr.reduce_trace(tr.Trace([tr.DeviceTrace(0, ops, [])], [], (0.0, 30.0)), kernels)
    assert r["kernel_calls"] == {"paged_append_attention": 1, "paged_append_attention_split": 1, "flash_attention_bwd_dq": 1}
    assert r["kernel_s"]["paged_append_attention_split"] == pytest.approx(10e-9)
    assert r["kernel_s"]["flash_attention_bwd_dq"] == pytest.approx(7e-9)


XSPACE_TEXT = """
planes { name: "/device:TPU:0"
  lines { name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 4000000 }
    events { metadata_id: 2 offset_ps: 4000000 duration_ps: 2000000 } }
  lines { name: "XLA Modules" timestamp_ns: 1000
    events { metadata_id: 3 offset_ps: 0 duration_ps: 6000000 } }
  event_metadata { key: 1 value { id: 1 name: "%fusion.1 = bf16[8] fusion()" } }
  event_metadata { key: 2 value { id: 2 name: "%all-reduce.1 = bf16[8] all-reduce()" } }
  event_metadata { key: 3 value { id: 3 name: "jit_step(123)" } } }
planes { name: "/device:TPU:1"
  lines { name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 3000000 } }
  event_metadata { key: 1 value { id: 1 name: "%fusion.1 = bf16[8] fusion()" } } }
planes { name: "/host:CPU"
  lines { name: "main" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 20000000 } }
  event_metadata { key: 1 value { id: 1 name: "bench.train_batch" } } }
"""


def test_reads_an_xspace_with_two_chips_and_a_collective():
    from jax.profiler import ProfileData

    trace = tr.from_profile_data(ProfileData.from_text_proto(XSPACE_TEXT))
    assert [d.ordinal for d in trace.devices] == [0, 1]
    r = tr.reduce_trace(trace, window_ns=(0.0, 100000.0))
    d0, d1 = r["devices"]
    assert d0["busy_s"] == pytest.approx(6e-6) and d1["busy_s"] == pytest.approx(3e-6)
    assert d0["collective_s"] == pytest.approx(2e-6)
    assert d0["collective_exposed_s"] == pytest.approx(2e-6)
    assert r["idle_share"] == pytest.approx(0.97)  # chip 1, the idler one
    assert r["programs"] == {"jit_step": pytest.approx(6e-6)}
    assert r["idle_gaps"][0][0] == "bench.train_batch"


def test_recorded_v5e_trace():
    """A trace recorded on the v5e in PR 22 (one prefill and one decode
    step of a 2-layer decoder at real widths), cut to 15 ms."""
    trace = tr.read_xplane(str(DATA / "v5e_one_prefill_one_decode.xplane.pb"))
    assert len(trace.devices) == 1 and trace.devices[0].ordinal == 0
    assert trace.window_ns == (0.0, 449614080.0)  # from the profile's own start and stop
    r = tr.reduce_trace(trace, ["paged_append_attention", "paged_append_attention_split"],
                        window_ns=(69.0e6, 84.0e6))
    assert r["busy_s"] == pytest.approx(0.006884283, rel=1e-6)
    assert r["idle_share"] == pytest.approx(1 - 0.006884283 / 0.015, rel=1e-6)
    assert r["kernel_calls"]["paged_append_attention"] == 2  # one per layer
    assert r["kernel_s"]["paged_append_attention"] == pytest.approx(9.0811e-05, rel=1e-4)
    assert set(r["programs"]) == {"jit__decode_impl", "jit__prefill_impl"}
    assert r["device_ops"][0][0] == "copy"  # the per-layer cache rewrite leads
    assert r["idle_gaps"][0][0] == "np.asarray(jax.Array)"  # the host reading the token back


# ------------------------------------------------------------ statistics


@pytest.mark.parametrize("values, p, want", [
    ([1, 2, 3, 4, 5, 6, 7, 8, 9, 10], 90, 9),
    ([1, 2, 3, 4, 5, 6, 7, 8, 9, 10], 99, 10),
    ([1, 2, 3, 4, 5, 6, 7, 8, 9, 10], 50, 5),
    ([5], 90, 5),
    (list(range(1, 101)), 90, 90),
    (list(range(1, 111)), 90, 99),
    ([3, 1, 2], 100, 3),
])
def test_percentile_is_nearest_rank(values, p, want):
    assert stats.percentile(values, p) == want


@pytest.mark.parametrize("bad", [0, -1, 101])
def test_percentile_refuses_nonsense(bad):
    with pytest.raises(ValueError):
        stats.percentile([1, 2], bad)
    with pytest.raises(ValueError):
        stats.percentile([], 50)


@pytest.mark.parametrize("n, p, beyond", [(110, 90, 11), (100, 90, 10), (90, 90, 9), (1000, 99, 10)])
def test_samples_beyond(n, p, beyond):
    assert stats.samples_beyond(n, p) == beyond


def test_median_and_quartile_spread():
    assert stats.median([3, 1, 2]) == 2
    assert stats.median([4, 1, 2, 3]) == 2.5
    assert stats.quartile_spread([100, 100, 100, 100]) == 0
    assert stats.quartile_spread([98, 99, 100, 101, 102]) == pytest.approx(0.02)


def _rec(due, sent, times, want=3, status=200, error=None):
    return {"id": 0, "due": due, "sent": sent, "token_times": times, "tokens": [7] * len(times),
            "max_new_tokens": want, "status": status, "error": error,
            "done_time": times[-1] + 0.001 if times else None, "prompt_len": 10}


def test_open_loop_latency_is_from_due_not_from_sent():
    r = _rec(due=10.0, sent=10.4, times=[10.5, 10.6, 10.8])
    assert stats.ttft_from_due_ms(r) == pytest.approx(500.0)  # the stall before sending counts
    assert stats.inter_token_gaps_ms(r) == pytest.approx([100.0, 200.0])
    assert stats.ttft_from_due_ms(_rec(1.0, 1.0, [])) is None


@pytest.mark.parametrize("rec, ok", [
    (_rec(0, 0, [1, 2, 3]), True),
    (_rec(0, 0, [1, 2]), False),  # fewer tokens than asked
    (_rec(0, 0, [1, 2, 3], status=503), False),  # refused
    (_rec(0, 0, [1, 2, 3], error="stream ended"), False),
])
def test_request_ok(rec, ok):
    assert stats.request_ok(rec) is ok


def test_windows_due_and_completed():
    a = _rec(due=9.9, sent=9.9, times=[10.1, 10.2, 10.3])  # due before, done inside
    b = _rec(due=10.5, sent=10.5, times=[10.6, 10.7, 20.5])  # due inside, done after
    assert stats.due_in_window([a, b], 10.0, 20.0) == [b]
    assert stats.completed_in_window([a, b], 10.0, 20.0) == [a]


def test_slo_counts_failures_as_misses():
    fast = _rec(0.0, 0.0, [0.5, 0.55, 0.6])
    late = _rec(0.0, 0.0, [1.5, 1.55, 1.6])
    slow = _rec(0.0, 0.0, [0.5, 0.8, 1.1])
    failed = _rec(0.0, 0.0, [0.1], status=503)
    assert [stats.slo_met(r, 1000.0, 100.0) for r in (fast, late, slow, failed)] == [True, False, False, False]


def test_train_flops_per_token():
    # 6 N + 12 L S H, by hand for BERT-Large's encoder at sequence 512
    assert stats.train_flops_per_token(302_000_000, 24, 512, 1024) == 6 * 302e6 + 12 * 24 * 512 * 1024


def test_peaks_table():
    row = stats.chip_peaks("TPU v5 lite")
    assert row["bf16_flops_per_s"] == 197e12 and row["hbm_bytes_per_s"] == 819e9 and row["source"]
    with pytest.raises(KeyError):
        stats.chip_peaks("TPU v9 imaginary")


# ---------------------------------------------------------- kernel model


def test_paged_attention_by_hand():
    # 8 rows attending 1000 positions in all, 16 heads of 64, float32 cache
    ops, nbytes = kernel_model.paged_attention_call(1000, 8, 16, 64, 4)
    assert ops == 4 * 1000 * 16 * 64  # a multiply-add for the score and one for the value, x 2
    assert nbytes == 2 * 1000 * 16 * 64 * 4 + 2 * 8 * 16 * 64 * 4
    secs, bound = kernel_model.least_seconds(ops, nbytes, stats.chip_peaks("TPU v5 lite"))
    assert bound == "memory" and secs == pytest.approx(nbytes / 819e9)


@pytest.mark.parametrize("kernel, matmuls, tensors, vectors", [
    ("flash_attention_fwd", 2, 4, 1),
    ("flash_attention_bwd_dq", 3, 5, 2),
    ("flash_attention_bwd_dkv", 4, 6, 2),
])
def test_flash_attention_by_hand(kernel, matmuls, tensors, vectors):
    b, s, h, d = 8, 512, 16, 64
    ops, nbytes = kernel_model.flash_attention_call(kernel, b, s, h, d)
    assert ops == matmuls * 2 * b * h * s * s * d
    assert nbytes == tensors * b * s * h * d * 2 + vectors * b * h * s * 4
    assert kernel_model.flash_attention_call(kernel, b, s, h, d, causal=True)[0] == ops / 2
    _, bound = kernel_model.least_seconds(ops, nbytes, stats.chip_peaks("TPU v5 lite"))
    assert bound == "compute"


# ------------------------------------------------------ files found by name


def test_benchmark_json_shape():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer"}
    assert 1 <= BENCH["run_seconds"] <= 51
    four = [w for w in BENCH["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(BENCH["workloads"]) // 4)
    assert any(m["name"] == "setup_s" for m in BENCH["end_to_end"])
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(set(pairs)) == len(pairs)
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.1 and m["source"] in ("host_clock", "device_trace")


@pytest.mark.parametrize("entry", BENCH["configs"] + BENCH["workloads"] + BENCH["end_to_end"] + BENCH["per_layer"],
                         ids=lambda e: e["name"])
def test_names_units_and_lines_obey_the_contract(entry):
    assert NAME.match(entry["name"])
    for key in ("config", "traffic", "moves"):
        if key in entry:
            assert NAME.match(entry[key])
    if "unit" in entry:
        assert UNIT.match(entry["unit"]) and entry["better"] in ("lower", "higher")
        assert entry["source"] in ("device_trace", "program_span", "program_counter", "host_clock")
    for key in ("why", "layer", "source"):
        if key in entry:
            assert 1 <= len(entry[key]) <= 200 and "\n" not in entry[key] and "\t" not in entry[key]
    for key in entry.get("reduced", []):
        assert NAME.match(key)


@pytest.mark.parametrize("cfg", BENCH["configs"], ids=lambda c: c["name"])
def test_config_files_load(cfg):
    assert any(cfg["file"].startswith(p + "/") for p in BENCH["paths"])
    body = json.loads((ROOT / cfg["file"]).read_text())
    assert body["source"] == cfg["source"] and body["reduced"] == cfg["reduced"]
    assert body["departures"] and body["assumed"] and body["deployment"] and body["rehearsal"]


@pytest.mark.parametrize("rehearsal", [False, True])
@pytest.mark.parametrize("w", BENCH["workloads"], ids=lambda w: w["name"])
def test_cells_load(w, rehearsal):
    cell = spec.load_cell(w["name"], rehearsal=rehearsal)
    assert cell.chips == w["chips"] and cell.workload["why"]
    importlib.import_module(f"benchmark.drivers.{cell.driver}").run  # the driver exists
    importlib.import_module(f"benchmark.traffic.{cell.traffic['generator']}").schedule
    e2e = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert cell.per_layer and all(m["moves"] in e2e for m in cell.per_layer)


@pytest.mark.parametrize("m", BENCH["end_to_end"] + BENCH["per_layer"], ids=lambda m: m["name"])
def test_every_metric_has_a_reader(m):
    mod = importlib.import_module(f"benchmark.layer_metrics.{m['name'].split('.', 1)[0]}")
    assert mod.__doc__ and callable(mod.read)
    assert mod.read({}) is None  # nothing to read -> nothing reported
    assert layer_metrics.read(m["name"], {}) is None
    if "moves" in m:
        assert m["moves"] in {e["name"] for e in BENCH["end_to_end"]}
    for w in m.get("workloads", []):
        assert w in {x["name"] for x in BENCH["workloads"]}


def test_files_under_paths_are_named_from_name_characters():
    for p in BENCH["paths"]:
        for f in (ROOT / p).rglob("*"):
            if "__pycache__" in f.parts:
                continue
            assert re.match(r"^[A-Za-z0-9_.\-/]+$", f.relative_to(ROOT).as_posix()), f


# ------------------------------- gpt2-medium.chat-steady: knee, rate, bounds

STEADY = json.loads((ROOT / "benchmark/workloads/gpt2-medium.chat-steady.json").read_text())
BOUND_STEPS = (0.02, 0.03, 0.05, 0.1)


def test_chat_steady_holds_a_sweep_of_its_traffic_as_committed_on_two_seeds():
    from benchmark.tools import knee_sweep

    knee, cell = STEADY["knee"], spec.load_cell("gpt2-medium.chat-steady")
    points = knee["points"]
    # what knee_of reads for the rate: this file's slots, lead-in and blocked arrivals, windows as long as a run
    assert all(p["slots"] == STEADY["deployment"]["slots"] and p["lead_in_s"] == STEADY["lead_in_s"] and p["arrivals"] == "blocks"
               and p["seconds"] == BENCH["run_seconds"] for p in points)
    assert cell.traffic["params"]["arrival_block_s"] and len({p["seed"] for p in points}) >= 2
    assert knee["knee_requests_per_s"] == knee_sweep.knee_of(points)
    # the knee is a swept point that met the rule on every seed, and the next rate up did not
    at = [p for p in points if p["rate_per_s"] == knee["knee_requests_per_s"]]
    assert len({p["seed"] for p in at}) >= 2 and all(p["met_share"] >= 0.9 and not p["backlog_grows"] for p in at)
    above = [p for p in points if p["rate_per_s"] > knee["knee_requests_per_s"]]
    nxt = min(p["rate_per_s"] for p in above)
    assert any(p["met_share"] < 0.9 or p["backlog_grows"] for p in above if p["rate_per_s"] == nxt)


def test_chat_steady_s_slot_count_comes_from_the_sweep_of_both():
    """The earlier passes (whole-run arrivals) are context: they chose
    the slot count, and ``knee_of`` is not asked about the rate from them."""
    from benchmark.tools import knee_sweep

    ctx = STEADY["knee"]["slots_from"]
    assert {p["slots"] for p in ctx["points"]} >= {8, 16} and len({p["seed"] for p in ctx["points"]}) >= 2
    assert all(p["arrivals"] == "whole-run" and p["seconds"] == BENCH["run_seconds"] for p in ctx["points"])
    by_slots = {n: knee_sweep.knee_of([p for p in ctx["points"] if p["slots"] == n]) for n in (8, 16)}
    assert ctx["knee_by_slots"] == {str(n): k for n, k in by_slots.items()}
    # the slot count with the higher knee, 8 where the two are within 10 %
    assert STEADY["deployment"]["slots"] == (16 if by_slots[16] > 1.1 * by_slots[8] else 8)


def test_chat_steady_runs_under_four_fifths_of_its_knee_and_says_at_which_share():
    knee = STEADY["knee"]
    rate = STEADY["traffic_params"]["rate_per_s"]
    assert knee["rate_per_s"] == rate and rate % 0.5 == 0 and rate <= 0.8 * knee["knee_requests_per_s"]
    assert knee["rate_share_of_knee"] == round(rate / knee["knee_requests_per_s"], 2)
    assert spec.load_cell("gpt2-medium.chat-steady").traffic["params"]["rate_per_s"] == rate
    why = next(w for w in BENCH["workloads"] if w["name"] == "gpt2-medium.chat-steady")["why"]
    assert f"{rate:g}/s" in why and f"{knee['rate_share_of_knee']:g} x the knee of {knee['knee_requests_per_s']:g}" in why
    # the instrument: the generator kept its schedule at that rate, but for a stalled run in ten
    lag = STEADY["spread"]["generator_lag_p99_ms"]
    assert 0 < lag["median"] < 10.0 and lag["runs"] >= 12 and lag["runs_over_10_ms"] <= lag["runs"] / 10


@pytest.mark.parametrize("m", [m for m in BENCH["end_to_end"] if "gpt2-medium.chat-steady" in m.get("workloads", [])],
                         ids=lambda m: m["name"])
def test_chat_steady_s_bounds_are_five_times_the_spread_recorded_beside_them(m):
    rec = STEADY["spread"]["metrics"][m["name"]]
    assert rec["runs"] >= 12 and rec["median"] > 0
    assert m["bound"] == rec["bound"] and m["bound"] >= 5.0 * rec["spread"]
    assert m["bound"] == min(b for b in BOUND_STEPS if b >= 5.0 * rec["spread"])  # the smallest step that is


@pytest.mark.parametrize("name", ["itl_p50_ms", "itl_p95_ms", "itl_p99_ms", "gap_mean_p50_ms", "ttft_p50_ms", "ttft_p90_ms",
                                  "slow_gap_share", "slo_attainment"])
def test_chat_steady_records_the_spread_of_every_candidate(name):
    rec = STEADY["spread"]["metrics"][name]
    lo, hi = rec["range"]
    assert rec["runs"] >= 12 and lo <= rec["median"] <= hi and rec["spread"] >= 0 and len(rec["spread_sets"]) == rec["runs"] // 6
    judged = {m["name"] for m in BENCH["end_to_end"] if "gpt2-medium.chat-steady" in m.get("workloads", [])}
    recorded = {m["name"] for m in BENCH["per_layer"] if "gpt2-medium.chat-steady" in m.get("workloads", [])}
    assert (name in judged) == (rec["bound"] is not None) and (name in judged) != (name in recorded)
    if 5.0 * rec["spread"] > 0.1:  # would need more than the ceiling: recorded, not judged
        assert name in recorded


@pytest.mark.parametrize("cell", ["gpt2-medium.chat-steady", "gpt2-medium.prompt-batch"])
def test_a_decoder_cell_s_limit_lies_between_its_sound_runs_and_its_control_with_room(cell):
    """Both readings and the limit are in the cell's file: a dozen seeds
    each, the control's smallest at least three times the sound runs'
    largest, and the limit the driver compares with well inside."""
    w = json.loads((ROOT / f"benchmark/workloads/{cell}.json").read_text())
    c = w["correct"]
    sound, control = c["sound"], c["control"]["bfloat16"]
    assert sound["seeds"] >= 12 and control["seeds"] >= 3
    assert 0 < sound["smallest"] <= sound["largest"] and control["smallest"] <= control["largest"]
    assert control["smallest"] >= 3.0 * sound["largest"]
    assert c["limit"] == w["near_tie_gap_limit"] and 2.0 * sound["largest"] <= c["limit"] <= control["smallest"] / 2.0
    assert c["control"]["int8_weights"]["smallest"] > control["smallest"]  # a step further down reads further off
    assert w["reference_sample"] >= 32 and "near_tie_gap" in c["what"]


# ---------------------------------------------------------------- traffic

CHAT = json.loads((ROOT / "benchmark/traffic/chat-steady.json").read_text())["params"]


def test_poisson_open_is_seeded_and_bounded():
    params = dict(CHAT, rate_per_s=20.0)
    a = traffic.schedule("poisson_open", 7, 30.0, params, {"vocab_size": 50257})
    b = traffic.schedule("poisson_open", 7, 30.0, params, {"vocab_size": 50257})
    c = traffic.schedule("poisson_open", 8, 30.0, params, {"vocab_size": 50257})
    assert a == b and a != c and a["mode"] == "open"
    reqs = a["requests"]
    assert len(reqs) == 600  # a Poisson process given its count
    due = [r["due_s"] for r in reqs]
    assert due == sorted(due) and 0 <= due[0] and due[-1] < 30.0
    third = sum(d < 10.0 for d in due)
    assert 160 < third < 240  # uniform over the run: 200 expected, sd ~12
    assert all(16 <= len(r["prompt"]) <= 512 and 16 <= r["max_new_tokens"] <= 128 for r in reqs)
    assert all(0 <= t < 50257 for r in reqs for t in r["prompt"])
    lens = sorted(len(r["prompt"]) for r in reqs)
    assert 80 <= lens[len(lens) // 2] <= 115  # median 96


def test_stratified_lengths_offer_every_seed_the_same_load():
    params = dict(CHAT, rate_per_s=2.0)
    assert params["stratified"]
    run = lambda p, seed: traffic.schedule("poisson_open", seed, 50.0, p, {"vocab_size": 50257})["requests"]
    out = [sum(q["max_new_tokens"] for q in run(params, seed)) for seed in (1, 2, 3)]
    assert max(out) - min(out) < 0.01 * min(out)
    iid = [sum(q["max_new_tokens"] for q in run(dict(params, stratified=False), seed)) for seed in range(8)]
    assert max(iid) - min(iid) > 0.03 * min(iid)  # independent draws differ by several per cent


@pytest.mark.parametrize("seed", [1, 2, 3, 2100000011])
def test_blocked_arrivals_give_every_window_of_whole_blocks_the_same_work_in_every_seed(seed):
    """chat-steady's arrivals: the count is given in every 10 s block,
    the lengths are stratified within a block's requests, and the
    lead-in and the window are whole blocks."""
    cell = spec.load_cell("gpt2-medium.chat-steady")
    params, w = cell.traffic["params"], cell.workload
    block, rate = params["arrival_block_s"], params["rate_per_s"]
    assert w["lead_in_s"] % block == 0 and BENCH["run_seconds"] % block == 0 and rate * block == round(rate * block)
    total = w["lead_in_s"] + BENCH["run_seconds"]
    reqs = traffic.schedule("poisson_open", seed, total, params, {"vocab_size": 50257})["requests"]
    due = [r["due_s"] for r in reqs]
    assert due == sorted(due) and len(reqs) == round(rate * total)
    per_block = round(rate * block)
    for b in range(int(total // block)):
        mine = [r for r in reqs if b * block <= r["due_s"] < (b + 1) * block]
        assert len(mine) == per_block and mine == reqs[b * per_block:(b + 1) * per_block]
        assert abs(sum(r["max_new_tokens"] for r in mine) - 5269) <= 12  # the same replies, to the strata's width
        assert abs(sum(len(r["prompt"]) for r in mine) - 12915) <= 150
        gaps = sorted(b2 - a for a, b2 in zip(due[b * per_block:], due[b * per_block + 1:(b + 1) * per_block]))
        assert gaps[len(gaps) // 2] < 1.0 / rate < gaps[-1] / 2.5  # bursty inside a block, as a Poisson process is
    window = [r for r in reqs if w["lead_in_s"] <= r["due_s"] < total]
    assert len(window) == round(rate * BENCH["run_seconds"])


def test_blocked_arrivals_are_seeded_and_a_cut_block_is_cut_not_squeezed():
    params = dict(CHAT, rate_per_s=3.0, arrival_block_s=10.0)
    run = lambda seed, secs: traffic.schedule("poisson_open", seed, secs, params, {"vocab_size": 512})["requests"]  # noqa: E731
    assert run(5, 20.0) == run(5, 20.0) and run(5, 20.0) != run(6, 20.0)
    assert len(run(5, 20.0)) == 60 and 5 <= len(run(5, 14.0)) - 30 <= 20  # 12 expected of the second block's 30
    assert all(r["due_s"] < 14.0 for r in run(5, 14.0))
    # the block length is the generator's one way: a mix that leaves it out is refused
    with pytest.raises(KeyError):
        traffic.schedule("poisson_open", 5, 20.0, {k: v for k, v in params.items() if k != "arrival_block_s"}, {"vocab_size": 512})


def test_gap_mean_p50_is_the_median_request_s_mean_gap():
    rec = lambda i, gaps: {"id": i, "due": 10.0, "sent": 10.0, "status": 200, "error": None, "done_time": 12.0,  # noqa: E731
                           "max_new_tokens": len(gaps) + 1, "tokens": [1] * (len(gaps) + 1),
                           "token_times": [10.1 + sum(gaps[:k]) for k in range(len(gaps) + 1)]}
    ctx = {"records": [rec(0, [0.004] * 10), rec(1, [0.004] * 9 + [0.034]), rec(2, [0.005] * 4),
                       rec(3, [])], "window": (10.0, 20.0)}
    # means 4, 7 and 5 ms; a request of one token has no gap and no say
    assert layer_metrics.read("gap_mean_p50_ms", ctx) == pytest.approx(5.0)
    assert layer_metrics.read("itl_p50_ms", ctx) == pytest.approx(4.0)
    assert layer_metrics.read("gap_mean_p50_ms", {"records": [rec(3, [])], "window": (10.0, 20.0)}) is None


def test_block_stratified_lengths_sum_alike_over_any_stretch_of_the_list():
    """prompt-batch's replies: a closed loop uses as much of its list as
    the server gets through, so the lengths are stratified within every
    16 consecutive requests and not over the whole list."""
    cell = spec.load_cell("gpt2-medium.prompt-batch")
    assert cell.traffic["params"]["output"]["stratified_block"] == 16
    assert "stratified_block" not in cell.traffic["params"]["prompt"]
    run = lambda seed: traffic.schedule("closed_clients", seed, 10.0, cell.traffic["params"], {"vocab_size": 50257})
    for seed in (1, 2):
        reqs = run(seed)["requests"]
        out = [r["max_new_tokens"] for r in reqs]
        assert all(8 <= n <= 32 for n in out) and all(513 <= len(r["prompt"]) <= 960 for r in reqs)
        blocks = [sum(out[i:i + 16]) for i in range(0, len(out) - 15, 16)]
        assert max(blocks) - min(blocks) <= 16  # 16 x 20 = 320 each, to within the strata's width
        assert len({tuple(out[i:i + 16]) for i in range(0, 64, 16)}) == 4  # ... in another order every time
        prompts = [sum(len(r["prompt"]) for r in reqs[i:i + 16]) for i in range(0, len(reqs) - 15, 16)]
        assert max(prompts) - min(prompts) > 500  # independent draws: sd of a block's sum ~520
    assert run(1) == run(1) and run(1) != run(2)


def test_the_trainer_s_throughput_is_the_median_slice_so_a_stall_or_two_do_not_move_it():
    reader = importlib.import_module("benchmark.layer_metrics.train_tokens_per_s")
    train = {"slice_steps": 16, "global_batch": 16, "seq": 512, "step_ms": [182.8] * 273}
    steady = reader.read({"train": train})
    assert steady == pytest.approx(16 * 512 / 0.1828)
    stalled = dict(train, step_ms=[182.8] * 40 + [445.0] + [182.8] * 150 + [370.0] + [182.8] * 81)
    assert reader.read({"train": stalled}) == pytest.approx(steady)  # 2 of 17 slices hit
    slower = dict(train, step_ms=[182.8 if i % 8 else 192.8 for i in range(273)])
    assert reader.read({"train": slower}) == pytest.approx(16 * 512 / (0.1828 + 0.010 / 8))  # every slice hit
    assert reader.read({"train": dict(train, step_ms=[100.0, 300.0])}) == pytest.approx(16 * 512 / 0.2)  # one short slice
    assert reader.read({"train": dict(train, step_ms=[])}) is None and reader.read({}) is None


@pytest.mark.parametrize("rehearsal", [False, True])
@pytest.mark.parametrize("w", BENCH["workloads"], ids=lambda w: w["name"])
def test_every_cell_s_mix_is_one_its_generator_takes(w, rehearsal):
    """The data files and the generators agree: each cell's own
    parameters, as the driver passes them, give a schedule."""
    cell = spec.load_cell(w["name"], rehearsal=rehearsal)
    vocab = cell.config["vocab_size"]
    s = traffic.schedule(cell.traffic["generator"], 3, 4.0, cell.traffic["params"], {"vocab_size": vocab})
    if s["mode"] == "batches":
        assert s["tokens"].shape[1] == s["seq"] and len(s["tokens"]) % s["global_batch"] == 0
        return
    assert s["requests"] and all(0 <= t < vocab for r in s["requests"] for t in r["prompt"])
    # the reference check pads to the mix's longest reply
    assert max(r["max_new_tokens"] for r in s["requests"]) <= cell.traffic["params"]["output"]["max"]
    assert ("due_s" in s["requests"][0]) == (s["mode"] == "open")


def test_an_unknown_length_distribution_is_refused():
    params = dict(CHAT, rate_per_s=1.0, prompt={"dist": "zipf", "min": 1, "max": 2})
    with pytest.raises(ValueError):
        traffic.schedule("poisson_open", 1, 10.0, params, {"vocab_size": 10})


def test_token_batches():
    params = {"global_batch": 4, "seq": 16, "max_steps_per_s": 2, "labels": "next_id"}
    s = traffic.schedule("token_batches", 5, 10.0, params, {"vocab_size": 100})
    assert s["tokens"].shape == (20 * 4, 16) and s["tokens"].dtype.name == "int32"
    assert ((s["tokens"] + 1) % 100 == s["labels"]).all() and s["tokens"].max() < 100
    again = traffic.schedule("token_batches", 5, 10.0, params, {"vocab_size": 100})
    assert (again["tokens"] == s["tokens"]).all()


# --------------------------------------------------------- load generator


class _StubServer(http.server.BaseHTTPRequestHandler):
    """Three SSE token events 20 ms apart, then done; prompts that start
    with 503 are refused."""

    def log_message(self, *a):
        pass

    def do_POST(self):
        body = json.loads(self.rfile.read(int(self.headers["Content-Length"])))
        if body["prompt"][0] == 503:
            self.send_response(503)
            self.send_header("Content-Length", "2")
            self.end_headers()
            self.wfile.write(b"{}")
            return
        self.send_response(200)
        self.send_header("Content-Type", "text/event-stream")
        self.end_headers()
        toks = list(range(body["max_new_tokens"]))
        for i in toks:
            time.sleep(0.02)
            self.wfile.write(f"id: {i}\ndata: {json.dumps({'token': i, 'index': i})}\n\n".encode())
            self.wfile.flush()
        self.wfile.write(f"data: {json.dumps({'done': True, 'tokens': toks})}\n\n".encode())


@pytest.fixture(scope="module")
def stub_url():
    httpd = http.server.ThreadingHTTPServer(("127.0.0.1", 0), _StubServer)
    t = threading.Thread(target=httpd.serve_forever, daemon=True)
    t.start()
    yield f"http://127.0.0.1:{httpd.server_address[1]}"
    httpd.shutdown()
    httpd.server_close()
    t.join(5)
    assert not t.is_alive()


def _loadgen(job):
    out = subprocess.run(
        [sys.executable, str(ROOT / "benchmark" / "loadgen.py")], input=json.dumps(job).encode(),
        capture_output=True, timeout=120, check=True,
    )
    return json.loads(out.stdout)


def test_loadgen_never_imports_jax():
    src = (ROOT / "benchmark" / "loadgen.py").read_text()
    assert not re.search(r"^\s*(import|from)\s+(jax|numpy|flexflow_tpu|benchmark)\b", src, re.M)


# The two tests below run beside the rest of tier-1 on a loaded machine,
# where the child may start late and any thread may be held up: they
# assert only what holds however late anything runs (a floor under
# every time, never a ceiling), and start the traffic 2 s ahead.


def test_loadgen_open_loop(stub_url):
    reqs = [{"id": i, "prompt": [503 if i == 2 else 1, 2], "max_new_tokens": 3, "due_s": 0.1 * i}
            for i in range(5)]
    job = {"url": stub_url, "model": "lm", "mode": "open", "t0": time.monotonic() + 2.0,
           "requests": reqs, "clients": 0, "workers": 4, "stop_s": 0.5, "drain_s": 30, "timeout_s": 30}
    got = _loadgen(job)
    recs = sorted(got["records"], key=lambda r: r["id"])
    assert len(recs) == 5 and got["undrained"] == 0
    for r, q in zip(recs, reqs):
        assert r["due"] == pytest.approx(job["t0"] + q["due_s"])
        assert r["sent"] >= r["due"]  # never early; the generator's own lateness is reported
    ok = [r for r in recs if r["id"] != 2]
    assert all(stats.request_ok(r) and r["tokens"] == [0, 1, 2] for r in ok)
    assert all(r["token_times"] == sorted(r["token_times"]) and r["done_time"] >= r["token_times"][-1] for r in ok)
    assert all(r["token_times"][0] >= r["sent"] for r in ok)
    assert all(r["done_time"] - r["sent"] >= 0.055 for r in ok)  # the stub sleeps 3 x 20 ms before it is done
    assert all(stats.ttft_from_due_ms(r) >= 15 for r in ok)  # ... and 20 ms before the first token
    assert recs[2]["status"] == 503 and not stats.request_ok(recs[2])


def test_loadgen_closed_loop(stub_url):
    reqs = [{"id": i, "prompt": [1], "max_new_tokens": 2} for i in range(100)]
    job = {"url": stub_url, "model": "lm", "mode": "closed", "t0": time.monotonic() + 2.0,
           "requests": reqs, "clients": 2, "workers": 0, "stop_s": 1.0, "drain_s": 30, "timeout_s": 30}
    got = _loadgen(job)
    recs = got["records"]
    # two clients, at least 40 ms a request, one second: each sends its next when its last completes
    assert 2 <= len(recs) <= 60 and not got["exhausted"] and got["undrained"] == 0
    assert all(stats.request_ok(r) for r in recs)
    assert max(r["due"] for r in recs) < job["t0"] + 1.0  # none taken after stop_s
    assert sorted(r["id"] for r in recs) == list(range(len(recs)))  # taken in order, none twice


def test_a_closed_loop_that_runs_out_of_its_list_fails_the_run_and_says_how_long_the_list_lasted(stub_url):
    """Through the drivers' own pair (``serve.start_loadgen`` /
    ``finish_loadgen``): three requests for two clients that would go on
    for 5 s. The child says when the list ended; the driver raises, with
    the list's size and the seconds it lasted in the message."""
    from benchmark.drivers import serve

    sched = {"mode": "closed", "clients": 2, "requests": [{"id": i, "prompt": [1], "max_new_tokens": 2} for i in range(3)]}
    w = {"drain_s": 30.0, "request_timeout_s": 30.0}
    child, t0 = serve.start_loadgen(stub_url, sched, w, 5.0)
    with pytest.raises(RuntimeError, match=r"ran out of requests: its list of 3 lasted \d+\.\d s .*`benchmark` issue"):
        serve.finish_loadgen(child, w)
    assert child.poll() == 0
    # ... and one that lasts is handed back with what it used: the window line's "N sent of M listed"
    child, t0 = serve.start_loadgen(stub_url, dict(sched, requests=sched["requests"] * 40), w, 0.3)
    gen = serve.finish_loadgen(child, w)
    assert not gen["exhausted"] and gen["exhausted_after_s"] is None and gen["listed"] == 120 and 2 <= gen["sent"] < 60
    assert serve.sent_of_listed(gen, sched) == f"{gen['sent']} sent of 120 listed ({100 * gen['sent'] / 120:.0f} %)"


@pytest.mark.parametrize("mode, sent, listed, want", [
    ("closed", 1241, 1360, "1241 sent of 1360 listed (91 %)"),  # prompt-batch on PR 50's program, the list of PR 22
    ("closed", 1241, 2704, "1241 sent of 2704 listed (46 %)"),  # ... and the list of PR 55
    ("closed", 417, 996, "417 sent of 996 listed (42 %)"),  # code-gen
    ("open", 475, 475, "475 sent in all"),  # an open loop sends its whole schedule: no share to watch
])
def test_the_window_line_says_how_much_of_its_list_a_closed_loop_used(mode, sent, listed, want):
    from benchmark.drivers import serve

    assert serve.sent_of_listed({"sent": sent, "listed": listed}, {"mode": mode}) == want


CLOSED_MIXES = sorted(p.stem for p in (ROOT / "benchmark" / "traffic").glob("*.json")
                      if json.loads(p.read_text())["generator"] == "closed_clients")


@pytest.mark.parametrize("mix", CLOSED_MIXES)
def test_every_closed_mix_says_what_its_list_was_sized_on(mix):
    """A list that ends fails the run (above), so each mix's file says
    what sizes it; the cell's file may set the number, and then says on
    which rate."""
    assert len(CLOSED_MIXES) >= 8
    t = json.loads((ROOT / "benchmark" / "traffic" / f"{mix}.json").read_text())
    assert "max_rate_per_s" in t["params"] and "sizes the list" in t["max_rate_why"]


def test_prompt_batch_s_list_holds_twice_the_rate_its_file_states():
    """``max_rate_per_s`` >= 2 x the completions a second of the accepted
    tree (``deployment.completions_per_s``, with the PR it was read on):
    the cell predates the rule the later cells' tests hold, and a list
    sized on PR 22's program ended under a change 9.6 % faster than PR
    50's (PR 55). Twice and not 2.5 x: the list's way through the pipe to
    the load generator's child is inside ``setup_s``."""
    cell = spec.load_cell("gpt2-medium.prompt-batch")
    d, p, w = cell.workload["deployment"], cell.traffic["params"], cell.workload
    assert p["max_rate_per_s"] >= 2.0 * d["completions_per_s"] > 40 and "PR 5" in d["completions_per_s_why"]
    sched = traffic.schedule("closed_clients", 7, w["lead_in_s"] + BENCH["run_seconds"], p, {"vocab_size": 50257})
    assert len(sched["requests"]) == 16 + 48 * 56 == 2704
    # what start_loadgen lets the child take to parse the job, inside setup_s (~38 s): 2 us a prompt token, 2.0 s at 24 a second, 5.0 at 60
    assert 2e-6 * sum(len(r["prompt"]) for r in sched["requests"]) < 4.2


def test_a_rehearsal_of_prompt_batch_on_a_list_too_short_fails_with_the_list_s_size_and_seconds():
    """The whole command at rehearsal size with ``max_rate_per_s`` set
    under what the rehearsal completes (some 100 a second on this CPU): no
    result, a non-zero exit, and the message a builder reads in the log.
    (With the file's own value it runs to its end:
    ``test_pipelined_step_share.py`` and ``test_inside_metrics.py``.)"""
    code = (
        "import sys; sys.path.insert(0, '.')\n"
        "from benchmark import run, spec\n"
        "load = spec.load_cell\n"
        "def short(name, rehearsal=False):\n"
        "    cell = load(name, rehearsal)\n"
        "    cell.traffic['params']['max_rate_per_s'] = 2\n"
        "    return cell\n"
        "spec.load_cell = short\n"
        "sys.exit(run.main(['--workload', 'gpt2-medium.prompt-batch', '--seed', '5', '--seconds', '3', '--trace', '0', '--rehearse']))\n"
    )
    out = subprocess.run([sys.executable, "-c", code], cwd=ROOT, capture_output=True, text=True, timeout=300)
    assert out.returncode != 0 and "rehearsal done" not in out.stdout, out.stdout[-2000:]
    assert re.search(r"RuntimeError: the closed loop ran out of requests: its list of 16 lasted \d+\.\d s", out.stderr), out.stderr[-3000:]


# ------------------------------------------------- readers, on a hand-made run


def _serve_ctx():
    def rec(i, due, times, prompt_len=100):
        return {"id": i, "due": due, "sent": due + 0.001, "token_times": times, "tokens": [1] * len(times),
                "max_new_tokens": len(times), "status": 200, "error": None, "done_time": times[-1],
                "prompt_len": prompt_len}
    records = [rec(0, 10.0, [10.1, 10.15, 10.2, 10.25]), rec(1, 11.0, [11.2, 11.25, 11.3, 11.35])]
    snap = lambda steps, d, p: {
        "step_counts": {"prefill": 0, "decode": steps, "verify": 0},
        "phase_time_s": {"decode": {"dispatch": 0.0, "execute": d, "readback": 0.0},
                         "prefill": {"dispatch": 0.0, "execute": p, "readback": 0.0},
                         "verify": {"dispatch": 0.0, "execute": 0.0, "readback": 0.0}},
        "trace_counts": {}}
    return {
        "records": records, "window": (10.0, 20.0), "trace_abs": (10.0, 12.0), "slots": 2,
        "stats_open": {"preemptions": 1}, "stats_close": {
            "preemptions": 3, "queue_time": {"p95_s": 0.25}, "ttft": {"p50_s": 0.140}},
        "stats_samples": [{"cache_blocks_used": 10, "cache_blocks_total": 40},
                          {"cache_blocks_used": 30, "cache_blocks_total": 40}],
        "engine_open": snap(100, 5.0, 1.0), "engine_close": snap(110, 5.6, 1.2),
        "setup_s": 33.0, "memory_peak_bytes": 9e9, "correct": True, "attempted": 2, "failed": 0,
        "cell": spec.load_cell("gpt2-medium.chat-steady"),
        "model": {"num_layers": 2, "num_heads": 16, "head_dim": 64, "cache_itemsize": 4},
        "peaks": stats.chip_peaks("TPU v5 lite"),
        "trace": {"idle_share": 0.25, "window_s": 2.0, "devices": [{"collective_exposed_s": 0.0}],
                  "kernel_s": {"paged_append_attention": 1e-4, "paged_append_attention_split": 0.0},
                  "kernel_calls": {}, "busy_s": 1.5, "device_ops": [["copy", 0.9]] * 12,
                  "idle_gaps": [["np.asarray(jax.Array)", 0.3]]},
    }


def _stalled_ctx():
    """One stream of 21 tokens: 18 gaps of 50 ms and two held up by an
    admission (120 and 130 ms)."""
    times, t = [10.1], 10.1
    for gap in [0.05] * 9 + [0.12] + [0.05] * 9 + [0.13]:
        t += gap
        times.append(t)
    rec = {"id": 0, "due": 10.0, "sent": 10.0, "token_times": times, "tokens": [1] * 21, "max_new_tokens": 21,
           "status": 200, "error": None, "done_time": t, "prompt_len": 50}
    return {"records": [rec], "window": (10.0, 20.0)}


def _train_ctx():
    return {
        "setup_s": 140.0, "memory_peak_bytes": 12e9, "correct": True, "attempted": 250, "failed": 0,
        "cell": spec.load_cell("bert-large.mlm-s512-x4"),
        "train": {"step_ms": [200.0, 210.0, 190.0], "slice_steps": 16,  # one slice: 3 x 64 x 512 tokens in 0.6 s
                  "search_s": 2.5, "predicted_step_s": 0.18,
                  "steps": 250, "window_s": 51.2,  # 250 x 64 x 512 tokens in 51.2 s = 160 k tokens/s
                  "n_params": 364_000_000, "n_embedding_params": 31_000_000, "num_layers": 24,
                  "hidden_size": 1024, "num_heads": 16, "seq": 512, "global_batch": 64, "chips": 4},
        "peaks": stats.chip_peaks("TPU v5 lite"),
        "trace": {"idle_share": 0.05, "window_s": 2.0, "busy_s": 1.9, "device_ops": [["fusion", 0.7]], "idle_gaps": [],
                  "devices": [{"collective_exposed_s": 0.1}, {"collective_exposed_s": 0.3}],
                  "kernel_s": {"flash_attention_fwd": 0.02, "flash_attention_bwd_dq": 0.0, "flash_attention_bwd_dkv": 0.0},
                  "kernel_calls": {"flash_attention_fwd": 40, "flash_attention_bwd_dq": 0, "flash_attention_bwd_dkv": 0}},
    }


_FLASH_FWD_LEAST = 40 * (2 * 2 * 16 * 16 * 512 * 512 * 64) / 197e12  # 40 calls, 16 sequences per chip
_PAGED_BYTES = 2 * (2 * 612 * 16 * 64 * 4 + 2 * 6 * 16 * 64 * 4)  # contexts 101 + 102 + 103, twice; 2 layers


@pytest.mark.parametrize("reader, ctx, want", [
    ("frontend_overhead_p50_ms", _serve_ctx, (99.0 + 199.0) / 2 - 140.0),
    ("queue_wait_p95_ms", _serve_ctx, 250.0),
    ("batch_occupancy", _serve_ctx, 100.0 * 6 / (10 * 2)),  # 3 decode tokens per request
    ("cache_blocks_used_peak", _serve_ctx, 75.0),
    ("preemptions", _serve_ctx, 2),
    ("decode_step_ms", _serve_ctx, 60.0),
    ("prefill_time_share", _serve_ctx, 25.0),
    ("paged_append_attention_roofline", _serve_ctx, 100.0 * (_PAGED_BYTES / 819e9) / 1e-4),
    ("device_idle_share", _serve_ctx, 25.0),
    ("generator_lag_p99_ms", _serve_ctx, 1.0),
    ("slo_attainment", _serve_ctx, 100.0),
    ("setup_s", _serve_ctx, 33.0),
    ("ttft_p50_ms", _serve_ctx, 150.0),  # first tokens 100 and 200 ms after due
    ("ttft_p90_ms", _serve_ctx, 200.0),
    ("itl_p50_ms", _serve_ctx, 50.0),
    ("itl_p99_ms", _serve_ctx, 50.0),
    ("served_tokens_per_s", _serve_ctx, 2 * (100 + 4) / 10.0),  # both completed inside the 10 s window
    ("itl_p50_ms", _stalled_ctx, 50.0),
    ("itl_p95_ms", _stalled_ctx, 120.0),  # rank 19 of 20
    ("itl_p99_ms", _stalled_ctx, 130.0),
    ("slow_gap_share", _stalled_ctx, 10.0),  # 2 of 20 gaps over twice the median
    ("slow_gap_share", _serve_ctx, 0.0),
    ("train_tokens_per_s", _train_ctx, 163_840.0),
    ("train_window_tokens_per_s", _train_ctx, 160_000.0),
    ("setup_s", _train_ctx, 140.0),
    ("train_step_ms", _train_ctx, 200.0),
    ("mfu", _train_ctx, 100.0 * 163.84e3 * (6 * 333e6 + 12 * 24 * 512 * 1024) / (4 * 197e12)),
    ("search_s", _train_ctx, 2.5),
    ("predicted_over_measured", _train_ctx, 0.9),
    ("collective_exposed_share", _train_ctx, 15.0),
    ("flash_attention_roofline", _train_ctx, 100.0 * _FLASH_FWD_LEAST / 0.02),
    ("device_idle_share", _train_ctx, 5.0),
])
def test_reader_arithmetic(reader, ctx, want):
    mod = importlib.import_module(f"benchmark.layer_metrics.{reader}")
    assert mod.read(ctx()) == pytest.approx(want, rel=1e-9)


# ------------------------------------------------------- the result line

_DEVICE = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}


@pytest.mark.parametrize("ctx", [_serve_ctx, _train_ctx])
def test_result_object_without_a_trace_holds_the_cell_s_end_to_end_metrics(ctx):
    ctx = ctx()
    trace = ctx.pop("trace")
    cell = ctx["cell"]
    out = bench_run.result_object(cell, ctx, _DEVICE, None, lambda msg: None)
    assert list(out) == ["correct", "attempted", "failed", "metrics", "device"]
    assert set(out["metrics"]) == {m["name"] for m in cell.end_to_end} and "setup_s" in out["metrics"]
    units = {m["name"]: m["unit"] for m in BENCH["end_to_end"]}
    assert all(set(v) == {"value", "unit"} and v["unit"] == units[k] and v["value"] > 0 for k, v in out["metrics"].items())
    assert out["device"] == dict(_DEVICE, memory_peak_bytes=int(ctx["memory_peak_bytes"]))
    json.dumps(out)
    # ... and with one, its per-layer metrics, the busy seconds and the breakdown
    out = bench_run.result_object(cell, ctx, _DEVICE, trace, lambda msg: None)
    assert list(out) == ["correct", "attempted", "failed", "metrics", "breakdown", "device"]
    assert set(out["metrics"]) == {m["name"] for m in cell.per_layer}
    assert out["device"]["busy_s"] == trace["busy_s"] and out["device"]["window_s"] == 2.0
    assert len(out["breakdown"]["device_ops"]) <= 10 and set(out["breakdown"]) == {"device_ops", "idle_gaps"}
    json.dumps(out)


def test_what_was_compared_is_printed_as_plain_numbers_beside_its_limit_and_why_a_run_is_not_correct():
    """``run.py`` ends a run's standard error, and its result line, with
    every number the driver's comparison held beside its limit."""
    import numpy as np

    ctx = {"compared": {"gap_ratio": [np.float32(1.5), 1.45], "state_error_at_0.5": [0.0, 4e-05], "near_tie_gap": [None, 8e-05]},
           "why_incorrect": ["gap_ratio 1.5 over the limit 1.45"]}
    assert json.loads(json.dumps(bench_run.compared_numbers(ctx))) == {"gap_ratio": [1.5, 1.45], "state_error_at_0.5": [0.0, 4e-05], "near_tie_gap": [None, 8e-05]}
    assert bench_run.compared_lines(ctx) == [
        "compared: gap_ratio 1.5 (limit 1.45)", "compared: state_error_at_0.5 0.0 (limit 4e-05)", "compared: near_tie_gap None (limit 8e-05)",
        "not correct: gap_ratio 1.5 over the limit 1.45",
    ]
    assert bench_run.compared_lines({"correct": True}) == []  # a driver that hands nothing over prints nothing


def test_a_run_that_lacks_an_end_to_end_metric_prints_no_result():
    ctx = _serve_ctx()
    ctx["records"] = []  # nothing was due: no first token, no gap
    with pytest.raises(RuntimeError, match="itl_p50_ms"):
        bench_run.result_object(ctx["cell"], ctx, _DEVICE, None, lambda msg: None)


def test_a_reader_that_finds_nothing_is_left_out_and_logged():
    said = []
    entries = [{"name": "setup_s", "unit": "s"}, {"name": "device_idle_share.itl", "unit": "%"}]
    assert layer_metrics.read_all(entries, {"setup_s": 12.5}, said.append) == {"setup_s": {"value": 12.5, "unit": "s"}}
    assert len(said) == 1 and "device_idle_share.itl" in said[0]


def test_memory_peak_counts_the_runtime_reservation_on_the_fullest_chip():
    class Dev:
        def __init__(self, in_use, reserved):
            self._m = {"peak_bytes_in_use": in_use, "peak_bytes_reserved": reserved}

        def memory_stats(self):
            return self._m

    assert stats.memory_peak_bytes([Dev(5, 1), Dev(2, 7), Dev(3, 3)]) == 9
