"""The cell ``mellum2-12b.code-gen`` (PR 31), as ``test_lfm2_cell.py``
holds PR 27's: its files say what the issue named, key for key; each
limit lies between its recorded readings; the new readers' arithmetic is
hand-worked; the whole command runs at rehearsal size on the CPU with
``correct`` true, and the cell's own limits fail its controls there."""
import json
import pathlib
import re
import statistics
import subprocess
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from benchmark import kernel_model, layer_metrics, mellum2_model, moe_model, spec  # noqa: E402

CELL = "mellum2-12b.code-gen"
WORKLOAD = json.loads((ROOT / f"benchmark/workloads/{CELL}.json").read_text())
CONFIG = json.loads((ROOT / "benchmark/configs/mellum2-12b.json").read_text())
TRAFFIC = json.loads((ROOT / "benchmark/traffic/code-gen.json").read_text())
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
CATALOG = pathlib.Path("/opt/skills/guides/model-configs/architectures.jsonl")
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}

MODEL = {  # the cell's sizes, as drivers/serve_mellum2.py::model_sizes gives them
    "num_layers": 12, "num_heads": 32, "kv_heads": 4, "head_dim": 128, "hidden_size": 2304, "moe_ff_size": 896,
    "num_experts": 64, "experts_per_token": 8, "vocab_size": 98304, "attention_layers": 3, "window_layers": 9,
    "window": 1024, "block_size": 16, "expert_layers": 12, "cache_itemsize": 2, "weight_itemsize": 2,
}


def test_the_configuration_is_the_catalog_s_row_cut_in_depth_alone():
    assert CONFIG["reduced"] == ["num_hidden_layers"] and CONFIG["num_hidden_layers"] in (12, 8)
    assert CONFIG["serving_dtype"] == CONFIG["cache_dtype"] == "bfloat16"
    if CATALOG.exists():
        row = next(json.loads(l) for l in CATALOG.read_text().splitlines() if '"Mellum2-12B-A2.5B-Instruct"' in l)
        assert CONFIG["source"] == row["source_url"]
        assert [k for k, v in row["config"].items() if CONFIG.get(k) != v] == ["num_hidden_layers"]
    from benchmark.reference import mellum2

    s = mellum2.sizes(CONFIG)
    n = CONFIG["num_hidden_layers"]
    assert s["types"] == ("sliding_attention", "sliding_attention", "sliding_attention", "full_attention") * (n // 4)
    assert dict(s["yarn"])["attention_factor"] == 1.2772588722239782 and s["window"] == 1024 and s["theta"] == 500000.0
    w = mellum2_model.weights(MODEL)
    layer = w["attention"] + w["router"] + 64 * w["expert"] + w["norms"]
    assert abs(layer / 1e6 - 417.7) < 0.1 and abs(2 * w["head"] / 1e6 - 453.0) < 0.1
    assert abs((12 * layer + 2 * w["head"]) / 1e9 - 5.47) < 0.01  # 10.93 GB in bfloat16, as the file says
    assert "mtp_head" in CONFIG["not_served"] and "qk_layernorm" in CONFIG["assumed"]
    r = CONFIG["rehearsal"]  # the preset the issue named
    assert (r["hidden_size"], r["num_attention_heads"], r["num_key_value_heads"], r["head_dim"]) == (64, 4, 2, 16)
    assert (r["num_experts"], r["num_experts_per_tok"], r["num_hidden_layers"], r["sliding_window"]) == (8, 2, 8, 16)
    assert r["rope_parameters"]["full_attention"]["original_max_position_embeddings"] == 32


def test_the_cell_is_the_one_the_issue_named_key_for_key():
    cell = spec.load_cell(CELL)
    p, d = cell.traffic["params"], cell.workload["deployment"]
    assert cell.chips == 1 and cell.driver == "serve_mellum2" and cell.traffic["generator"] == "closed_clients"
    assert p["clients"] == 2 * d["slots"] and d["slots"] in (16, 32, 48, 64)
    # the lengths the issue named: prompts drawn independently, replies stratified within every 32 consecutive requests
    assert p["prompt"] == {"dist": "uniform", "min": 1024, "max": 2048} and not p.get("stratified")
    assert p["output"] == {"dist": "uniform", "min": 128, "max": 640, "stratified_block": 32}
    assert (d["max_seq_len"], d["prompt_buckets"]) == (3072, [1536, 2048])
    # the deployment the issue named and no server option beside it: everything else at the server's defaults
    assert set(d) == {"slots", "block_size", "max_seq_len", "prompt_buckets", "slots_why", "block_size_why", "slot_sweep", "block_sweep",
                      "completions_per_s", "completions_per_s_why"}
    # block 16 unless a sweep of 16 / 32 / 64 recorded in the cell's file says otherwise: the best of the three read
    blocks = {int(k): v for k, v in d["block_sweep"]["served_tokens_per_s"].items()}
    assert set(blocks) == {16, 32, 64} and d["block_size"] == max(blocks, key=blocks.get)
    assert p["prompt"]["max"] + p["output"]["max"] <= d["max_seq_len"] and p["prompt"]["min"] >= 1024  # every decode step past the window
    assert (cell.workload["lead_in_s"], cell.workload["drain_s"]) == (40.0, 90.0)
    assert {m["name"] for m in cell.end_to_end} == {"served_tokens_per_s", "setup_s"}
    assert cell.workload["reference_sample"] >= 16 and cell.workload["reference_tokens_least"] >= 4000
    # the slot count: the smallest of those read whose served_tokens_per_s is within 10 % of the best
    sweep = {int(k): v for k, v in d["slot_sweep"]["served_tokens_per_s"].items()}
    assert set(sweep) >= {16, 32, 48} and all(v > 0 for v in sweep.values())
    assert d["slots"] == min(s for s, v in sweep.items() if v >= 0.9 * max(sweep.values()))
    # the list: 2.5-4 x the completions a second of the accepted tree the cell's file states (PR 55: the sweep's own rate
    # stays the record of PR 31 that it is, and the program has more than doubled since)
    rate = d["completions_per_s"]
    assert 2.5 * rate <= TRAFFIC["params"]["max_rate_per_s"] <= 4.0 * rate and rate > 2 * d["slot_sweep"]["completions_per_s"][str(d["slots"])]
    assert "PR 5" in d["completions_per_s_why"] and "max_rate_why" in TRAFFIC


def test_benchmark_json_gained_one_configuration_one_cell_and_metrics_that_list_it():
    assert [c["name"] for c in BENCH["configs"]].count("mellum2-12b") == 1
    entry = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    assert (entry["config"], entry["traffic"], entry["chips"]) == ("mellum2-12b", "code-gen", 1)
    assert sum(w["chips"] == 4 for w in BENCH["workloads"]) == 1  # no 4-chip cell was added
    mine = {m["name"]: m for m in BENCH["per_layer"] if m.get("workloads") == [CELL]}
    assert set(mine) == {"paged_window_attention_roofline.served", "window_decode_roofline.served", "window_cache_saving.served"}
    assert [m["name"] for m in BENCH["per_layer"][-3:]] == list(mine)  # appended; no accepted reader under a second name
    assert len({m["name"].split(".")[0] + "." + m["moves"] for m in BENCH["per_layer"]}) == len(BENCH["per_layer"])
    assert all(m["moves"] == "served_tokens_per_s" for m in mine.values())
    assert mine["window_cache_saving.served"]["layer"] == "cache" and mine["window_cache_saving.served"]["source"] == "program_counter"
    listed = {m["name"] for m in BENCH["per_layer"] if CELL in m.get("workloads", [])}
    assert listed >= set(mine) | {
        "batch_occupancy.served", "cache_blocks_used_peak", "decode_step_ms.served", "prefill_time_share.served",
        "device_idle_share.served", "admit_stall_mean_ms.served", "host_dispatch_share.served", "host_readback_share.served",
        "host_sched_share.served", "cache_offload_share.served", "pipelined_step_share.served",
        # PR 27's readers, unchanged: the experts' counters, and the accepted kernel's calls (the 3 full layers')
        "expert_load_imbalance.served", "expert_tokens_per_call.served", "paged_gqa_attention_roofline.served",
    }
    # every reader the cell's metrics name exists
    for name in listed:
        assert (ROOT / "benchmark/layer_metrics" / f"{name.split('.')[0]}.py").exists(), name


def test_each_limit_lies_between_the_sound_runs_and_every_control():
    """The readings and both limits are in the cell's file. The four
    controls: a step coarser than stated (int8 weights, bfloat16 sums)
    and this model's own mechanisms done wrong (window ignored, plain
    rotary in the full layers); a limit that passed either of the last
    two would be remade."""
    c = WORKLOAD["correct"]
    sound = c["sound"]
    assert set(c["control"]) == {"int8_weights", "bfloat16_sums", "window_ignored", "plain_rotary"}
    assert c["limit"] == WORKLOAD["gap_ratio_limit"] and c["request_limit"] == WORKLOAD["request_excess_limit"]
    assert sound["seeds"] == len(sound["every_seed"]) == len(sound["worst_request"]["every_seed"]) >= 6
    assert max(sound["every_seed"]) < c["limit"] and max(sound["worst_request"]["every_seed"]) < c["request_limit"]
    for name, control in c["control"].items():
        assert len(control["every_seed"]) == len(control["worst_request"]["every_seed"]) == sound["seeds"], name
        pooled, worst = min(control["every_seed"]) > c["limit"], min(control["worst_request"]["every_seed"]) > c["request_limit"]
        assert pooled or worst, name  # by one of the cell's limits at least
        assert control["fails"] == [n for n, over in (("limit", pooled), ("request_limit", worst)) if over], name
    for name in ("window_ignored", "plain_rotary"):  # this model's own mechanisms done wrong: both limits
        assert c["control"][name]["fails"] == ["limit", "request_limit"], name
    for name in ("int8_weights", "bfloat16_sums"):  # a step coarser: the pooled limit, set against them with room
        assert "limit" in c["control"][name]["fails"], name
        assert min(c["control"][name]["every_seed"]) - c["limit"] >= 0.2 and c["limit"] - max(sound["every_seed"]) >= 0.2
    assert c["request_limit"] >= 5 * max(sound["worst_request"]["every_seed"])  # room above the sound runs ...
    assert 4 * c["request_limit"] <= min(c["control"]["plain_rotary"]["worst_request"]["every_seed"])  # ... and below the mechanisms


def test_the_recorded_spread_is_what_the_runs_read_and_says_where_it_misses():
    """Every set of six is recorded with its runs and its spread as the
    driver's instructions define it. The 2 % that PERF.md section 2's
    rule asks of a bound of 0.1 is NOT reached, and one set reads over
    the half of the bound that the benchmark check admits a set at: the
    file names that set and says so, and this test holds it to that."""
    six = WORKLOAD["spread_of_six"]
    runs = [v for s in six["sets"] for v in s["served_tokens_per_s"]]
    assert len(six["sets"]) >= 2 and all(len(s["served_tokens_per_s"]) == 6 for s in six["sets"])

    def spread(values):  # statistics.quantiles, as the instructions say (numpy's quartiles, and stats.quartile_spread's, lie closer)
        q = statistics.quantiles(values, n=4)
        return (q[2] - q[0]) / statistics.median(values)

    assert six["quartile_distance_over_median"] == pytest.approx(spread(runs), abs=5e-4)
    bound = next(m["bound"] for m in BENCH["end_to_end"] if m["name"] == "served_tokens_per_s")
    for s in six["sets"] + six["with_the_prompts_stratified"]["sets"]:
        assert s["quartile_distance_over_median"] == pytest.approx(spread(s["served_tokens_per_s"]), abs=5e-4), s["name"]
    over = [s["name"] for s in six["sets"] if s["quartile_distance_over_median"] >= 0.5 * bound]
    assert six["sets_over_half_the_bound"] == over and (not over or "may refuse the cell" in six["note"])
    assert (six["quartile_distance_over_median"] <= 0.2 * bound) or "ABOVE the 2 %" in six["note"]
    # by the token (the runs that logged it): the work done inside a window is steadier than what the metric counts of it
    for s in six["sets"]:
        if "by_the_token" in s:
            emitted, prefilled = s["reply_tokens_emitted_inside_the_window"], s["prompt_tokens_prefilled_inside_the_window"]
            work = [1.2e-3 * e + 0.108e-3 * p for e, p in zip(emitted, prefilled)]
            assert s["by_the_token"]["work_s_spread"] == pytest.approx(spread(work), abs=5e-4)
            assert spread(work) < s["quartile_distance_over_median"]
            assert s["by_the_token"]["emitted_spread"] == pytest.approx(spread(emitted), abs=5e-4)
    assert WORKLOAD["memory_peak_bytes"] >= 0.25 * 16.9e9


def test_window_attention_by_hand():
    # rows at contexts 500 (inside the window), 1,040 (window + block, exactly) and 2,600 (far past it)
    assert mellum2_model.window_positions([500, 1040, 2600], MODEL) == 500 + 1040 + 1040
    ops, nbytes = mellum2_model.paged_window_attention_call(2580, 3, MODEL)
    assert ops == 4 * 2580 * 32 * 128  # over the 32 QUERY heads
    assert nbytes == 2 * 2580 * 4 * 128 * 2 + 2 * 3 * 32 * 128 * 2  # K and V over the 4 K/V heads of 128 at 2 B; q in, out
    assert (ops, nbytes) == moe_model.paged_gqa_attention_call(2580, 3, 32, 4, 128, 2)


def test_decode_step_bytes_by_hand():
    w = mellum2_model.weights(MODEL)
    assert w["expert"] == 3 * 2304 * 896 and w["attention"] == 2304 * 128 * 72 + 256 and w["head"] == 98304 * 2304
    rows, ctx = 32, 32 * 1730
    reach = 32 * 1040
    ops, nbytes = mellum2_model.window_decode_step(MODEL, rows, ctx, reach, 64)
    # all 64 experts touched: 12 layers of 417.7 M and the head, at 2 bytes: 10.5 GB; K/V 3 full + 9 window layers
    kv = 2 * 4 * 128 * 2 * (3 * (ctx + rows) + 9 * (reach + rows))
    assert abs(nbytes / 1e9 - (12 * 417.7e6 * 2 + 98304 * 2304 * 2) / 1e9 - kv / 1e9) < 0.02
    assert 11.3 < nbytes / 1e9 < 11.6 and abs(kv / 1e6 - (340.8 + 614.0)) < 1.0
    fewer = mellum2_model.window_decode_step(MODEL, rows, ctx, reach, 40)[1]
    assert abs((nbytes - fewer) - 12 * 24 * w["expert"] * 2) < 1  # an expert no token chose is not read
    assert 13.8e-3 < kernel_model.least_seconds(ops, nbytes, PEAKS)[0] < 14.1e-3  # memory-bound: 11.4 GB at 819 GB/s
    per_row = (ops - 4.0 * (3 * ctx + 9 * reach) * 32 * 128) / (2 * rows)
    assert abs(per_row - (12 * (w["attention"] + 8 * w["expert"] + w["router"]) + w["head"])) < 1  # 8 of 64 experts


def _ctx():
    records = [{"prompt_len": 1500, "token_times": [0.5, 1.5, 2.5, 3.5]}, {"prompt_len": 900, "token_times": [1.2, 2.2]}]
    return {
        "records": records, "window": (0.0, 4.0), "trace_abs": (1.0, 3.0), "model": MODEL, "peaks": PEAKS,
        "engine_open": {"step_counts": {"decode": 10}}, "engine_close": {"step_counts": {"decode": 12}},
        "trace": {"programs": {"jit__decode_impl": 0.080, "jit__prefill_impl": 0.5},
                  "kernel_s": {"paged_append_attention": 2e-4, "paged_append_attention_split": 0.0}},
        "window_kernels": {"kernel_s": {"paged_window_attention": 3e-4, "paged_window_attention_split": 0.0},
                           "kernel_calls": {"paged_window_attention": 18, "paged_window_attention_split": 0}},
    }


def test_the_four_new_readers_on_a_hand_made_run():
    ctx = _ctx()
    # traced: tokens 1 and 2 of the first request (contexts 1,501 and 1,502) and token 1 of the second (901)
    ops, nbytes = mellum2_model.paged_window_attention_call(1040 + 1040 + 901, 3, MODEL)
    least = kernel_model.least_seconds(9 * ops, 9 * nbytes, PEAKS)[0]
    got = layer_metrics.read("paged_window_attention_roofline.served", ctx)
    assert got == pytest.approx(100 * least / 3e-4) and 0 < got < 100
    # the full layers' calls are the accepted kernel's, 3 a step over the whole context
    ops, nbytes = moe_model.paged_gqa_attention_call(3904, 3, 32, 4, 128, 2)
    assert layer_metrics.read("paged_gqa_attention_roofline.served", ctx) == pytest.approx(
        100 * kernel_model.least_seconds(3 * ops, 3 * nbytes, PEAKS)[0] / 2e-4)
    # over the window 4 decode tokens in 2 steps: 2 rows a step, so the traced 3 rows are 1.5 steps
    touched = 64 * (1 - (1 - 8 / 64) ** 2)
    ops, nbytes = mellum2_model.window_decode_step(MODEL, 2.0, 3904 / 1.5, 2981 / 1.5, touched)
    got = layer_metrics.read("window_decode_roofline.served", ctx)
    assert got == pytest.approx(100 * kernel_model.least_seconds(1.5 * ops, 1.5 * nbytes, PEAKS)[0] / 0.080) and 0 < got < 100
    # two samples: half saved, a quarter saved
    ctx["stats_samples"] = [{"cache": {"live_bytes": 50, "one_table_bytes": 100}}, {"cache": {"live_bytes": 75, "one_table_bytes": 100}},
                            {"cache": {"live_bytes": 0, "one_table_bytes": 0}}, {}]
    assert layer_metrics.read("window_cache_saving.served", ctx) == pytest.approx(37.5)


@pytest.mark.parametrize("name", ["paged_window_attention_roofline.served", "window_decode_roofline.served",
                                  "window_cache_saving.served"])
def test_a_program_without_the_window_leaves_the_new_metrics_out(name):
    """On the parent there is no window kernel, no ``cache`` section and
    no window in the model's sizes: nothing to read, and nothing raised."""
    assert layer_metrics.read(name, {}) is None
    lfm2 = dict(_ctx(), model={"num_heads": 32, "kv_heads": 8, "expert_layers": 14}, window_kernels=None,
                stats_samples=[{"cache_blocks_total": 10}])
    assert layer_metrics.read(name, lfm2) is None
    silent = dict(_ctx(), window_kernels={"kernel_s": {"paged_window_attention": 0.0}, "kernel_calls": {}})
    silent["trace"] = {"programs": {}, "kernel_s": {}}
    assert layer_metrics.read(name, silent) is None


def test_the_whole_command_runs_the_cell_at_rehearsal_size():
    """``run.py --rehearse --trace 1``: tiny widths on the CPU backend,
    the whole control flow (weights from the seed, warm-up, the prefix
    cache aged, HTTP, the closed loop, the counters' readers, the
    reference's verdict), no result line."""
    out = subprocess.run(
        [sys.executable, str(ROOT / "benchmark/run.py"), "--workload", CELL, "--seed", "3100000123", "--seconds", "3",
         "--trace", "1", "--rehearse"], capture_output=True, text=True, timeout=600, cwd=str(ROOT),
    )
    assert out.returncode == 0, out.stdout[-3000:] + out.stderr[-3000:]
    assert "rehearsal done: correct=True" in out.stdout and "failed=0" in out.stdout
    found = out.stdout.split("readers that found something: ")[1].splitlines()[0]
    for name in ("window_cache_saving.served", "pipelined_step_share.served", "expert_load_imbalance.served",
                 "expert_tokens_per_call.served", "host_sched_share.served", "cache_blocks_used_peak"):
        assert name in found, found
    assert "reference: gap_ratio 0.0000" in out.stdout  # float32 on the CPU is the reference's own arithmetic
    held = re.search(r"'blocks_per_sequence': (\d+), 'held_by_a_sequence_peak': (\d+)", out.stdout)
    assert held and 0 < int(held.group(2)) <= int(held.group(1)) == 3
    released = re.search(r"window blocks released inside the window (\d+)", out.stdout)
    assert released and int(released.group(1)) > 0
    drains = re.search(r"'drains_total': (\{[^}]*\})", out.stdout)
    assert drains and "'pressure': 0" in drains.group(1)  # the release drains nothing


@pytest.mark.parametrize("arm, pooled, by_request", [
    ("program", True, True), ("window_ignored", False, False), ("plain_rotary", False, None),
])
def test_the_cell_s_own_limits_fail_the_controls_at_rehearsal_size(arm, pooled, by_request, _row={}):
    """The program's tokens, served through its own scheduler, read
    under both of the cell's limits; the window ignored, put in the
    program's place, reads over both; plain rotary in the full layers
    and int8 weights over the pooled one (at this size plain rotary
    reads 3.5-4.5 pooled where the cell's size reads 21-70, and its
    worst request's excess is smaller in proportion)."""
    if not _row:
        from benchmark.tools import mellum2_check

        _row.update(mellum2_check.readings(spec.load_cell(CELL, rehearsal=True), 3100000123))
    assert (_row["limit"], _row["request_limit"]) == (WORKLOAD["gap_ratio_limit"], WORKLOAD["request_excess_limit"])
    read = _row[arm]
    assert read["tokens"] >= 200 and _row["bfloat16"]["gap_ratio"] == 1.0
    assert (read["gap_ratio"] <= _row["limit"]) == pooled, _row
    if by_request is not None:
        assert (read["worst_request_excess"] <= _row["request_limit"]) == by_request, _row
