"""The cell ``lfm2-8b-a1b.gen-batch`` (PR 27), as the tests beside this
file hold the older cells: its files say what the issue named, its
limit lies between its recorded readings, its arithmetic is hand-worked,
the whole command runs at rehearsal size on the CPU, and the cell's own
limit fails the control there."""
import json
import pathlib
import subprocess
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from benchmark import kernel_model, layer_metrics, moe_model, spec  # noqa: E402

CELL = "lfm2-8b-a1b.gen-batch"
WORKLOAD = json.loads((ROOT / f"benchmark/workloads/{CELL}.json").read_text())
CONFIG = json.loads((ROOT / "benchmark/configs/lfm2-8b-a1b.json").read_text())
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
CATALOG = pathlib.Path("/opt/skills/guides/model-configs/architectures.jsonl")

MODEL = {  # the cell's sizes, as drivers/serve_lfm2.py::model_sizes gives them
    "num_layers": 16, "num_heads": 32, "kv_heads": 8, "head_dim": 64, "hidden_size": 2048, "ff_size": 7168,
    "moe_ff_size": 1792, "num_experts": 32, "experts_per_token": 4, "conv_kernel": 3, "vocab_size": 65536,
    "attention_layers": 4, "conv_layers": 12, "expert_layers": 14, "dense_layers": 2,
    "cache_itemsize": 2, "weight_itemsize": 2,
}


def test_the_configuration_is_the_catalog_s_row_cut_in_depth_alone():
    assert CONFIG["reduced"] == ["num_hidden_layers"] and CONFIG["num_hidden_layers"] == 16
    assert CONFIG["serving_dtype"] == CONFIG["cache_dtype"] == "bfloat16"
    if not CATALOG.exists():
        pytest.skip("no catalog here")
    row = next(json.loads(l) for l in CATALOG.read_text().splitlines() if '"LFM2-8B-A1B"' in l)
    assert CONFIG["source"] == row["source_url"]
    differs = [k for k, v in row["config"].items() if CONFIG.get(k) != v]
    assert differs == ["num_hidden_layers"]  # layer_types is the source's list, whole: the first 16 are read
    from benchmark.reference import lfm2

    s = lfm2.sizes(CONFIG)
    assert s["types"].count("conv") == 12 and s["types"].count("attention") == 4 and len(s["types"]) == 16
    w = moe_model.weights(MODEL)
    total = (14 * (32 * w["expert"] + w["router"]) + 2 * w["dense_ffn"] + 12 * w["conv"] + 4 * w["attention"]
             + 16 * w["norms"] + w["embedding"])
    assert abs(total / 1e9 - 5.40) < 0.01  # 5.40 G parameters = 10.8 GB in bfloat16, as the file says


def test_the_cell_is_the_one_the_issue_named():
    cell = spec.load_cell(CELL)
    p, d = cell.traffic["params"], cell.workload["deployment"]
    assert cell.chips == 1 and cell.driver == "serve_lfm2" and cell.traffic["generator"] == "closed_clients"
    assert p["clients"] == 2 * d["slots"] and d["slots"] in (16, 32, 64)
    assert p["prompt"] == {"dist": "uniform", "min": 64, "max": 448}  # independent draws, as named
    assert p["output"] == {"dist": "uniform", "min": 128, "max": 512, "stratified_block": 32}
    # the deployment the issue named and no server option beside it: everything else at the server's defaults
    assert set(d) == {"slots", "block_size", "max_seq_len", "prompt_buckets", "slots_why", "slot_sweep"}
    assert not p.get("stratified")  # by blocks of the list, not over the whole of it: a closed loop uses a stretch
    assert d["block_size"] == 16 and d["max_seq_len"] == 1024 and d["prompt_buckets"] == [128, 256, 512]
    assert p["prompt"]["max"] + p["output"]["max"] <= d["max_seq_len"]
    e2e = {m["name"] for m in cell.end_to_end}
    assert e2e == {"served_tokens_per_s", "setup_s"}
    # the slot count: the smallest of 16 / 32 / 64 within 10 % of the best, from the three readings kept in the file
    sweep = {int(k): v for k, v in d["slot_sweep"]["served_tokens_per_s"].items()}
    assert set(sweep) == {16, 32, 64} and all(v > 0 for v in sweep.values())  # three readings, none missing
    assert d["slots"] == min(s for s, v in sweep.items() if v >= 0.9 * max(sweep.values()))
    # twice the completions per second measured, so that the closed loop never runs out
    assert p["max_rate_per_s"] >= 2.0 * d["slot_sweep"]["completions_per_s"][str(d["slots"])] * 0.95


def test_every_new_metric_lists_the_cell_and_is_read_there():
    new = [m for m in BENCH["per_layer"] if m.get("workloads") == [CELL]]
    assert {m["name"] for m in new} >= {"expert_load_imbalance.served", "expert_tokens_per_call.served",
                                        "moe_decode_roofline.served", "paged_gqa_attention_roofline.served"}
    assert all(m["moves"] == "served_tokens_per_s" for m in new)
    assert BENCH["workloads"][-1]["name"] == CELL and BENCH["configs"][-1]["name"] == "lfm2-8b-a1b"
    assert sum(w["chips"] == 4 for w in BENCH["workloads"]) == 1  # no 4-chip cell was added


def test_the_limit_lies_between_the_sound_runs_and_the_control_with_room():
    """Both readings and the limit are in the cell's file, a dozen seeds
    each. The statistic is a ratio against the stated arithmetic's own
    distance (``reference/lfm2.py::gap_ratio``), so the sound runs sit
    about 1 and what counts is room on both sides of the limit in units
    of the readings' own spread."""
    c = WORKLOAD["correct"]
    sound, control = c["sound"], c["control"]["int8_weights"]
    assert sound["seeds"] >= 12 and control["seeds"] >= 12
    assert 0 < sound["smallest"] <= sound["largest"] < control["smallest"] <= control["largest"]
    assert c["limit"] == WORKLOAD["gap_ratio_limit"]
    room = min(c["limit"] - sound["largest"], control["smallest"] - c["limit"])
    assert room >= 2.0 * max(sound["largest"] - sound["smallest"], control["largest"] - control["smallest"]) / 2.0
    assert room >= 0.1 * c["limit"]
    assert WORKLOAD["reference_sample"] >= 32 and "gap_ratio" in c["what"] and "near_tie_gap" in c["rejected"]
    # the same ratio request by request, the largest: its own two readings and its own limit between them
    w_sound, w_control = sound["worst_request"], control["worst_request"]
    assert len(w_sound["every_seed"]) >= 12 and len(w_control["every_seed"]) >= 12
    assert max(w_sound["every_seed"]) < c["request_limit"] < min(w_control["every_seed"])
    assert c["request_limit"] == WORKLOAD["request_gap_ratio_limit"]
    assert min(c["request_limit"] - max(w_sound["every_seed"]), min(w_control["every_seed"]) - c["request_limit"]) >= 0.1
    # the other step below the stated arithmetic was read too, whatever it says
    assert len(c["control"]["bfloat16_sums"]["every_seed"]) >= 12


def test_gqa_attention_by_hand():
    # 10 attended positions, 2 rows, 32 query heads over 8 K/V heads of 64, a bfloat16 cache
    ops, nbytes = moe_model.paged_gqa_attention_call(10, 2, 32, 8, 64, 2)
    assert ops == 4 * 10 * 32 * 64  # over the QUERY heads
    assert nbytes == 2 * 10 * 8 * 64 * 2 + 2 * 2 * 32 * 64 * 2  # K and V over the K/V heads; q in, out
    # a group of 1 at float32 is kernel_model's plain call
    assert moe_model.paged_gqa_attention_call(7, 3, 16, 16, 64, 4, io_itemsize=4) == kernel_model.paged_attention_call(7, 3, 16, 64, 4)


def test_decode_step_bytes_by_hand():
    w = moe_model.weights(MODEL)
    assert w["expert"] == 3 * 2048 * 1792 and w["conv"] == 4 * 2048 * 2048 + 3 * 2048
    ops, nbytes = moe_model.moe_decode_step(MODEL, rows=32, context_tokens=32 * 500, experts_touched=32)
    # all 32 experts touched: every weight of the 5.40 G once, at 2 bytes (the float32 routers at 4)
    assert abs(nbytes / 1e9 - (10.8 + 4 * 2 * 8 * 64 * 2 * 32 * 501 / 1e9 + 2 * 32 * 12 * 2 * 2048 * 2 / 1e9)) < 0.02
    fewer = moe_model.moe_decode_step(MODEL, 32, 32 * 500, 16)[1]
    assert abs((nbytes - fewer) - 14 * 16 * w["expert"] * 2) < 1  # an expert no token chose is not read
    assert 13.0e-3 < kernel_model.least_seconds(ops, nbytes, {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9})[0] < 13.6e-3
    # a row's matmuls: 4 of 32 experts, not all
    per_row = (ops - 4 * 4.0 * 32 * 500 * 32 * 64) / (2 * 32)
    assert abs(per_row - (14 * (4 * w["expert"] + w["router"]) + 2 * w["dense_ffn"] + 12 * w["conv"] + 4 * w["attention"]
                          + w["embedding"])) < 1


def _experts(tokens, decode, prefill):
    return {"layers": [2, 3], "tokens_total": tokens, "decode_calls_total": decode, "prefill_calls_total": prefill}


def test_expert_readers_by_hand():
    ctx = {"stats_open": {"experts": _experts([10, 10, 10, 10], 5, 1)},
           "stats_close": {"experts": _experts([50, 30, 20, 20], 25, 5)}}
    # growth 40, 20, 10, 10: the busiest over the mean of 20
    assert layer_metrics.read("expert_load_imbalance.served", ctx) == pytest.approx(2.0)
    # 80 tokens over 4 experts x 2 layers x 24 calls
    assert layer_metrics.read("expert_tokens_per_call.served", ctx) == pytest.approx(80 / (4 * 2 * 24))
    for name in ("expert_load_imbalance.served", "expert_tokens_per_call.served", "moe_decode_roofline.served",
                 "paged_gqa_attention_roofline.served"):
        assert layer_metrics.read(name, {}) is None  # a program without the counters: left out, not raised
        assert layer_metrics.read(name, {"stats_open": {}, "stats_close": {}, "trace": {"programs": {}, "kernel_s": {
            "paged_append_attention": 0.0, "paged_append_attention_split": 0.0}}, "model": {"num_heads": 16}}) is None


def test_roofline_readers_by_hand():
    records = [{"prompt_len": 100, "token_times": [0.5, 1.5, 2.5, 3.5]}, {"prompt_len": 50, "token_times": [1.2, 2.2]}]
    ctx = {
        "records": records, "window": (0.0, 4.0), "trace_abs": (1.0, 3.0), "model": MODEL,
        "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
        "engine_open": {"step_counts": {"decode": 10}}, "engine_close": {"step_counts": {"decode": 12}},
        "trace": {"programs": {"jit__decode_impl": 0.040, "jit__prefill_impl": 0.5},
                  "kernel_s": {"paged_append_attention": 1e-4, "paged_append_attention_split": 0.0}},
    }
    # in the traced part: tokens 1 and 2 of the first request (contexts 101, 102) and token 1 of the second (51)
    ops, nbytes = moe_model.paged_gqa_attention_call(254, 3, 32, 8, 64, 2)
    least = kernel_model.least_seconds(4 * ops, 4 * nbytes, ctx["peaks"])[0]
    assert layer_metrics.read("paged_gqa_attention_roofline.served", ctx) == pytest.approx(100 * least / 1e-4)
    # over the window 4 decode tokens in 2 steps: 2 rows a step, so the traced 3 rows are 1.5 steps
    touched = 32 * (1 - (1 - 4 / 32) ** 2)
    ops, nbytes = moe_model.moe_decode_step(MODEL, 2.0, 254 / 1.5, touched)
    least = kernel_model.least_seconds(1.5 * ops, 1.5 * nbytes, ctx["peaks"])[0]
    got = layer_metrics.read("moe_decode_roofline.served", ctx)
    assert got == pytest.approx(100 * least / 0.040) and 0 < got < 100


def test_the_whole_command_runs_the_cell_at_rehearsal_size():
    """``run.py --rehearse``: tiny widths on the CPU backend, the whole
    control flow (weights from the seed, warm-up, HTTP, the closed loop,
    the counters' readers, the reference's verdict), no result line."""
    out = subprocess.run(
        [sys.executable, str(ROOT / "benchmark/run.py"), "--workload", CELL, "--seed", "2700000123", "--seconds", "3",
         "--trace", "0", "--rehearse"], capture_output=True, text=True, timeout=600, cwd=str(ROOT),
    )
    assert out.returncode == 0, out.stdout[-3000:] + out.stderr[-3000:]
    assert "rehearsal done: correct=True" in out.stdout and "failed=0" in out.stdout
    found = out.stdout.split("readers that found something: ")[1].splitlines()[0]
    assert "served_tokens_per_s" in found and "setup_s" in found
    assert "reference: gap_ratio 0.0000" in out.stdout  # float32 on the CPU is the reference's own arithmetic
    # set-up left the prefix cache where the traffic keeps it (the cell's
    # set_up_state): the tier at its budget at both edges, and what the
    # window evicted it dropped, reading nothing out
    import re

    tier = re.search(r"host tier: (\d+) blocks read out to it inside the window of (\d+) evicted; it held (\d+) of (\d+) "
                     r"bytes at the open, (\d+) at the close", out.stdout)
    assert tier, out.stdout[-3000:]
    read_out, evicted, at_open, budget, at_close = map(int, tier.groups())
    assert read_out == 0 and evicted > 0 and at_open == at_close == budget > 0


@pytest.mark.parametrize("arm, correct", [("program", True), ("int8", False)])
def test_the_cell_s_own_limit_fails_the_control_at_rehearsal_size(arm, correct, _row={}):
    """As ``test_control.py`` for the GPT-2 cells: the program's tokens
    read under the cell's own limit, and the reference on int8-rounded
    weights, put in the program's place, reads over it."""
    if not _row:
        from benchmark.tools import lfm2_check

        _row.update(lfm2_check.readings(spec.load_cell(CELL, rehearsal=True), 2700000123))
    assert _row["limit"] == WORKLOAD["gap_ratio_limit"]  # the cell's own limit, not one for the test
    read = _row[arm]
    assert read["tokens"] >= 200 and read["near_ties"] >= 5 and _row["bfloat16"]["gap_ratio"] == 1.0
    assert (read["gap_ratio"] <= _row["limit"]) == correct, _row
    assert _row["request_limit"] == WORKLOAD["request_gap_ratio_limit"]
    assert (read["worst_request_ratio"] <= _row["request_limit"]) == correct, _row
    if not correct:
        assert read["off_argmax"] > _row["bfloat16"]["off_argmax"] > 0, _row
