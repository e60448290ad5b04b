"""The cell ``sdar-30b-a3b-chat.block-gen`` (PR 45), as
``test_longcat_cell.py`` holds PR 41's: its files say what the issue
named, key for key; the parameter count is the family's from the file's
own sizes; ``sdar_model.py``'s arithmetic is hand-worked and no share
passes 100 %; every new reader on a hand-made run, and nothing raised
where a program lacks what this PR adds; and ONE run of the whole
command at rehearsal size on the CPU that finds every new reader."""
import json
import pathlib
import subprocess
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from benchmark import kernel_model, layer_metrics, sdar_model  # noqa: E402
from benchmark.reference import sdar  # noqa: E402

CELL = "sdar-30b-a3b-chat.block-gen"
WORKLOAD = json.loads((ROOT / f"benchmark/workloads/{CELL}.json").read_text())
CONFIG = json.loads((ROOT / "benchmark/configs/sdar-30b-a3b-chat.json").read_text())
TRAFFIC = json.loads((ROOT / "benchmark/traffic/block-gen.json").read_text())
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
CATALOG = pathlib.Path("/opt/skills/guides/model-configs/architectures.jsonl")
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
MINE = ("tokens_per_forward.served", "commit_forward_share.served", "block_step_ms.served", "block_step_roofline.served",
        "paged_block_attention_roofline.served", "block_prefill_ms_per_ktoken.served")
MODEL = {  # the cell's sizes, as drivers/serve_sdar.py::model_sizes gives them
    "num_layers": 7, "num_heads": 32, "kv_heads": 4, "head_dim": 128, "hidden_size": 2048, "moe_ff_size": 768,
    "num_experts": 128, "experts_per_token": 8, "vocab_size": 151936, "block_length": 4, "block_size": 64,
    "cache_itemsize": 2, "weight_itemsize": 2,
}


def test_the_configuration_is_the_catalog_s_row_cut_in_depth_alone():
    assert CONFIG["reduced"] == ["num_hidden_layers"] and CONFIG["num_hidden_layers"] == 7
    assert (CONFIG["hidden_size"], CONFIG["num_attention_heads"], CONFIG["num_key_value_heads"], CONFIG["head_dim"]) == (2048, 32, 4, 128)
    assert (CONFIG["num_experts"], CONFIG["moe_intermediate_size"], CONFIG["num_experts_per_tok"], CONFIG["vocab_size"]) == (128, 768, 8, 151936)
    if CATALOG.exists():
        row = next(r for r in map(json.loads, CATALOG.read_text().splitlines()) if r["name"] == "SDAR-30B-A3B-Chat")
        assert CONFIG["source"] == row["source_url"]
        assert sorted(k for k, v in row["config"].items() if CONFIG.get(k, "absent") != v) == CONFIG["reduced"]
        assert row["config"]["num_hidden_layers"] == 48
    g = CONFIG["generation"]
    assert (g["block_length"], g["mask_token_id"], g["denoising_steps"], g["remasking"]) == (4, 151669, 4, "low_confidence_static")
    assert {"block_length", "denoising_steps", "mask_token_id", "logits_not_shifted", "commit_forward", "qk_layernorm",
            "router_dtype", "softmax_dtype", "untied_head", "weights"} <= set(CONFIG["assumed"])
    assert all("Not taken" in CONFIG["assumed"][k] for k in ("block_length", "logits_not_shifted", "commit_forward"))
    for part in ("every layer whole on one TPU v5e", "pipeline stage 1 of 7", "layers 7-47 on six further chips", "embedding and the untied head"):
        assert part in CONFIG["cut"]["deployment"], part
    assert any("never a candidate" in d for d in CONFIG["departures"]) and any("by a flag" in d for d in CONFIG["departures"])
    assert {"context", "stop_inside_a_block"} <= set(CONFIG["not_served"])


def test_the_parameter_count_from_the_file_s_own_sizes():
    full, cut = sdar.parameter_count(CONFIG, 48), sdar.parameter_count(CONFIG)
    assert round(full["outside_experts"] / 1e6, 2) == 19.14 and round(full["expert"] / 1e6, 2) == 4.72
    assert round(full["layer"] / 1e6, 1) == 623.1 and round(full["ends"] / 1e6, 1) == 622.3
    assert round(full["total"] / 1e9, 1) == 30.5 and round(full["active"] / 1e9, 2) == 3.35  # "30B", "A3B"
    assert round(cut["total"] / 1e9, 2) == 4.98 and round(2 * cut["total"] / 1e9, 2) == 9.97
    assert 2 * cut["total"] / 16.9e9 > 0.25  # the floor of a cell's size, by the weights alone


def test_the_cell_is_the_one_the_issue_named_key_for_key():
    d, p = WORKLOAD["deployment"], TRAFFIC["params"]
    assert (WORKLOAD["config"], WORKLOAD["traffic"], WORKLOAD["chips"], WORKLOAD["driver"]) == ("sdar-30b-a3b-chat", "block-gen", 1, "serve_sdar")
    assert (d["block_size"], d["max_seq_len"], d["prompt_buckets"], d["denoising_steps"], d["remasking"]) == (
        64, 2048, [512, 1024], 2, "low_confidence_static")
    assert d["slots"] in (32, 48, 64) and WORKLOAD["traffic_params"]["clients"] == 2 * d["slots"]
    assert TRAFFIC["generator"] == "closed_clients" and p["clients"] is None
    assert p["prompt"] == {"dist": "uniform", "min": 256, "max": 1024}
    assert p["output"] == {"dist": "uniform", "min": 256, "max": 768, "stratified_block": 32}
    assert p["prompt"]["max"] + p["output"]["max"] <= d["max_seq_len"] and WORKLOAD["lead_in_s"] == 40.0
    assert WORKLOAD["reference_sample"] == 16 and len(WORKLOAD["why"]) <= 700


def test_benchmark_json_gained_one_configuration_one_cell_and_six_metrics_that_list_it():
    assert BENCH["configs"][-1]["name"] == "sdar-30b-a3b-chat" and BENCH["configs"][-1]["reduced"] == ["num_hidden_layers"]
    assert BENCH["workloads"][-1] == {k: BENCH["workloads"][-1][k] for k in ("name", "config", "traffic", "chips", "why")}
    assert BENCH["workloads"][-1]["name"] == CELL and BENCH["workloads"][-1]["chips"] == 1 and len(BENCH["workloads"]) == 10
    assert sum(w["chips"] == 4 for w in BENCH["workloads"]) == 1
    assert [m["name"] for m in BENCH["per_layer"][-6:]] == list(MINE)
    layers = {m["name"]: m["layer"] for m in BENCH["per_layer"][-6:]}
    assert layers == dict(zip(MINE, ("scheduler", "engine", "engine", "kernels", "kernels", "engine")))
    for m in BENCH["per_layer"][-6:]:
        assert m["moves"] == "served_tokens_per_s" and m["workloads"] == [CELL]
    served = next(m for m in BENCH["end_to_end"] if m["name"] == "served_tokens_per_s")
    assert served["workloads"][-1] == CELL and served["bound"] == 0.1
    listed = {m["name"] for m in BENCH["per_layer"] if CELL in m.get("workloads", [])}
    # the accepted readers that find something here; those that read a decode step are left unlisted
    assert {"cache_blocks_used_peak", "prefill_time_share.served", "device_idle_share.served", "host_dispatch_share.served",
            "expert_load_imbalance.served", "expert_tokens_per_call.served", "trace_record_share.served"} <= listed
    assert not listed & {"decode_step_ms.served", "batch_occupancy.served", "pipelined_step_share.served", "dispatch_offcpu_share.served"}


def test_a_block_forward_by_hand():
    w = sdar_model.weights(MODEL)
    assert w["attention"] == 2048 * 128 * (2 * 32 + 2 * 4) + 256 and w["expert"] == 3 * 2048 * 768
    # 64 slots x 4 rows = 256 rows: every expert is touched, to a part in ten million
    assert sdar_model.experts_touched(MODEL, 256) == pytest.approx(128.0, abs=1e-4)
    assert sdar_model.experts_touched(MODEL, 1) == pytest.approx(8.0)
    slots, context = 64, 64 * 1300
    ops, nbytes = sdar_model.block_forward(MODEL, slots, context)
    touched = sdar_model.experts_touched(MODEL, 256)
    weight_bytes = 2 * (7 * (w["attention"] + w["norms"] + touched * w["expert"]) + w["head"] + 257 * 2048) + 4 * 7 * w["router"]
    assert nbytes == pytest.approx(weight_bytes + 2 * 4 * 128 * 2 * 7 * (context + 256))
    assert 9.3e9 < weight_bytes < 9.4e9  # the issue's 9.35 GB a forward
    per_row = 7 * (w["attention"] + 8 * w["expert"] + w["router"]) + w["head"]
    assert ops == pytest.approx(2 * 256 * per_row + 7 * 4 * context * 4 * 32 * 128)
    least, bound = kernel_model.least_seconds(ops, nbytes, PEAKS)
    assert bound == "memory" and 0.0125 < least < 0.0135  # 11.4 ms of weights + 1.4 ms of K/V


def test_the_paged_call_at_a_block_s_rows_by_hand():
    ops, nbytes = sdar_model.paged_block_attention_call(1000.0, 2.0, MODEL)
    assert ops == 4 * 1000 * 4 * 32 * 128  # every row's query heads over its slot's context
    assert nbytes == 2 * 1000 * 4 * 128 * 2 + 2 * 8 * 32 * 128 * 2  # K and V read ONCE a slot, q in and the result out a row


def _ctx():
    diffusion = lambda k: {"slot_forwards_total": 3000 * k, "commit_forwards_total": 1000 * k, "tokens_fixed_total": 4000 * k,  # noqa: E731
                           "blocks_committed_total": 1000 * k}
    snap = lambda k: {"diffusion": diffusion(k), "prefill_attention": {"tokens_total": 10000 * k}}  # noqa: E731
    engine = lambda k: {"step_counts": {"block_step": 50 * k, "decode": 0, "prefill": 8 * k},  # noqa: E731
                        "phase_time_s": {"block_step": {"dispatch": 0.05 * k, "execute": 0.9 * k, "readback": 0.05 * k},
                                         "prefill": {"dispatch": 0.1 * k, "execute": 0.4 * k, "readback": 0.0}}}
    return {
        "stats_open": snap(1), "stats_close": snap(2), "engine_open": engine(1), "engine_close": engine(2),
        "window": (100.0, 150.0), "trace_abs": (147.0, 150.0), "model": dict(MODEL), "peaks": PEAKS,
        "records": [{"prompt_len": 510, "token_times": [146.0, 147.5, 148.0]}, {"prompt_len": 300, "token_times": [149.0, 151.0]}],
        "trace": {"programs": {"jit__block_impl": 0.9}, "kernel_s": {"paged_append_attention": 0.08, "paged_append_attention_split": 0.0},
                  "kernel_calls": {"paged_append_attention": 350}},
    }


def test_the_six_new_readers_on_a_hand_made_run():
    ctx = _ctx()
    assert layer_metrics.read("tokens_per_forward.served", ctx) == pytest.approx(4000 / 3000)
    assert layer_metrics.read("commit_forward_share.served", ctx) == pytest.approx(100 / 3)
    assert layer_metrics.read("block_step_ms.served", ctx) == pytest.approx(1000.0 / 50)
    assert layer_metrics.read("block_prefill_ms_per_ktoken.served", ctx) == pytest.approx(500.0 / 10)
    # traced: 350 kernel calls over 7 layers = 50 forwards; 3,000 slot forwards in 50 steps = 60 live slots a forward;
    # token events in the traced part at prompt + index 511, 512 and 300: to their blocks' ends 512, 516, 304
    forwards, slots, context = 50.0, 60.0, (512 + 516 + 304) / 3
    ops, nbytes = sdar_model.block_forward(MODEL, slots, slots * context)
    got = layer_metrics.read("block_step_roofline.served", ctx)
    assert got == pytest.approx(100 * kernel_model.least_seconds(forwards * ops, forwards * nbytes, PEAKS)[0] / 0.9) and 0 < got < 100
    ops, nbytes = sdar_model.paged_block_attention_call(slots * context, slots, MODEL)
    got = layer_metrics.read("paged_block_attention_roofline.served", ctx)
    assert got == pytest.approx(100 * kernel_model.least_seconds(350 * ops, 350 * nbytes, PEAKS)[0] / 0.08) and 0 < got < 100


@pytest.mark.parametrize("name", MINE)
def test_a_program_without_what_this_pr_adds_leaves_the_new_metrics_out(name):
    """On the parent there is no ``diffusion`` section, no ``block_step``
    step kind and no block length in the model's sizes: nothing to read,
    and nothing raised."""
    assert layer_metrics.read(name, {}) is None
    parent = _ctx()
    for key in ("stats_open", "stats_close"):
        del parent[key]["diffusion"]
    for key in ("engine_open", "engine_close"):
        del parent[key]["step_counts"]["block_step"], parent[key]["phase_time_s"]["block_step"]
    del parent["model"]["block_length"]
    parent["trace"]["programs"] = {"jit__decode_impl": 0.9}
    assert layer_metrics.read(name, parent) is None
    silent = dict(_ctx(), stats_close=_ctx()["stats_open"], engine_close=_ctx()["engine_open"])
    assert layer_metrics.read(name, silent) is None


def test_a_judged_request_s_trajectory():
    """Prompt of 6 (2 rows of its last block fixed), reply of 9: blocks at 4
    (2 rows open), 8 and 12 (whole); the block at 12 holds 3 reply tokens
    and a row the budget cut, so it is not judged."""
    prompt, reply, fixed_at = list(range(10, 16)), list(range(20, 29)), [1, 0, 0, 1, 1, 0, 0, 0, 1]
    lay = sdar.trajectory(prompt, reply, fixed_at, 4, pad_to=24, states=8)
    assert lay["valid"].sum() == 4 and list(lay["bases"][:4]) == [4, 4, 8, 8]
    assert list(lay["tokens"][:12]) == prompt + reply[:6] and not lay["tokens"][12:].any()
    assert lay["masked"][0].tolist() == [False, False, True, True] and lay["chosen"][0].tolist() == [False, False, False, True]
    assert lay["masked"][1].tolist() == [False, False, True, False] and lay["chosen"][1].tolist() == [False, False, True, False]
    assert lay["blocks"][1].tolist() == [14, 15, 0, 21] and lay["picked"][1].tolist() == [14, 15, 20, 21]
    assert lay["chosen"][2].tolist() == [True, False, False, True] and lay["chosen"][3].tolist() == [False, True, True, False]
    from benchmark.drivers import serve_sdar

    assert serve_sdar.whole_blocks(6, 9, 4) == (3, 1) and serve_sdar.whole_blocks(8, 8, 4) == (2, 0)


def test_the_whole_command_runs_the_cell_at_rehearsal_size():
    """``run.py --rehearse --trace 1``: tiny widths on the CPU backend, the
    whole control flow (weights from the seed, warm-up, HTTP, the closed
    loop, the counters' identities, the judged requests served once more
    and scored along their trajectories), no result line."""
    out = subprocess.run(
        [sys.executable, str(ROOT / "benchmark/run.py"), "--workload", CELL, "--seed", "4100000123", "--seconds", "3",
         "--trace", "1", "--rehearse"], capture_output=True, text=True, timeout=600, cwd=str(ROOT),
    )
    assert out.returncode == 0, out.stdout[-3000:] + out.stderr[-3000:]
    assert "rehearsal done: correct=True" in out.stdout and "failed=0" in out.stdout
    found = out.stdout.split("readers that found something: ")[1].splitlines()[0]
    for name in ("tokens_per_forward.served", "commit_forward_share.served", "block_step_ms.served", "block_prefill_ms_per_ktoken.served",
                 "expert_load_imbalance.served", "expert_tokens_per_call.served", "cache_blocks_used_peak", "prefill_time_share.served",
                 "host_dispatch_share.served", "admit_stall_mean_ms.served"):
        assert name in found, found
    # (the two shares of a roofline read a device trace: the chip's)
    assert "reference: gap_ratio 0.0000 (the tokens' part alone 0.0000, the rows' 0.0000)" in out.stdout  # float32 on the CPU is the reference's own arithmetic
    assert "block diffusion over blocks of 4, 2 steps, low_confidence_static" in out.stdout
    assert "kernels {'block_step': {'body': 'reference', 'group': 2}}" in out.stdout and "identities: " in out.stdout
