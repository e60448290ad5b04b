"""The cell ``phi-4-mini-flash-reasoning.think-gen`` (PR 57), as
``test_nemotron_cell.py`` holds PR 48's: the configuration is the catalog's row
with NOTHING cut; the cell's files say what the issue named, key for key;
``phi4flash_model.py``'s arithmetic is hand-worked and no share passes 100 %;
every new reader on a hand-made run, and nothing raised where a program lacks
what this PR adds; and ONE run of the whole command at rehearsal size on the
CPU. Membership in ``BENCHMARK.json``'s lists is asserted, never a list's END
or its length: the next PR appends."""
import json
import pathlib
import subprocess
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from benchmark import kernel_model, layer_metrics, phi4flash_model  # noqa: E402

CELL = "phi-4-mini-flash-reasoning.think-gen"
NAME = "phi-4-mini-flash-reasoning"
WORKLOAD = json.loads((ROOT / f"benchmark/workloads/{CELL}.json").read_text())
CONFIG = json.loads((ROOT / f"benchmark/configs/{NAME}.json").read_text())
TRAFFIC = json.loads((ROOT / "benchmark/traffic/think-gen.json").read_text())
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
CATALOG = pathlib.Path("/opt/skills/guides/model-configs/architectures.jsonl")
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
MINE = ("selective_update_roofline.served", "shared_kv_attention_roofline.served", "samba_decode_roofline.served",
        "shared_kv_step_share.served", "gmu_step_share.served", "prefill_cross_rows_skipped_share.served")
MODEL = {  # the cell's sizes, as drivers/serve_phi4flash.py::model_sizes gives them: the PUBLISHED ones
    "num_layers": 32, "mamba_layers": 9, "window_layers": 8, "gmu_layers": 7, "cross_layers": 7, "hidden_size": 2560, "ff_size": 10240,
    "num_heads": 40, "kv_heads": 20, "head_dim": 64, "mamba_inner": 5120, "state_size": 16, "dt_rank": 160, "conv_kernel": 4,
    "window": 512, "vocab_size": 200064, "block_size": 64, "cache_itemsize": 2, "weight_itemsize": 2,
}


def test_the_configuration_is_the_catalog_s_row_with_nothing_cut():
    if not CATALOG.exists():
        pytest.skip("the catalog is not on this machine")
    row = next(r for r in map(json.loads, CATALOG.read_text().splitlines()) if r["name"] == "Phi-4-mini-flash-reasoning")
    assert CONFIG["source"] == row["source_url"] and CONFIG["reduced"] == []
    assert [k for k, v in row["config"].items() if CONFIG.get(k) != v] == []  # every key of the catalog's, under its own name
    assert (CONFIG["num_hidden_layers"], CONFIG["vocab_size"], CONFIG["hidden_size"], CONFIG["serving_dtype"]) == (32, 200064, 2560, "bfloat16")
    assumed = CONFIG["assumed"]
    assert {"head_dim", "mamba_d_state", "mamba_d_conv", "mamba_expand", "mamba_dt_rank", "mamba_bias", "attention_bias", "differential_attention",
            "positions", "window", "split", "state_dtype", "ssm_init", "lambda_init"} <= set(assumed)
    assert [assumed[k]["value"] for k in ("head_dim", "mamba_d_state", "mamba_d_conv", "mamba_expand", "mamba_dt_rank")] == [64, 16, 4, 2, 160]
    assert all("origin" in assumed[k] for k in ("head_dim", "mamba_d_state", "mamba_d_conv", "mamba_expand", "mamba_dt_rank", "ssm_init"))
    assert any("float32" in d for d in CONFIG["departures"]) and "one TPU v5e chip" in CONFIG["deployment"]
    from benchmark.reference import phi4flash

    assert round(phi4flash.parameter_counts(CONFIG)["whole"] / 1e9, 2) == 3.85


def test_the_cell_is_the_one_the_issue_named_key_for_key():
    d, p = WORKLOAD["deployment"], TRAFFIC["params"]
    assert (WORKLOAD["config"], WORKLOAD["traffic"], WORKLOAD["chips"], WORKLOAD["driver"]) == (NAME, "think-gen", 1, "serve_phi4flash")
    assert (d["block_size"], d["max_seq_len"], d["prompt_buckets"]) == (64, 2304, [512, 1024])
    assert WORKLOAD["traffic_params"]["clients"] == 2 * d["slots"]
    assert TRAFFIC["generator"] == "closed_clients" and p["clients"] is None and p["max_rate_per_s"] is None
    assert p["prompt"] == {"dist": "uniform", "min": 256, "max": 1024}
    assert p["output"] == {"dist": "uniform", "min": 640, "max": 1280, "stratified_block": 32}
    assert p["prompt"]["max"] + p["output"]["max"] <= d["max_seq_len"]
    assert (WORKLOAD["lead_in_s"], WORKLOAD["reference_sample"]) == (40.0, 16) and "max_queue" not in d
    # the list a closed loop draws from: three times the completions a second the finished change sustains (M13's rule)
    assert WORKLOAD["traffic_params"]["max_rate_per_s"] == pytest.approx(3 * d["completions_per_s"], rel=0.15)
    # the repo's slot rule over the counts that ran to their end: the smallest within 10 % of the best
    sweep = d["slot_sweep"]
    medians = {int(s): m for s, m in sweep["median"].items()}
    assert d["slots"] == min(s for s, m in medians.items() if m >= 0.9 * max(medians.values()))
    assert set(sweep["does_not_fit"]) >= {"128"} and all(len(why) > 60 for why in sweep["does_not_fit"].values())
    for limit in ("gap_ratio_limit", "request_excess_limit", "state_error_limits"):
        assert limit in WORKLOAD["tolerances"] and len(WORKLOAD["tolerances"][limit]) > 100, limit  # every tolerance with its reason
    assert set(WORKLOAD["state_error_limits"]) == {"0.5", "0.9"}  # a median AND a high share (PR 55's lesson)
    assert WORKLOAD["probe_sample"] <= WORKLOAD["reference_sample"] and WORKLOAD["probe_sample"] <= d["slots"] and WORKLOAD["probe_steps"] >= 128
    controls = {k: c for k, c in WORKLOAD["controls"].items() if k != "what"}
    assert set(controls) == {"bfloat16_state", "no_lambda", "memory_from_other", "window_as_full", "cross_own_kv"}
    assert not any(c["comes_out_correct"] for c in controls.values()) and all(len(c["why"]) > 60 for c in controls.values())
    program = WORKLOAD["tolerances"]["program_readings"]
    assert len(program["gap_ratio"]) >= 12 and len(set(program["seeds"])) >= 4  # twelve sound runs on four seeds and more, all correct
    assert max(program["gap_ratio"]) < WORKLOAD["gap_ratio_limit"] and max(program["worst_request_excess"]) < WORKLOAD["request_excess_limit"]
    for share, limit in WORKLOAD["state_error_limits"].items():
        assert max(program["state_error_at"][share]) < limit / 3, share  # room above what a sound run reads at the most
    # each control fails by one of the cell's limits: the four that change the model by the served tokens, the rounded
    # state, which reads inside the program's own band of served tokens, by the probe's median
    for name in ("no_lambda", "memory_from_other", "window_as_full", "cross_own_kv"):
        assert controls[name]["gap_ratio"] > 3 * WORKLOAD["gap_ratio_limit"], name
    rounded = controls["bfloat16_state"]
    assert rounded["gap_ratio"] <= WORKLOAD["gap_ratio_limit"] and rounded["state_error_at"]["0.5"] > 3 * WORKLOAD["state_error_limits"]["0.5"]
    spread = WORKLOAD["spread_of_six"]
    assert len(spread["served_tokens_per_s"]) == 2 and all(len(s) == 6 for s in spread["served_tokens_per_s"])
    assert max(spread["spread"]["served_tokens_per_s"]) < 0.05 and WORKLOAD["memory_peak_bytes"] > 0.25 * 16.9e9


def test_benchmark_json_holds_the_configuration_the_cell_and_six_metrics_that_list_it():
    config = next(c for c in BENCH["configs"] if c["name"] == NAME)
    assert config["file"] == f"benchmark/configs/{NAME}.json" and config["reduced"] == [] and config["source"] == CONFIG["source"]
    entry = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    assert entry == {"name": CELL, "config": NAME, "traffic": "think-gen", "chips": 1, "why": entry["why"]}
    assert all(len(e["why"]) <= 200 for e in (entry, config))
    assert sum(w["chips"] == 4 for w in BENCH["workloads"]) == 1
    by_name = {m["name"]: m for m in BENCH["per_layer"]}
    for name in MINE:
        m = by_name[name]
        assert (m["workloads"][0], m["moves"], m["unit"]) == (CELL, "served_tokens_per_s", "%"), name
        assert (ROOT / "benchmark/layer_metrics" / f"{name.split('.')[0]}.py").exists(), name
    served = next(m for m in BENCH["end_to_end"] if m["name"] == "served_tokens_per_s")
    assert CELL in served["workloads"] and served["bound"] == 0.1
    listed = {m["name"] for m in BENCH["per_layer"] if CELL in m.get("workloads", [])}
    assert listed >= set(MINE) | {
        "batch_occupancy.served", "cache_blocks_used_peak", "decode_step_ms.served", "prefill_time_share.served", "device_idle_share.served",
        "host_dispatch_share.served", "host_readback_share.served", "host_sched_share.served", "host_unspanned_share.served",
        "host_release_share.served", "pipelined_step_share.served", "window_cache_saving.served", "ssm_step_share.served",
        "ssm_prefill_ms_per_ktoken.served", "setup_compile_s", "setup_spanned_share"}
    assert not listed & {"expert_load_imbalance.served", "ssm_state_update_roofline.served", "hybrid_decode_roofline.served"}


def test_the_update_call_the_shared_read_and_a_decode_step_by_hand():
    assert phi4flash_model.state_values(MODEL) == 5120 * 16
    ops, nbytes = phi4flash_model.update_call(MODEL, 64)
    assert ops == 6 * 64 * 81920 and nbytes == 4 * (64 * (2 * 81920 + 3 * 5120 + 2 * 16) + 81920)
    least, bound = kernel_model.least_seconds(ops, nbytes, PEAKS)
    assert bound == "memory" and 5.5e-5 < least < 5.9e-5  # 21 MB of state each way at 819 GB/s
    w = phi4flash_model.weights(MODEL)
    # a layer's parameters by kind, its feed-forward and norms included: the issue's table
    assert [round((w[k] + w["ffn"]) / 1e6, 1) for k in ("mamba", "attention", "gmu", "cross")] == [119.9, 98.3, 104.9, 91.8]
    assert w["head"] == 2560 * 200064
    # ONE call over the shared K/V: 64 rows of 1,200 positions read 5,120 B a position (20 heads of 64, K and V)
    ops, nbytes = phi4flash_model.shared_kv_call(MODEL, 64, 64 * 1200)
    assert ops == 6 * 64 * 1200 * 2560 and nbytes == 64 * 1200 * 5120 + 64 * 3 * 2560 * 2
    assert phi4flash_model.window_positions([100, 600, 2000], MODEL) == 100 + 576 + 576
    ops, nbytes = phi4flash_model.decode_step(MODEL, 64, 64 * 1200, 64 * 576)
    weights = 2 * (9 * w["mamba"] + 9 * w["attention"] + 7 * w["gmu"] + 7 * w["cross"] + 32 * w["ffn"] + w["head"])
    assert 7.69e9 < weights < 7.72e9  # the whole model, the tied embedding once
    state, shared, window = 9 * 64 * (8 * 81920 + 2 * 3 * 5120 * 2), 5120 * (8 * 64 * 1200 + 64), 8 * 5120 * 64 * 577
    assert nbytes == pytest.approx(weights + 64 * 2560 * 2 + state + shared + window)
    assert 12.6e9 < nbytes < 12.9e9  # 7.7 GB of weights + 64 x (49 + 24 + 6.1 MB)
    least, bound = kernel_model.least_seconds(ops, nbytes, PEAKS)
    assert bound == "memory" and 0.0153 < least < 0.0158
    ops, nbytes = phi4flash_model.prefill(MODEL, 1024)
    assert kernel_model.least_seconds(ops, nbytes, PEAKS)[1] == "compute" and nbytes > 7.7e9
    # the cross-decoder on ONE row: a prefill's operations are those of 18 of 32 layers over every row
    every_layer = 2 * 1024 * (9 * w["mamba"] + 9 * w["attention"] + 7 * w["gmu"] + 7 * w["cross"] + 32 * w["ffn"])
    assert 0.5 * every_layer < ops < 0.75 * every_layer


def _ctx():
    snap = lambda k: {  # noqa: E731
        "cache": {"ssm": {"bytes_per_slot": 3225600, "slots_live": 64}, "shared_kv": {"reader_layers": list(range(19, 32, 2))},
                  "live_bytes": 3.0e9, "one_table_bytes": 4.0e9},
        "prefill": {"cross_layers": 14, "cross_rows_run_total": 100 * k, "cross_rows_skipped_total": 80000 * k},
        "prefill_attention": {"tokens_total": 20000 * k}}
    engine = lambda k: {"step_counts": {"decode": 2000 * k, "prefill": 30 * k},  # noqa: E731
                        "phase_time_s": {"decode": {"dispatch": 0.5 * k, "execute": 40.0 * k, "readback": 0.5 * k},
                                         "prefill": {"dispatch": 0.2 * k, "execute": 1.7 * k, "readback": 0.1 * k}}}
    return {
        "stats_open": snap(1), "stats_close": snap(2), "stats_samples": [snap(1), snap(2)], "engine_open": engine(1), "engine_close": engine(2),
        "window": (100.0, 150.0), "trace_abs": (147.0, 150.0), "model": dict(MODEL), "peaks": PEAKS, "slots": 64,
        "records": [{"prompt_len": 500, "token_times": [146.0] + [147.0 + 0.02 * i for i in range(120)]},
                    {"prompt_len": 900, "token_times": [148.0] + [148.1 + 0.02 * i for i in range(80)]}],
        "trace": {"programs": {"jit__decode_impl": 2.7, "jit__prefill_impl": 0.2}, "busy_s": 2.9, "kernel_s": {}, "kernel_calls": {}},
        "ssm_kernels": {"kernel_s": {"selective_state_update": 0.12}, "kernel_calls": {"selective_state_update": 1080}},
        "samba_scopes": {"scope_s": {"attention.full": 0.2, "attention.cross": 0.7, "attention.window": 0.5, "gmu": 0.3, "mlp": 0.8, "other": 0.2},
                         "kernel_s": {"attention.full": 0.05, "attention.cross": 0.35, "attention.window": 0.2}, "decode_s": 2.7, "steps": 120},
    }


def test_the_six_new_readers_on_a_hand_made_run():
    ctx = _ctx()
    ops, nbytes = phi4flash_model.update_call(MODEL, 64)
    got = layer_metrics.read("selective_update_roofline.served", ctx)
    assert got == pytest.approx(100 * kernel_model.least_seconds(1080 * ops, 1080 * nbytes, PEAKS)[0] / 0.12) and 0 < got < 100
    assert layer_metrics.read("ssm_step_share.served", ctx) == pytest.approx(100 * 0.12 / 2.9)  # (the accepted reader finds the new kernel's seconds)
    assert layer_metrics.read("ssm_prefill_ms_per_ktoken.served", ctx) == pytest.approx(2000.0 / 20)
    assert layer_metrics.read("window_cache_saving.served", ctx) == pytest.approx(25.0)
    assert layer_metrics.read("shared_kv_step_share.served", ctx) == pytest.approx(100 * 0.9 / 2.7)
    assert layer_metrics.read("gmu_step_share.served", ctx) == pytest.approx(100 * 0.3 / 2.7)
    assert layer_metrics.read("prefill_cross_rows_skipped_share.served", ctx) == pytest.approx(100 * 80000 / 80100)
    # traced: 1,080 update calls over 9 layers = 120 steps; 200 decode rows in the traced part = 1.67 rows a step
    contexts = [500 + i for i in range(1, 121)] + [900 + i for i in range(1, 81)]
    ops, nbytes = phi4flash_model.shared_kv_call(MODEL, 200, sum(contexts))
    got = layer_metrics.read("shared_kv_attention_roofline.served", ctx)
    assert got == pytest.approx(100 * kernel_model.least_seconds(8 * ops, 8 * nbytes, PEAKS)[0] / 0.4) and 0 < got < 100
    ops, nbytes = phi4flash_model.decode_step(MODEL, 200 / 120, sum(contexts) / 120, phi4flash_model.window_positions(contexts, MODEL) / 120)
    got = layer_metrics.read("samba_decode_roofline.served", ctx)
    assert got == pytest.approx(100 * kernel_model.least_seconds(120 * ops, 120 * nbytes, PEAKS)[0] / 2.7) and 0 < got < 100


@pytest.mark.parametrize("name", MINE)
def test_a_program_without_the_new_kinds_leaves_the_new_metrics_out(name):
    """On the parent there is no ``prefill`` section, no update kernel or
    scope of these names in the trace and no such sizes in the model's:
    nothing to read, and nothing raised."""
    assert layer_metrics.read(name, {}) is None
    parent = _ctx()
    for key in ("stats_open", "stats_close"):
        del parent[key]["prefill"]
    del parent["ssm_kernels"], parent["samba_scopes"], parent["model"]["mamba_layers"], parent["model"]["cross_layers"]
    assert layer_metrics.read(name, parent) is None
    silent = dict(_ctx(), ssm_kernels={"kernel_s": {"selective_state_update": 0.0}, "kernel_calls": {"selective_state_update": 0}},
                  samba_scopes={"scope_s": {}, "kernel_s": {}, "decode_s": 0.0, "steps": 0}, stats_close=_ctx()["stats_open"])
    assert layer_metrics.read(name, silent) is None


def test_the_scopes_of_a_compiled_program_s_text_are_read_innermost_first():
    from benchmark.drivers import serve_phi4flash

    text = """
  %fusion.7 = bf16[64,2560] fusion(%a), kind=kLoop, metadata={op_name="jit(_decode_impl)/layer19/attention.cross/dot_general"}
  %paged_append_attention.3 = bf16[64,64,128] custom-call(%q), metadata={op_name="jit(_decode_impl)/layer17/attention.full/pallas_call"}
  ROOT %fusion.9 = f32[64,5120] fusion(%b), metadata={op_name="jit(_decode_impl)/layer16/ssm/ssm.conv/mul"}
  %fusion.11 = bf16[64,2560] fusion(%c), metadata={op_name="jit(_decode_impl)/layer18/gmu/dot_general"}
  %copy.1 = bf16[2] copy(%d)
"""
    assert serve_phi4flash.scope_of(text) == {"fusion.7": "attention.cross", "paged_append_attention.3": "attention.full",
                                               "fusion.9": "ssm.conv", "fusion.11": "gmu"}


def test_the_whole_command_runs_the_cell_at_rehearsal_size():
    """``run.py --rehearse --trace 1``: tiny widths on the CPU backend, the
    whole control flow (weights from the seed, warm-up, HTTP, the closed
    loop, the judged requests scored by the reference, the probe), no
    result line."""
    out = subprocess.run(
        [sys.executable, str(ROOT / "benchmark/run.py"), "--workload", CELL, "--seed", "3400000123", "--seconds", "3",
         "--trace", "1", "--rehearse"], capture_output=True, text=True, timeout=600, cwd=str(ROOT),
    )
    assert out.returncode == 0, out.stdout[-3000:] + out.stderr[-3000:]
    assert "rehearsal done: correct=True" in out.stdout and "failed=0" in out.stdout
    found = out.stdout.split("readers that found something: ")[1].splitlines()[0]
    for name in ("ssm_prefill_ms_per_ktoken.served", "decode_step_ms.served", "batch_occupancy.served", "pipelined_step_share.served",
                 "window_cache_saving.served", "prefill_cross_rows_skipped_share.served", "cache_blocks_used_peak", "prefill_time_share.served",
                 "host_dispatch_share.served", "host_release_share.served"):
        assert name in found, found
    # (the five shares of a device trace are the chip's)
    assert "reference: gap_ratio 0.0000" in out.stdout  # float32 on the CPU is the reference's own arithmetic
    assert "engine: 8 L (3 mamba: inner 128, state 16, dt rank 4; 2 window of 16; the K/V layer 5, memory from 4; 1 gmu, 1 cross" in out.stdout
    assert "refused ['kv_handoff', 'prefix_reuse', 'speculation', 'tensor_parallel']" in out.stdout and "shared_kv {'producer_layers': [5]" in out.stdout
