#!/usr/bin/env python3
"""Phi-4-mini-flash-reasoning's checks that are no benchmark run (as ``nemotron_check.py``).

    python benchmark/tools/phi4flash_check.py compile --slots 64 96 128     (sandbox, no chip)
    chiprun -- python benchmark/tools/phi4flash_check.py control --seed N
    chiprun -- python benchmark/tools/phi4flash_check.py sweep --slots 64 96 128 --seeds A B

``compile``: deviceless v5e compiles of the cell's decode program by slots
and of its prefills at the configuration's real widths (same rule as
``compile_check.py``: a setting fits if every program leaves 1 GiB of the
chip's 15.75 GiB to spare), with the Mosaic calls each holds. Nothing runs.

``control``: one RUN of the cell as ``benchmark/run.py`` makes it (the same
``main``, the same driver), whose judged sample carries the five controls'
choices beside the served tokens (``reference/phi4flash.py::CONTROLS``: the
state stored in bfloat16, ``lambda a2`` left out, the GMU's memory from
another Mamba layer, the window read as full attention, a cross layer
reading K/V of its own projection); every arm goes through the driver's own
``verdict``. Exit 0 only if the served tokens come out correct and no
control does.

``sweep``: plain runs of the cell at each slot count (clients = 2 x slots)
on the SAME seeds, in alternating order, each in a process of its own (a
chip belongs to one at a time): ``chiprun_out/phi4flash_sweep.json``.
"""
from __future__ import annotations

import argparse
import json
import os
import pathlib
import subprocess
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

CELL = "phi-4-mini-flash-reasoning.think-gen"


def compile_(slots_list) -> None:
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from benchmark import spec
    from benchmark.reference import phi4flash
    from benchmark.tools import compile_check
    from flexflow_tpu.generation import GenerationEngine, init_decoder_params

    compile_check.take_tpu_paths()
    one = SingleDeviceSharding(compile_check.topology().devices[0])
    cell = spec.load_cell(CELL)
    d = cell.workload["deployment"]
    cfg = phi4flash.engine_config(cell.config, int(d["max_seq_len"]))
    shapes = jax.eval_shape(lambda k: init_decoder_params(k, cfg), jax.random.key(0))
    sds = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=one)  # noqa: E731
    on_chip = lambda tree: jax.tree.map(lambda a: sds(a.shape, a.dtype), tree)  # noqa: E731
    params = on_chip(shapes)
    weights = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(shapes))
    key = jax.eval_shape(lambda: jax.random.key(0))
    i32, f32 = jnp.int32, jnp.float32
    for slots in slots_list:
        engine = GenerationEngine(
            shapes, cfg, max_batch_slots=slots, block_size=int(d["block_size"]),
            prompt_buckets=list(d["prompt_buckets"]), max_seq_len=int(d["max_seq_len"]),
        )
        engine.backend = "tpu"
        engine.attention_kernels = engine.paged_lowerings()
        b, mb, v = slots, engine.max_blocks_per_seq, cfg.vocab_size
        ck = sds(engine.cache.k.shape, engine.cache.k.dtype)
        state, counts = on_chip(engine.cache.state), on_chip(engine.expert_counts)
        window_kv = {name: state[name] for name in ("wk", "wv")}
        wtables = {"tables": sds((b, engine.window_columns), i32), "first": sds((b,), i32)}
        wtable = {"tables": sds((engine.window_columns,), i32), "first": sds((), i32)}
        held = 2 * ck.size * ck.dtype.itemsize + sum(a.size * a.dtype.itemsize for a in window_kv.values())
        t0 = time.time()
        dec = jax.jit(engine._decode_impl, donate_argnums=(3, 4, 13)).lower(
            params, sds((b,), i32), sds((b,), i32), ck, ck, sds((b, mb), i32), sds((b,), i32),
            sds((b,), f32), sds((b,), i32), sds((b,), f32), sds((b,), jnp.uint32), sds((b,), i32),
            sds((b, v), f32), state, counts, wtables,
        ).compile()
        text = dec.as_text()
        ok = compile_check.report(
            f"phi4flash slots={slots} decode (state {engine.slot_state.total_bytes / compile_check.GIB:.2f} GiB, full + window K/V "
            f"{held / compile_check.GIB:.2f} GiB, weights {weights / compile_check.GIB:.2f} GiB; Mosaic calls "
            f"{text.count('tpu_custom_call')}, of them selective_state_update {text.count('selective_state_update')}; kernels "
            f"{engine.kernel_stats()}; {time.time() - t0:.0f}s)", dec)
        for bucket in d["prompt_buckets"]:
            t0 = time.time()
            pre = jax.jit(engine._prefill_impl).lower(
                params, sds((1, bucket), i32), sds((), i32), ck, ck, sds((mb,), i32), sds((), f32),
                sds((), i32), jax.ShapeDtypeStruct(key.shape, key.dtype, sharding=one), sds((v,), f32),
                {}, None, counts, wtable,
            ).compile()
            # this configuration's prefill returns the prompt's rows and NO pool (engine._rows_install): the hand-over
            # below writes them, donating, so no second copy of the pools stands beside the first
            handed = {"rows_k": sds((len(cfg.kv_layers), bucket) + tuple(ck.shape[3:]), ck.dtype),
                      "rows_v": sds((len(cfg.kv_layers), bucket) + tuple(ck.shape[3:]), ck.dtype),
                      **{name: sds(a.shape[:1] + a.shape[2:], a.dtype) for name, a in state.items() if name in engine.slot_state.names}}
            inst = jax.jit(engine._install_rows_impl, donate_argnums=(0, 1, 2)).lower(
                ck, ck, state, sds((), i32), handed, sds((), i32), sds((mb,), i32), wtable).compile()
            ok &= compile_check.report(f"phi4flash slots={slots} install_rows[{bucket}]", inst)
            ok &= compile_check.report(
                f"phi4flash slots={slots} prefill[{bucket}] ({engine.prefill_lowering(bucket)}; Mosaic calls "
                f"{pre.as_text().count('tpu_custom_call')}; {time.time() - t0:.0f}s)", pre)
        print(f"phi4flash slots={slots}: {'FITS' if ok else 'does not fit'} (the slots' state lives beside every program: "
              f"decode holds it as an argument, a prefill's peak is its own + {engine.slot_state.total_bytes / compile_check.GIB:.2f} GiB)",
              flush=True)
        del engine


def control(seed: int, seconds: float, rehearsal: bool) -> int:
    from benchmark import run as harness
    from benchmark.drivers import serve_phi4flash as driver
    from benchmark.reference import phi4flash

    driver.CONTROL_ARMS = phi4flash.CONTROLS
    kept, run = {}, driver.run

    def run_and_keep(cell, rt, peaks):
        kept.update(run(cell, rt, peaks), cell=cell)
        return kept

    driver.run = run_and_keep
    t0 = time.monotonic()
    rc = harness.main(["--workload", CELL, "--seed", str(seed), "--seconds", str(seconds), "--trace", "0"]
                      + (["--rehearse"] if rehearsal else []))
    sample, w = kept["reference"], kept["cell"].workload
    row = {"seed": seed, "seconds": round(time.monotonic() - t0, 1), "run_rc": rc, "run_correct": bool(kept["correct"]),
           "why_incorrect": kept["why_incorrect"],
           "limits": {**{k: float(w[k]) for k in ("gap_ratio_limit", "request_excess_limit")},
                      "state_error_limits": w["state_error_limits"]},
           "program": sample["read"], "comes_out_correct": {"program": bool(kept["correct"])}}
    for name in driver.CONTROL_ARMS:
        row[name], failures = driver.verdict(sample["judged"][name], sample["judged"]["stated"], sample["valid"], w, sample["probed"][name])
        row[name]["fails"] = failures
        row["comes_out_correct"][name] = not failures
    out_dir = ROOT / "chiprun_out" / "control"
    out_dir.mkdir(parents=True, exist_ok=True)
    (out_dir / f"phi4flash.{seed}.json").write_text(json.dumps(row))
    arms = ("program",) + tuple(driver.CONTROL_ARMS)
    print("control row: " + json.dumps(row), flush=True)
    for arm in arms:
        r = row[arm]
        print(f"{arm:18s} gap_ratio {r['gap_ratio']:.4g}  worst_request_excess {r['worst_request_excess']:.4g}  state_error at shares "
              f"{ {k: float(f'{x:.3g}') for k, x in r['state_error_at'].items()} }  ({r['tokens']} tokens of {r['requests']} requests)  "
              f"-> {'correct' if row['comes_out_correct'][arm] else 'NOT correct: ' + '; '.join(r.get('fails') or row['why_incorrect'])}",
              flush=True)
    sound = row["comes_out_correct"].pop("program")
    return 0 if sound and not any(row["comes_out_correct"].values()) else 1


def sweep(slots_list, seeds, seconds: float, out_name: str = "phi4flash_sweep.json") -> None:
    """Plain runs of the cell, every slot count on every seed, the order of
    the counts turned round from seed to seed; the cell's file rewritten in
    place for a run and put back after."""
    path = ROOT / "benchmark" / "workloads" / f"{CELL}.json"
    original = path.read_text()
    out = {str(s): [] for s in slots_list}
    try:
        for i, seed in enumerate(seeds):
            for slots in (slots_list if i % 2 == 0 else slots_list[::-1]):
                w = json.loads(original)
                w["deployment"]["slots"] = slots
                w["traffic_params"]["clients"] = 2 * slots
                if 2 * slots > 256:  # the scheduler's default queue bound: the loop's first round would be refused
                    w["deployment"]["max_queue"] = 2 * slots
                path.write_text(json.dumps(w, indent=2))
                run = subprocess.run(
                    [sys.executable, str(ROOT / "benchmark" / "run.py"), "--workload", CELL, "--seed", str(seed),
                     "--seconds", str(seconds), "--trace", "0"], capture_output=True, text=True,
                )
                lines = run.stdout.strip().splitlines()
                print("\n".join(line for line in lines if "inside:" in line or "window " in line or "NOT CORRECT" in line
                                or "reference:" in line or "engine:" in line), flush=True)
                row = json.loads(lines[-1]) if run.returncode == 0 and lines else {"failed": run.returncode, "stderr": run.stderr[-3000:]}
                row["seed"] = seed
                out[str(slots)].append(row)
                print(f"slots {slots} seed {seed}: {json.dumps(row)}", flush=True)
                (ROOT / "chiprun_out").mkdir(exist_ok=True)
                (ROOT / "chiprun_out" / out_name).write_text(json.dumps(out, indent=1))
    finally:
        path.write_text(original)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    sub = ap.add_subparsers(dest="what", required=True)
    sub.add_parser("compile").add_argument("--slots", type=int, nargs="+", default=[64, 96, 128])
    c = sub.add_parser("control")
    c.add_argument("--seed", type=int, required=True)
    c.add_argument("--seconds", type=float, default=50.0)
    c.add_argument("--rehearse", action="store_true", help="sandbox only: tiny widths on the CPU")
    s = sub.add_parser("sweep")
    s.add_argument("--slots", type=int, nargs="+", default=[64, 96, 128])
    s.add_argument("--seeds", type=int, nargs="+", required=True)
    s.add_argument("--seconds", type=float, default=50.0)
    s.add_argument("--out", default="phi4flash_sweep.json", help="the file under chiprun_out/ the rows go to")
    args = ap.parse_args()
    if args.what == "compile":
        compile_(args.slots)
        return 0
    if args.what == "sweep":
        sweep(args.slots, args.seeds, args.seconds, args.out)
        return 0
    return control(args.seed, args.seconds, args.rehearse)


if __name__ == "__main__":
    sys.exit(main())
