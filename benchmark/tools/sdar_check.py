#!/usr/bin/env python3
"""SDAR-30B-A3B-Chat's checks that are no benchmark run (as ``mellum2_check.py``).

    python benchmark/tools/sdar_check.py compile --slots 32 48 64     (sandbox, no chip)
    chiprun -- python benchmark/tools/sdar_check.py control --seeds 1 2 3
    chiprun -- python benchmark/tools/sdar_check.py sweep --slots 32 48 64 --seed 5

``compile``: deviceless v5e compiles of the cell's block step by slots
and of its prefills at the configuration's real widths (same rule as
``compile_check.py``: a setting fits if every program leaves 1 GiB of
the chip's 15.75 GiB to spare). Nothing runs. The prefill's line also
shows what skipping the head leaves of its temporaries.

``control``: the readings the cell's limits are set from, at the cell's
own size on the chip: per seed the weights, the engine and the schedule
as a run makes them; ``reference_sample`` requests of the schedule served
by the program through its own scheduler (no HTTP); every denoising
state along their own trajectories judged by the float32 reference as
the driver judges served ones; then what the equations fix after the
same states in the arithmetic the configuration states (the yardstick)
and in the controls (``bfloat16_sums``: bfloat16 throughout, one step of
precision down; ``int8`` weights; ``causal_mask``: this model's own
mechanism done wrong). Read: ``gap_ratio`` (``reference/sdar.py::
pooled_gap_ratio``: the tokens' and the rows' distances summed; its two
parts beside it) of the program (SOUND) and of the controls; every judged
row's distances go to ``chiprun_out/control/<cell>.<seed>.npz``. One JSON line per seed; the rows go
to ``chiprun_out/control/``.

``sweep``: one plain run of the cell a slot count (clients = 2 x slots),
each in a process of its own (a chip belongs to one at a time): the
result lines go to ``chiprun_out/sdar_sweep.json``.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import pathlib
import subprocess
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

CELL = "sdar-30b-a3b-chat.block-gen"
ARITHMETICS = ("bfloat16", "bfloat16_sums", "int8", "causal_mask")  # the stated one (the yardstick), and the controls


def compile_(slots_list) -> None:
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from benchmark import spec
    from benchmark.reference import sdar
    from benchmark.tools import compile_check
    from flexflow_tpu.generation import GenerationEngine, init_decoder_params

    compile_check.take_tpu_paths()
    one = SingleDeviceSharding(compile_check.topology().devices[0])
    cell = spec.load_cell(CELL)
    d = cell.workload["deployment"]
    cfg = sdar.engine_config(cell.config, int(d["max_seq_len"]))
    rule = sdar.diffusion_rule(cell.config, denoising_steps=int(d["denoising_steps"]), remasking=d["remasking"])
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
            prompt_buckets=list(d["prompt_buckets"]), max_seq_len=int(d["max_seq_len"]), diffusion=rule,
        )
        engine.backend = "tpu"
        b, w, mb, v = slots, rule.block_length, engine.max_blocks_per_seq, cfg.vocab_size
        ck = sds(engine.cache.k.shape, engine.cache.k.dtype)
        counts = on_chip(engine.expert_counts)
        held = 2 * ck.size * ck.dtype.itemsize
        t0 = time.time()
        step = jax.jit(engine._block_impl, donate_argnums=(5, 6)).lower(
            params, sds((b, w), i32), sds((b, w), i32), sds((b,), i32), sds((b,), i32), ck, ck, sds((b, mb), i32),
            sds((b,), i32), sds((b,), f32), sds((b,), i32), sds((b,), jnp.uint32), sds((b,), i32), sds((b,), f32),
            sds((b,), f32), counts,
        ).compile()
        text = step.as_text()
        ok = compile_check.report(
            f"sdar slots={slots} block_step (K/V {held / compile_check.GIB:.2f} GiB, weights {weights / compile_check.GIB:.2f} "
            f"GiB; Mosaic calls {text.count('tpu_custom_call')}; kernels {engine.paged_lowerings()}; experts "
            f"{engine.expert_lowerings()}; {time.time() - t0:.0f}s)", step)
        for bucket in d["prompt_buckets"]:
            t0 = time.time()
            pre = jax.jit(engine._prefill_impl).lower(
                params, sds((1, bucket), i32), sds((), i32), ck, ck, sds((mb,), i32), sds((), f32),
                sds((), i32), jax.ShapeDtypeStruct(key.shape, key.dtype, sharding=one), sds((v,), f32),
                {}, None, counts,
            ).compile()
            # prefill donates nothing: the old K/V lives beside the new; and this one runs no head
            ok = compile_check.report(
                f"sdar slots={slots} prefill[{bucket}] (no head: [{bucket}, {v}] float32 logits would be "
                f"{bucket * v * 4 / compile_check.GIB:.2f} GiB; {engine.prefill_lowering(bucket)}; {time.time() - t0:.0f}s)", pre) and ok
        print(f"sdar slots={slots}: {'FITS' if ok else 'does not fit'}", flush=True)
        del engine


def readings(cell, seed: int):
    """One seed's row: the program's reading and each arithmetic's after
    the same states."""
    import numpy as np

    from benchmark import traffic
    from benchmark.drivers import serve_sdar
    from benchmark.reference import sdar
    from flexflow_tpu.generation.engine import SamplingParams
    from flexflow_tpu.generation.scheduler import ContinuousBatchingScheduler

    w = cell.workload
    params, cfg, engine = serve_sdar.build_engine(cell, seed)
    rule = engine.diffusion
    reqs = traffic.schedule(cell.traffic["generator"], seed, float(w["lead_in_s"]) + 50.0,
                            cell.traffic["params"], {"vocab_size": cfg.vocab_size})["requests"]
    rs = np.random.RandomState(seed + 1)
    engine.generate([[int(t) for t in rs.randint(0, cfg.vocab_size, size=b)] for b in engine.buckets[:-1]],
                    SamplingParams(max_new_tokens=2))
    engine.reset()
    picked = [reqs[i] for i in np.random.RandomState(seed + 2).choice(
        len(reqs), size=min(int(w["reference_sample"]), len(reqs)), replace=False)]
    own = ContinuousBatchingScheduler(engine)
    handles = [own.submit(list(r["prompt"]), SamplingParams(max_new_tokens=r["max_new_tokens"])) for r in picked]
    while any(not h.done() for h in handles) and own.step():
        pass
    served = [(h.result(timeout=0), list(h._request.fixed_at)) for h in handles]
    engine.cache.k = engine.cache.v = None  # their room is the reference's
    del own
    n_fix, threshold = rule.rows_per_forward(), rule.threshold_of()
    pad_to, states = serve_sdar.judged_shape(cell, rule.block_length, rule.denoising_steps)
    names = ("program",) + ARITHMETICS
    pooled = {a: {"gap": [], "row_gap": []} for a in names}
    for r, (tokens, fixed_at) in zip(picked, served):
        lay = sdar.trajectory(r["prompt"], tokens, fixed_at, rule.block_length, pad_to, states)
        arms = {"program": {"chosen": lay["chosen"], "picked": lay["picked"]}}
        arms.update({a: sdar.choices(params, cell.config, lay, n_fix, threshold, a) for a in ARITHMETICS})
        for a, parts in sdar.judge(params, cell.config, lay, arms, n_fix, threshold).items():
            for k, vals in parts.items():
                pooled[a][k].append(vals)
    pooled = {a: {k: np.concatenate(v) for k, v in parts.items()} for a, parts in pooled.items()}
    # every judged row's two distances, by arm: what another statistic of them would have read
    np.savez_compressed(ROOT / "chiprun_out" / "control" / f"{cell.name}.{seed}.npz",
                        **{f"{a}.{k}": v.astype(np.float32) for a, parts in pooled.items() for k, v in parts.items()})
    row = {"seed": seed, "limit": float(w["gap_ratio_limit"])}
    for a in names:
        row[a] = dict(sdar.reading(pooled[a]), gap_ratio=sdar.pooled_gap_ratio(pooled[a], pooled["bfloat16"]),
                      token_gap_ratio=sdar.gap_ratio(pooled[a], pooled["bfloat16"]),
                      row_gap_ratio=sdar.row_gap_ratio(pooled[a], pooled["bfloat16"]))
    del engine
    return row


def control(seeds, rehearsal: bool) -> None:
    import jax

    from benchmark import spec

    if not rehearsal:
        from flexflow_tpu.device import enable_compile_cache, require_tpu

        require_tpu()
        enable_compile_cache()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    cell = spec.load_cell(CELL, rehearsal=rehearsal)
    out_dir = ROOT / "chiprun_out" / "control"
    out_dir.mkdir(parents=True, exist_ok=True)
    rows = []
    for seed in seeds:
        gc.collect()  # the last seed's engine (cycles through its jits) holds 10 GB of the chip
        t0 = time.monotonic()
        row = readings(cell, seed)
        row.update(device=jax.devices()[0].device_kind, seconds=round(time.monotonic() - t0, 1))
        rows.append(row)
        print(json.dumps(row), flush=True)
        (out_dir / f"{cell.name}.json").write_text(json.dumps(rows, indent=1))
    for arm in ("program",) + ARITHMETICS[1:]:
        for stat in ("gap_ratio", "token_gap_ratio", "row_gap_ratio"):
            vals = [r[arm][stat] for r in rows]
            print(f"{arm:13s} {stat} over {len(rows)} seeds: {min(vals):.4g} .. {max(vals):.4g} (the limit on gap_ratio: {rows[0]['limit']:g})")


def sweep(slots_list, seed: int, seconds: float) -> None:
    """One plain run of the cell a slot count, each in its own process,
    the cell's file rewritten in place for the run and put back after."""
    path = ROOT / "benchmark" / "workloads" / f"{CELL}.json"
    original = path.read_text()
    out = {}
    try:
        for i, slots in enumerate(slots_list):
            w = json.loads(original)
            w["deployment"]["slots"] = slots
            w["traffic_params"]["clients"] = 2 * slots
            path.write_text(json.dumps(w, indent=2))
            run = subprocess.run(
                [sys.executable, str(ROOT / "benchmark" / "run.py"), "--workload", CELL, "--seed", str(seed + 7919 * i),
                 "--seconds", str(seconds), "--trace", "0"], capture_output=True, text=True,
            )
            lines = run.stdout.strip().splitlines()
            print("\n".join(line for line in lines if "inside:" in line or "window " in line or "NOT CORRECT" in line), flush=True)
            out[str(slots)] = json.loads(lines[-1]) if run.returncode == 0 and lines else {"failed": run.returncode, "stderr": run.stderr[-2000:]}
            print(f"slots {slots}: {json.dumps(out[str(slots)])}", flush=True)
    finally:
        path.write_text(original)
    (ROOT / "chiprun_out").mkdir(exist_ok=True)
    (ROOT / "chiprun_out" / "sdar_sweep.json").write_text(json.dumps(out, indent=1))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    sub = ap.add_subparsers(dest="what", required=True)
    sub.add_parser("compile").add_argument("--slots", type=int, nargs="+", default=[32, 48, 64])
    c = sub.add_parser("control")
    c.add_argument("--seeds", type=int, nargs="+", required=True)
    c.add_argument("--rehearse", action="store_true", help="sandbox only: tiny widths on the CPU")
    s = sub.add_parser("sweep")
    s.add_argument("--slots", type=int, nargs="+", default=[32, 48, 64])
    s.add_argument("--seed", type=int, required=True)
    s.add_argument("--seconds", type=float, default=50.0)
    args = ap.parse_args()
    if args.what == "compile":
        compile_(args.slots)
    elif args.what == "sweep":
        sweep(args.slots, args.seed, args.seconds)
    else:
        if args.rehearse:
            os.environ["JAX_PLATFORMS"] = "cpu"
        control(args.seeds, args.rehearse)
    return 0


if __name__ == "__main__":
    sys.exit(main())
