#!/usr/bin/env python3
"""JoyAI-LLM-Flash's two checks that are no benchmark run (as ``mellum2_check.py``).

    python benchmark/tools/joyai_check.py compile --slots 32 48 64 96     (sandbox, no chip)
    chiprun -- python benchmark/tools/joyai_check.py control --seeds 1 2 3

``compile``: deviceless v5e compiles of the cell's decode program and
its largest prefill at the configuration's real widths, as
``compile_check.py`` does for GPT-2 (same rule: a setting fits if every
program leaves 1 GiB of the chip's 15.75 GiB to spare), and the layout
the latent cache's array is given there. Nothing runs.

``control``: the readings the cell's limits are set from, at the cell's
own size on the chip: per seed the weights, the engine and the schedule
as a run makes them; ``reference_sample`` requests of the schedule
served by the program through its own scheduler (no HTTP); their tokens
judged by the float32 reference as the driver judges served ones; then
the choices, after the same prefixes, of the equations in the arithmetic
the configuration states (the yardstick) and of the four controls (int8
weights and bfloat16 running sums: a step coarser; the absorbed scores
scaled by 1 / sqrt(576) and the shared expert left out: this model's own
mechanisms done wrong), judged the same way. Read: ``gap_ratio`` and
``worst_request_excess`` of the program (SOUND) and of the controls.
One JSON line per seed; the rows go to ``chiprun_out/control/``.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

CELL = "joyai-llm-flash.long-gen"
ARITHMETICS = ("bfloat16", "int8", "bfloat16_sums", "absorbed_scale", "no_shared_expert")  # the stated one, and the controls


def compile_(slots_list) -> None:
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from benchmark import spec
    from benchmark.reference import joyai
    from benchmark.tools import compile_check
    from flexflow_tpu.generation import GenerationEngine, init_decoder_params

    compile_check.take_tpu_paths()
    one = SingleDeviceSharding(compile_check.topology().devices[0])
    cell = spec.load_cell(CELL)
    d = cell.workload["deployment"]
    cfg = joyai.engine_config(cell.config, int(d["max_seq_len"]))
    shapes = jax.eval_shape(lambda k: init_decoder_params(k, cfg), jax.random.key(0))
    sds = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=one)  # noqa: E731
    on_chip = lambda tree: jax.tree.map(lambda a: sds(a.shape, a.dtype), tree)  # noqa: E731
    params = on_chip(shapes)
    weights = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(shapes))
    key = jax.eval_shape(lambda: jax.random.key(0))
    i32, f32 = jnp.int32, jnp.float32
    bucket = max(d["prompt_buckets"])
    for slots in slots_list:
        engine = GenerationEngine(
            shapes, cfg, max_batch_slots=slots, block_size=int(d["block_size"]),
            prompt_buckets=list(d["prompt_buckets"]), max_seq_len=int(d["max_seq_len"]),
        )
        engine.backend = "tpu"
        b, mb, v = slots, engine.max_blocks_per_seq, cfg.vocab_size
        ck, cv = (sds(a.shape, a.dtype) for a in (engine.cache.k, engine.cache.v))
        counts = on_chip(engine.expert_counts)
        held = ck.size * ck.dtype.itemsize
        t0 = time.time()
        dec = jax.jit(engine._decode_impl, donate_argnums=(3, 4, 13)).lower(
            params, sds((b,), i32), sds((b,), i32), ck, cv, sds((b, mb), i32), sds((b,), i32),
            sds((b,), f32), sds((b,), i32), sds((b,), f32), sds((b,), jnp.uint32), sds((b,), i32),
            sds((b, v), f32), {}, counts,
        ).compile()
        text = dec.as_text()
        ok_d = compile_check.report(
            f"joyai slots={slots} decode (latent cache {ck.shape} {held / compile_check.GIB:.2f} GiB, laid out "
            f"{dec.input_formats[0][3].layout}; weights {weights / compile_check.GIB:.2f} GiB; Mosaic calls "
            f"{text.count('tpu_custom_call')}; {time.time() - t0:.0f}s)", dec)
        t0 = time.time()
        pre = jax.jit(engine._prefill_impl).lower(
            params, sds((1, bucket), i32), sds((), i32), ck, cv, sds((mb,), i32), sds((), f32),
            sds((), i32), jax.ShapeDtypeStruct(key.shape, key.dtype, sharding=one), sds((v,), f32),
            {}, None, counts,
        ).compile()
        # prefill donates nothing: the old cache lives beside the new
        ok_p = compile_check.report(f"joyai slots={slots} prefill[{bucket}] ({time.time() - t0:.0f}s)", pre)
        print(f"joyai slots={slots}: {'FITS' if ok_d and ok_p else 'does not fit'}", flush=True)
        del engine


def readings(cell, seed: int):
    """One seed's row: ``reading`` of the program's tokens and of each
    arithmetic's choices after the same prefixes."""
    import numpy as np

    from benchmark import traffic
    from benchmark.drivers import serve_joyai
    from benchmark.reference import joyai
    from flexflow_tpu.generation.engine import SamplingParams
    from flexflow_tpu.generation.scheduler import ContinuousBatchingScheduler

    w = cell.workload
    params, cfg, engine = serve_joyai.build_engine(cell, seed)
    reqs = traffic.schedule(cell.traffic["generator"], seed, float(w["lead_in_s"]) + 50.0,
                            cell.traffic["params"], {"vocab_size": cfg.vocab_size})["requests"]
    # (not serve.warm: here one prompt a bucket compiles what this scheduler runs)
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
    lay = joyai.layout([r["prompt"] for r in picked], [h.result(timeout=0) for h in handles],
                       serve_joyai.pad_to(cell), int(cell.traffic["params"]["output"]["max"]))
    engine.cache.k = engine.cache.v = None  # their room is the reference's
    engine.cache.state = {}
    del own, engine
    arms = {"program": lay["chosen"]}
    arms.update({a: joyai.choices(params, cell.config, lay["tokens"], lay["at"], a) for a in ARITHMETICS})
    judged = joyai.judge(params, cell.config, lay["tokens"], lay["at"], arms, lay["valid"])
    row = {"seed": seed, "limit": float(w["gap_ratio_limit"]), "request_limit": float(w["request_excess_limit"])}
    for arm in arms:
        row[arm] = dict(joyai.reading(judged[arm]), mean_gap=float(judged[arm]["gap"].mean()),
                        median_margin=float(np.median(judged[arm]["margin"])),
                        gap_ratio=joyai.gap_ratio(judged[arm], judged["bfloat16"]),
                        worst_request_ratio=joyai.worst_request_ratio(judged[arm], judged["bfloat16"], lay["valid"]),
                        worst_request_excess=joyai.worst_request_excess(judged[arm], judged["bfloat16"], lay["valid"])["excess"])
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
        gc.collect()  # the last seed's engine (cycles through its jits) holds most of the chip
        t0 = time.monotonic()
        row = readings(cell, seed)
        row.update(device=jax.devices()[0].device_kind, seconds=round(time.monotonic() - t0, 1))
        rows.append(row)
        print(json.dumps(row), flush=True)
        (out_dir / f"{cell.name}.json").write_text(json.dumps(rows, indent=1))
    for arm in ("program",) + ARITHMETICS[1:]:
        for stat, limit in (("gap_ratio", "limit"), ("worst_request_excess", "request_limit"), ("worst_request_ratio", "request_limit")):
            vals = [r[arm][stat] for r in rows]
            print(f"{arm:16s} {stat} over {len(rows)} seeds: {min(vals):.4g} .. {max(vals):.4g} (limit {rows[0][limit]:g})")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    sub = ap.add_subparsers(dest="what", required=True)
    sub.add_parser("compile").add_argument("--slots", type=int, nargs="+", default=[32, 48, 64, 96])
    c = sub.add_parser("control")
    c.add_argument("--seeds", type=int, nargs="+", required=True)
    c.add_argument("--rehearse", action="store_true", help="sandbox only: tiny widths on the CPU")
    args = ap.parse_args()
    if args.what == "compile":
        compile_(args.slots)
    else:
        if args.rehearse:
            os.environ["JAX_PLATFORMS"] = "cpu"
        control(args.seeds, args.rehearse)
    return 0


if __name__ == "__main__":
    sys.exit(main())
