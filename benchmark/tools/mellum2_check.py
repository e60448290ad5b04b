#!/usr/bin/env python3
"""Mellum2-12B-A2.5B's two checks that are no benchmark run (as ``lfm2_check.py``).

    python benchmark/tools/mellum2_check.py compile --slots 16 32 48 64     (sandbox, no chip)
    chiprun -- python benchmark/tools/mellum2_check.py control --seeds 1 2 3

``compile``: deviceless v5e compiles of the cell's decode program and
its largest prefill at the configuration's real widths, as
``compile_check.py`` does for GPT-2 (same rule: a setting fits if every
program leaves 1 GiB of the chip's 15.75 GiB to spare). Nothing runs.

``control``: the two readings the cell's ``near_tie_gap_limit`` is set
from, at the cell's own size on the chip, as ``control_check.py`` takes
them for the GPT-2 cells: per seed the weights, the engine and the
schedule as a run makes them; ``reference_sample`` requests of the
schedule served by the program through its own scheduler (no HTTP);
their tokens judged by the float32 reference as the driver judges
served ones; then the choices, after the same prefixes, of the
equations in the arithmetic the configuration states (the yardstick)
and of the four controls (int8 weights and bfloat16 running sums: a
step coarser; the window ignored and plain rotary in the full layers:
this model's own mechanisms done wrong), judged the same way. Read:
``gap_ratio`` and ``worst_request_excess`` of the program (SOUND) and of
the controls (``worst_request_ratio`` beside them, not judged).
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

CELL = "mellum2-12b.code-gen"
ARITHMETICS = ("bfloat16", "int8", "bfloat16_sums", "window_ignored", "plain_rotary")  # the stated one (the yardstick), and the controls


def compile_(slots_list) -> None:
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from benchmark import spec
    from benchmark.reference import mellum2
    from benchmark.tools import compile_check
    from flexflow_tpu.generation import GenerationEngine, init_decoder_params

    compile_check.take_tpu_paths()
    one = SingleDeviceSharding(compile_check.topology().devices[0])
    cell = spec.load_cell(CELL)
    d = cell.workload["deployment"]
    cfg = mellum2.engine_config(cell.config, int(d["max_seq_len"]))
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
        ck = sds(engine.cache.k.shape, engine.cache.k.dtype)
        state, counts = on_chip(engine.cache.state), on_chip(engine.expert_counts)
        held = 2 * ck.size * ck.dtype.itemsize + sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(state))
        t0 = time.time()
        dec = jax.jit(engine._decode_impl, donate_argnums=(3, 4, 13)).lower(
            params, sds((b,), i32), sds((b,), i32), ck, ck, sds((b, mb), i32), sds((b,), i32),
            sds((b,), f32), sds((b,), i32), sds((b,), f32), sds((b,), jnp.uint32), sds((b,), i32),
            sds((b, v), f32), state, counts,
            {"tables": sds((b, engine.window_config.blocks_per_sequence(engine.max_seq_len)), i32), "first": sds((b,), i32)},
        ).compile()
        text = dec.as_text()
        ok_d = compile_check.report(
            f"mellum2 slots={slots} decode (K/V of both pools {held / compile_check.GIB:.2f} GiB, weights "
            f"{weights / compile_check.GIB:.2f} GiB; Mosaic calls {text.count('tpu_custom_call')}; {time.time() - t0:.0f}s)", dec)
        t0 = time.time()
        pre = jax.jit(engine._prefill_impl).lower(
            params, sds((1, bucket), i32), sds((), i32), ck, ck, sds((mb,), i32), sds((), f32),
            sds((), i32), jax.ShapeDtypeStruct(key.shape, key.dtype, sharding=one), sds((v,), f32),
            state, None, counts,
            {"tables": sds((engine.window_config.blocks_per_sequence(engine.max_seq_len),), i32), "first": sds((), i32)},
        ).compile()
        # prefill donates nothing: the old K/V and state live beside the new
        ok_p = compile_check.report(f"mellum2 slots={slots} prefill[{bucket}] ({time.time() - t0:.0f}s)", pre)
        print(f"mellum2 slots={slots}: {'FITS' if ok_d and ok_p else 'does not fit'}", flush=True)
        del engine


def readings(cell, seed: int):
    """One seed's row: ``reading`` of the program's tokens and of the
    control's choices after the same prefixes."""
    import numpy as np

    from benchmark import traffic
    from benchmark.drivers import serve_mellum2
    from benchmark.reference import mellum2 as lfm2
    from flexflow_tpu.generation.engine import SamplingParams
    from flexflow_tpu.generation.scheduler import ContinuousBatchingScheduler

    w = cell.workload
    params, cfg, engine = serve_mellum2.build_engine(cell, seed)
    reqs = traffic.schedule(cell.traffic["generator"], seed, float(w["lead_in_s"]) + 50.0,
                            cell.traffic["params"], {"vocab_size": cfg.vocab_size})["requests"]
    # (not serve.warm: it serves prompts until the block pool overflows, 90 s a seed, for the sake of
    # programs a window must not compile; here one prompt a bucket compiles what this scheduler runs)
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
    lay = lfm2.layout([r["prompt"] for r in picked], [h.result(timeout=0) for h in handles],
                      serve_mellum2.pad_to(cell), int(cell.traffic["params"]["output"]["max"]))
    engine.cache.k = engine.cache.v = None  # their room is the reference's
    engine.cache.state = {}
    del own, engine
    arms = {"program": lay["chosen"]}
    arms.update({a: lfm2.choices(params, cell.config, lay["tokens"], lay["at"], a) for a in ARITHMETICS})
    judged = lfm2.judge(params, cell.config, lay["tokens"], lay["at"], arms, lay["valid"])
    row = {"seed": seed, "limit": float(w["gap_ratio_limit"]), "request_limit": float(w["request_excess_limit"])}
    for arm in arms:
        row[arm] = dict(lfm2.reading(judged[arm]), mean_gap=float(judged[arm]["gap"].mean()),
                        gap_ratio=lfm2.gap_ratio(judged[arm], judged["bfloat16"]),
                        worst_request_ratio=lfm2.worst_request_ratio(judged[arm], judged["bfloat16"], lay["valid"]),
                        worst_request_excess=lfm2.worst_request_excess(judged[arm], judged["bfloat16"], lay["valid"])["excess"])
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
        gc.collect()  # the last seed's engine (cycles through its jits) holds 11 GB of the chip
        t0 = time.monotonic()
        row = readings(cell, seed)
        row.update(device=jax.devices()[0].device_kind, seconds=round(time.monotonic() - t0, 1))
        rows.append(row)
        print(json.dumps(row), flush=True)
        (out_dir / f"{cell.name}.json").write_text(json.dumps(rows, indent=1))
    for arm in ("program",) + ARITHMETICS[1:]:
        for stat, limit in (("gap_ratio", "limit"), ("worst_request_excess", "request_limit"), ("worst_request_ratio", "request_limit")):
            vals = [r[arm][stat] for r in rows]
            print(f"{arm:13s} {stat} over {len(rows)} seeds: {min(vals):.4g} .. {max(vals):.4g} (limit {rows[0][limit]:g})")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    sub = ap.add_subparsers(dest="what", required=True)
    sub.add_parser("compile").add_argument("--slots", type=int, nargs="+", default=[16, 32, 48, 64])
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
