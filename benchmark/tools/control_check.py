#!/usr/bin/env python3
"""The two readings a decoder cell's ``correct`` limit is set from, at
the cell's own size, on the chip:

    chiprun -- python benchmark/tools/control_check.py --workload gpt2-medium.chat-steady --seeds 1 2 3

Per seed: the weights, the engine and the schedule as a run of the cell
makes them; ``reference_sample`` requests of the schedule picked by the
seed and served by the program (in this process, through its own
scheduler, without the HTTP front end); their tokens judged by the
float32 reference exactly as the driver judges served ones (the SOUND
reading); then, after the same prefixes, the choices of the reference
in each lower precision (``benchmark/reference/control.py``) judged
the same way (the CONTROL's reading). Prints one JSON line per seed
and, per arm, the smallest and largest ``near_tie_gap`` over the seeds;
the rows go to ``chiprun_out/control/``. A limit lies above the sound
runs' largest and below the control's smallest; where the second is
under three times the first, no limit holds. A benchmark run never
runs this.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmark import spec, traffic  # noqa: E402
from benchmark.drivers import serve  # noqa: E402


CONTROLS = ("bfloat16", "int8")


def readings(cell: spec.Cell, seed: int):
    """One seed's row: ``decoder.reading`` of the program's tokens and
    of each control's choices after the same prefixes."""
    import numpy as np

    from benchmark.reference import control, decoder
    from flexflow_tpu.generation.engine import SamplingParams
    from flexflow_tpu.generation.scheduler import ContinuousBatchingScheduler

    w = cell.workload
    params, cfg, engine = serve.build_engine(cell, seed)
    reqs = traffic.schedule(cell.traffic["generator"], seed, float(w["lead_in_s"]) + 50.0,
                            cell.traffic["params"], {"vocab_size": cfg.vocab_size})["requests"]
    serve.warm(engine, reqs, cfg.vocab_size, seed, lambda m: None)
    picked = [reqs[i] for i in np.random.RandomState(seed + 2).choice(
        len(reqs), size=min(int(w["reference_sample"]), len(reqs)), replace=False)]
    own = ContinuousBatchingScheduler(engine)
    handles = [own.submit(list(r["prompt"]), SamplingParams(max_new_tokens=r["max_new_tokens"])) for r in picked]
    while any(not h.done() for h in handles) and own.step():
        pass
    lay = decoder.layout([r["prompt"] for r in picked], [h.result(timeout=0) for h in handles],
                         engine.max_seq_len, int(cell.traffic["params"]["output"]["max"]))
    arms = {"program": lay["chosen"]}
    arms.update({p: control.choices(params, lay["tokens"], lay["at"], p) for p in CONTROLS})
    row = {"seed": seed, "limit": float(w["near_tie_gap_limit"])}
    for arm, chosen in arms.items():
        row[arm] = decoder.reading(decoder.judge(params, lay["tokens"], lay["at"], chosen, lay["valid"]))
    return row


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args()

    import jax

    from flexflow_tpu.device import enable_compile_cache, require_tpu

    require_tpu()
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    cell = spec.load_cell(args.workload)
    out_dir = ROOT / "chiprun_out" / "control"
    out_dir.mkdir(parents=True, exist_ok=True)
    rows = []
    for seed in args.seeds:
        t0 = time.monotonic()
        row = readings(cell, seed)
        row.update(device=jax.devices()[0].device_kind, seconds=round(time.monotonic() - t0, 1))
        rows.append(row)
        print(json.dumps(row), flush=True)
    for arm in ("program",) + CONTROLS:
        vals = [r[arm]["near_tie_gap"] for r in rows]
        print(f"{arm:10s} near_tie_gap over {len(rows)} seeds: {min(vals):.4g} .. {max(vals):.4g} (limit {rows[0]['limit']:g})")
    (out_dir / f"{cell.name}.json").write_text(json.dumps(rows, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
