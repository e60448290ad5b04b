"""On-chip XLA profile: where does the non-MXU time go?

"Is flash attention actually MXU-bound at the chosen blocks? what does
the pipeline shard_map boundary cost?" This tool captures a
jax.profiler device trace of ONE traced training window (the same
program bench.py times), parses the xplane protobuf, and reports the
per-op device-time breakdown grouped into MXU (dot/conv fusions) vs
vector/elementwise vs copy/layout vs infeed/outfeed vs collective time.

Reference analog: the reference reads per-op measured costs out of its
simulator to find hotspots (src/runtime/simulator.cc:588-628); on TPU
the equivalent ground truth is the XLA device trace.

One process on the chip (it imports bench.py's helpers, it does not run
it); without a TPU it raises.

Usage:  python tools/mfu_profile.py [--searched] [--batch 32] [--large]
Output: chiprun_out/MFU_PROFILE.json (appended per run) + stdout summary.
"""
from __future__ import annotations

import argparse
import glob
import json
import os
import sys
import time
from collections import defaultdict
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))
OUT = REPO / "chiprun_out" / "MFU_PROFILE.json"


def parse_xspace(logdir: str) -> dict:
    """Per-op device-time breakdown via the xprof ``hlo_stats`` tool.

    The converter ships its own HLO categorization (convolution fusion,
    elementwise fusion, copy, all-reduce, ...), so the fractions below
    use the profiler's official buckets rather than name heuristics.
    """
    files = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"), recursive=True)
    if not files:
        return {"error": f"no xplane.pb under {logdir}"}
    try:
        from xprof.convert import raw_to_tool_data as r2t

        data, _ctype = r2t.xspace_to_tool_data(sorted(files), "hlo_stats", {})
    except Exception as e:  # tool matrix varies across installs
        return {"error": f"hlo_stats conversion failed: {e!r}"}
    s = data.decode() if isinstance(data, (bytes, bytearray)) else data
    table = json.loads(s)
    cols = [c["id"] for c in table.get("cols", [])]
    try:
        i_cat = cols.index("category")
        i_name = cols.index("hlo_op_name")
        i_self = cols.index("total_self_time")
    except ValueError:
        return {"error": f"unexpected hlo_stats columns: {cols}"}

    per_op: dict = {}
    cats: dict = defaultdict(float)
    for row in table.get("rows", []):
        c = [cell.get("v") for cell in row["c"]]
        self_us = float(c[i_self] or 0.0)
        cats[str(c[i_cat])] += self_us
        key = (str(c[i_cat]), str(c[i_name]))
        per_op[key] = per_op.get(key, 0.0) + self_us
    total = sum(cats.values())
    if total <= 0:
        return {"error": "hlo_stats reported zero device time"}
    top = sorted(per_op.items(), key=lambda kv: -kv[1])[:25]
    return {
        "total_device_us": round(total, 1),
        "category_fractions": {k: round(v / total, 4)
                               for k, v in sorted(cats.items(), key=lambda kv: -kv[1])},
        "top_ops": [{"category": k[0], "op": k[1][:120],
                     "us": round(us, 1), "frac": round(us / total, 4)}
                    for k, us in top],
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--searched", action="store_true")
    ap.add_argument("--batch", type=int, default=32)
    ap.add_argument("--large", action="store_true")
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--iters", type=int, default=10)
    args = ap.parse_args()

    import jax
    import numpy as np

    from bench import _bench_one, peak_flops_per_device, train_flops_per_token
    from flexflow_tpu import DataType, FFConfig, LossType, SGDOptimizer
    from flexflow_tpu.device import enable_compile_cache, require_tpu
    from flexflow_tpu.models import TransformerConfig, build_transformer

    kind = require_tpu().device_kind
    enable_compile_cache()
    backend = jax.default_backend()

    cfg = TransformerConfig(
        num_layers=24 if args.large else 12,
        hidden_size=1024 if args.large else 768,
        num_heads=16 if args.large else 12,
        ff_size=4096 if args.large else 3072,
        seq_length=args.seq, dtype=DataType.BFLOAT16,
    )
    config = FFConfig(
        batch_size=args.batch, workers_per_node=len(jax.devices()), num_nodes=1,
        only_data_parallel=not args.searched,
        search_budget=5 if args.searched else 0,
    )
    model = build_transformer(config, cfg)
    model.compile(optimizer=SGDOptimizer(lr=0.01),
                  loss_type=LossType.MEAN_SQUARED_ERROR)
    ex = model.executor

    # measured step time with the SAME helper bench.py uses, so the
    # profile fractions can be read against the recorded MFU numbers.
    # The timed train_batch_repeated windows inside feed the truth
    # ledger's measure side; the executor registered the simulator's
    # predicted step time at compile — so the prediction-error block
    # below comes from the SHARED ledger, not a private comparison.
    step_s = _bench_one(ex, args.batch, cfg, args.iters)

    from flexflow_tpu.obs.truth import GLOBAL_LEDGER

    truth = next((e for e in GLOBAL_LEDGER.report()["entries"]
                  if e["key"] == f"{ex._prog_ns}.train_step"), None)
    prediction = None
    if truth is not None and truth["pairs"]:
        prediction = {
            "predicted_step_ms": round(truth["predicted_s"] * 1e3, 3),
            "measured_step_ms": round(truth["measured_p50_s"] * 1e3, 3),
            "rel_err": round(truth["rel_err_p50"], 3),
            "pairs": truth["pairs"],
            "provenance": truth["provenance"],
        }

    import jax.numpy as jnp
    rs = np.random.RandomState(0)
    x = jnp.asarray(rs.randn(args.batch, cfg.seq_length, cfg.hidden_size), cfg.dtype.jnp)
    y = jnp.asarray(rs.randn(args.batch, cfg.seq_length, cfg.hidden_size), cfg.dtype.jnp)
    rng = jax.random.key(0)

    logdir = str(REPO / ".profile" / time.strftime("%Y%m%d_%H%M%S"))
    with jax.profiler.trace(logdir):
        mets = ex.train_batch_repeated([x], y, rng, num_steps=args.iters)
        float(mets["loss"])

    breakdown = parse_xspace(logdir)

    peak = peak_flops_per_device(kind) * len(jax.devices())
    n_params = sum(int(np.prod(p.shape)) for p in jax.tree.leaves(ex.params))
    fpt = train_flops_per_token(n_params, cfg.num_layers, cfg.seq_length, cfg.hidden_size)
    mfu = (args.batch * cfg.seq_length / step_s) * fpt / peak

    entry = {
        "ts": time.strftime("%Y-%m-%dT%H:%M:%S"),
        "backend": backend, "device_kind": kind,
        "config": {"large": args.large, "batch": args.batch, "seq": args.seq,
                   "searched": args.searched},
        "step_ms": round(step_s * 1e3, 3),
        "mfu": round(mfu, 4),
        "prediction": prediction,
        "breakdown": breakdown,
    }
    data = {"what": "XLA device-trace breakdown of the timed training window",
            "runs": []}
    if OUT.exists():
        try:
            data = json.loads(OUT.read_text())
        except json.JSONDecodeError:
            pass
    data["runs"].append(entry)
    OUT.parent.mkdir(exist_ok=True)
    tmp = OUT.with_suffix(".json.tmp")
    tmp.write_text(json.dumps(data, indent=1) + "\n")
    os.replace(tmp, OUT)
    print(json.dumps({k: entry[k] for k in ("backend", "step_ms", "mfu", "prediction")} |
                     {"categories": breakdown.get("category_fractions"),
                      "top3": breakdown.get("top_ops", [])[:3]}))


if __name__ == "__main__":
    main()
