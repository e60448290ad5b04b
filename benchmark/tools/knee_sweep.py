#!/usr/bin/env python3
"""The one-off sweep that finds an open-loop cell's knee, on the chip:

    chiprun -- python benchmark/tools/knee_sweep.py --workload gpt2-medium.chat-steady \
        --slots 8 16 --rates 8 12 16 20 24 --seconds 50 --seed 1 2

One process: the engine is built and warmed once per slot count, the
server stays up, and every seed runs every rate (lowest first) with the
cell's own traffic (same generator, lengths and limits, only
``rate_per_s`` changed) for the cell's lead-in plus ``--seconds``, then
drains. A seed's sweep ends after a rate at which fewer than half of
the requests met the limits (``STOP_BELOW``): the rates above it only
queue. Per rate it prints the share of requests inside both limits
(first token from due, mean gap), the tails, how full the batch ran, how
late the generator sent, and whether the backlog grew: the median
first-token wait of the last third of the window against the first
third's. The first point on a fresh engine also holds whatever the
program warms up under traffic, unless the cell's lead-in covers it.

The KNEE (``knee_of``) is the highest rate such that it and every swept
rate under it met, on every seed: at least 90 % of requests inside both
limits and no growing backlog. A cell runs at 0.8 x the knee or below,
and says at which share: write the points and the rate into the cell's file
(``workloads/<cell>.json``: ``knee`` and ``traffic_params.rate_per_s``)
and into PERF.md. A benchmark run never searches for a rate.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmark import layer_metrics, spec, stats, traffic  # noqa: E402
from benchmark.drivers import serve  # noqa: E402


STOP_BELOW = 0.5  # share of requests inside the limits under which a seed's sweep ends


def one_rate(url, cell, engine, cfg, rate, seconds, seed, lm_stats):
    """One swept point: the cell's traffic at ``rate`` for a lead-in
    plus ``seconds``, drained, read by the cell's own readers."""
    w = cell.workload
    lead_in = float(w["lead_in_s"])
    sched = traffic.schedule(
        cell.traffic["generator"], seed, lead_in + seconds,
        dict(cell.traffic["params"], rate_per_s=rate), {"vocab_size": cfg.vocab_size},
    )
    before = serve.engine_snapshot(engine)
    child, t0 = serve.start_loadgen(url, sched, w, lead_in + seconds)
    gen = serve.finish_loadgen(child, w)
    after = serve.engine_snapshot(engine)
    lo, hi = t0 + lead_in, t0 + lead_in + seconds
    # the same readers as a run of the cell (benchmark/layer_metrics/)
    ctx = {"cell": cell, "records": gen["records"], "window": (lo, hi),
           "engine_open": before, "engine_close": after, "slots": engine.max_batch_slots}
    read = lambda name: layer_metrics.read(name, ctx)
    by_due = sorted(stats.window_ok(ctx), key=lambda r: r["due"])
    third = max(1, len(by_due) // 3)
    early = stats.median([stats.ttft_from_due_ms(r) for r in by_due[:third]])
    late = stats.median([stats.ttft_from_due_ms(r) for r in by_due[-third:]])
    point = {
        "rate_per_s": rate, "seconds": seconds, "lead_in_s": lead_in,
        "due": len(stats.due_in_window(gen["records"], lo, hi)), "ok": len(by_due),
        "undrained": gen["undrained"], "met_share": read("slo_attainment") / 100.0,
        **{name: read(name) for name in
           ("ttft_p50_ms", "ttft_p90_ms", "itl_p50_ms", "itl_p95_ms", "itl_p99_ms", "slow_gap_share", "decode_step_ms",
            "batch_occupancy", "generator_lag_p99_ms")},
        "ttft_p50_first_third_ms": early, "ttft_p50_last_third_ms": late,
        "backlog_grows": late > 2.0 * early + 100.0,
        "completed_per_s": len(stats.completed_in_window(gen["records"], lo, hi)) / seconds,
        "queue_depth_at_end": lm_stats().get("queue_depth"),
    }
    print(json.dumps(point), flush=True)
    return point


def knee_of(points):
    """The knee of one slot count's points: the highest swept rate such
    that it and every swept rate under it met the rule on every seed
    that swept it (>= 90 % of requests inside both limits and no growing
    backlog). None where the lowest rate already fails."""
    ok = lambda p: p["met_share"] >= 0.9 and not p["backlog_grows"]  # noqa: E731
    knee = None
    for rate in sorted({p["rate_per_s"] for p in points}):
        if not all(ok(p) for p in points if p["rate_per_s"] == rate):
            break
        knee = rate
    return knee


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seed", type=int, nargs="+", default=[1],
                    help="each seed sweeps every rate on the one warmed engine; "
                         "rate i of seed s sends schedule 1000 s + i")
    ap.add_argument("--slots", type=int, nargs="+", help="default: the cell's own")
    ap.add_argument("--out", default=str(ROOT / "chiprun_out" / "knee_sweep.json"))
    args = ap.parse_args()

    from flexflow_tpu.device import enable_compile_cache, require_tpu
    from flexflow_tpu.serving import InferenceServer
    from flexflow_tpu.serving.generation import GenerationModel

    dev = require_tpu()
    enable_compile_cache()
    cell = spec.load_cell(args.workload)
    points = []
    out = pathlib.Path(args.out)
    out.parent.mkdir(parents=True, exist_ok=True)
    write = lambda pts: out.write_text(  # noqa: E731
        json.dumps({"device": dev.device_kind, "seconds": args.seconds, "points": pts}, indent=1))
    for slots in args.slots or [cell.workload["deployment"]["slots"]]:
        cell.workload["deployment"]["slots"] = slots
        params, cfg, engine = serve.build_engine(cell, args.seed[0])
        probe = traffic.schedule(
            cell.traffic["generator"], args.seed[0], 600.0,
            dict(cell.traffic["params"], rate_per_s=max(args.rates)), {"vocab_size": cfg.vocab_size},
        )
        serve.warm(engine, probe["requests"], cfg.vocab_size, args.seed[0], print)
        server = InferenceServer(port=0)
        model = GenerationModel(engine, name="lm")
        server.register_generation(model)
        with server:
            url = f"http://127.0.0.1:{server.port}"
            for seed in args.seed:
                for i, rate in enumerate(sorted(args.rates)):
                    p = one_rate(url, cell, engine, cfg, rate, args.seconds, 1000 * seed + i,
                                 lambda: server.stats()["generation"]["lm"])
                    p.update(slots=slots, seed=seed)
                    points.append(p)
                    write(points)  # after every point: a call cut short keeps what it swept
                    time.sleep(1.0)
                    if p["met_share"] < STOP_BELOW:
                        break
        del engine, params, model, server
    for slots in sorted({p["slots"] for p in points}):
        knee = knee_of([p for p in points if p["slots"] == slots])
        print(f"slots {slots}: knee {knee} requests/s on seeds {args.seed} "
              f"(0.8 x knee = {0.8 * knee if knee else None})")
    return 0


if __name__ == "__main__":
    sys.exit(main())
