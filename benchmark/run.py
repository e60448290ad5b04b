#!/usr/bin/env python3
"""The benchmark's one command.

    python benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

ONE process holds the chip(s). It refuses to start unless JAX's first
device is a TPU and there are as many as the cell asks for: the exit
code is then non-zero and no result line is printed. Inputs and weights
come from ``--seed``. Everything the window will run is warmed first and
counts as set-up; then the cell's traffic runs for a short unmeasured
lead-in and ``--seconds`` measured seconds. The LAST line of stdout is
one JSON object: ``correct``, ``attempted``, ``failed``, ``metrics``,
``device`` and, with ``--trace 1``, ``breakdown``; last in it, where the
cell's driver hands them over (``ctx["compared"]``), ``compared``: every
number that decided ``correct`` beside its limit, which are also the last
lines on standard error. With ``--trace 0``
the metrics are the cell's end-to-end metrics; with ``--trace 1`` a
profiler trace of part of the window is taken and the metrics are the
cell's per-layer metrics. Earlier lines are a log (medians, counts).

``--rehearse`` is for the sandbox only: tiny widths on the CPU backend,
the whole control flow, and NO result line and no device metric.
"""
from __future__ import annotations

import time

_T_IMPORT = time.monotonic()

import argparse
import importlib
import json
import os
import pathlib
import shutil
import sys
from typing import Dict, List, Optional

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parent
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from benchmark import kernel_model, layer_metrics, spec, stats, trace_reduce  # noqa: E402

PALLAS_KERNELS = kernel_model.PAGED_KERNELS + kernel_model.FLASH_KERNELS


def process_start_monotonic() -> float:
    """When this process started, on ``time.monotonic()``'s clock (Linux:
    CLOCK_MONOTONIC counts from boot, as ``/proc/<pid>/stat`` does), so
    that interpreter start-up is inside ``setup_s``."""
    try:
        with open("/proc/self/stat") as f:
            ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        start = ticks / os.sysconf("SC_CLK_TCK")
        if 0.0 <= _T_IMPORT - start < 60.0:
            return start
    except (OSError, ValueError, IndexError):
        pass
    return _T_IMPORT


class Runtime:
    """What a driver needs from the harness: the clock's origin, a log,
    the compile counter, and the profiler around part of the window."""

    def __init__(self, args, cell: spec.Cell):
        self.args = args
        self.cell = cell
        self.t_start = process_start_monotonic()
        self.compiles: List[float] = []  # monotonic time of every backend compile
        self.trace_dir = ROOT / ".bench_trace" / cell.name
        self.trace_t0: Optional[float] = None  # monotonic time at which the trace began
        self._stopped = False

    def log(self, msg: str) -> None:
        print(f"[bench +{time.monotonic() - self.t_start:7.2f}s] {msg}", flush=True)

    # -- compiles: nothing may compile inside the measured window
    def watch_compiles(self) -> None:
        import jax.monitoring

        def on_event(name: str, _secs: float, **_kw) -> None:
            if name == "/jax/core/compile/backend_compile_duration":
                self.compiles.append(time.monotonic())

        jax.monitoring.register_event_duration_secs_listener(on_event)

    def compiles_between(self, lo: float, hi: float) -> int:
        return sum(lo <= t < hi for t in self.compiles)

    # -- the profiler, around the last seconds of the window
    def trace_start(self) -> None:
        import jax

        shutil.rmtree(self.trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0  # the Python tracer slows the host several-fold
        opts.host_tracer_level = 2
        jax.profiler.start_trace(str(self.trace_dir), profiler_options=opts)
        # collection is on when start_trace returns; the trace's own
        # clock starts within some tens of milliseconds of this
        self.trace_t0 = time.monotonic()

    def trace_stop(self) -> None:
        """Stopping writes the trace and stalls the whole process for
        seconds, so the drivers call this only AFTER the window has
        closed; the reduction then looks at the part inside the window."""
        import jax

        if self.trace_t0 is not None and not self._stopped:
            self._stopped = True
            jax.profiler.stop_trace()

    def reduced_trace(self, seconds: float) -> Optional[Dict]:
        """The trace reduced over its first ``seconds`` seconds."""
        files = sorted(self.trace_dir.glob("plugins/profile/*/*.xplane.pb"))
        if not files:
            return None
        t0 = time.monotonic()
        reduced = trace_reduce.reduce_trace(
            trace_reduce.read_xplane(str(files[-1])), PALLAS_KERNELS, window_ns=(0.0, seconds * 1e9)
        )
        self.log(
            f"trace {files[-1].stat().st_size / 2**20:.1f} MiB reduced in {time.monotonic() - t0:.1f}s: "
            f"window {reduced['window_s']:.3f}s, busy {reduced['busy_s']:.3f}s, "
            f"programs {reduced['programs']}"
        )
        shutil.rmtree(self.trace_dir, ignore_errors=True)
        return reduced


def result_object(cell: spec.Cell, ctx: Dict, device: Dict, reduced: Optional[Dict], log) -> Dict:
    """The result line's object from what the driver gathered: without a
    reduced trace the cell's end-to-end metrics, with one its per-layer
    metrics, the device's busy seconds and the breakdown. Every metric
    comes from its reader (``layer_metrics/``)."""
    out: Dict = {
        "correct": bool(ctx["correct"]), "attempted": int(ctx["attempted"]), "failed": int(ctx["failed"]),
    }
    device = dict(device, memory_peak_bytes=int(ctx["memory_peak_bytes"]))
    if reduced is None:
        out["metrics"] = layer_metrics.read_all(cell.end_to_end, ctx, log)
        missing = sorted({m["name"] for m in cell.end_to_end} - set(out["metrics"]))
        if missing:
            raise RuntimeError(f"{cell.name} did not produce its end-to-end metrics {missing}")
    else:
        ctx["trace"] = reduced
        out["metrics"] = layer_metrics.read_all(cell.per_layer, ctx, log)
        device.update(busy_s=reduced["busy_s"], window_s=reduced["window_s"])
        out["breakdown"] = {
            "device_ops": reduced["device_ops"][:10], "idle_gaps": reduced["idle_gaps"][:10],
        }
    out["device"] = device
    return out


def compared_numbers(ctx: Dict) -> Dict:
    """``ctx["compared"]`` as plain numbers: ``{name: [value, limit]}``,
    every number the driver's comparison held beside its limit
    (``at_least`` in the name where the limit is a floor; a value that
    could not be read is ``None``)."""
    plain = lambda x: None if x is None else float(x)  # noqa: E731
    return {name: [plain(value), plain(limit)] for name, (value, limit) in (ctx.get("compared") or {}).items()}


def compared_lines(ctx: Dict) -> List[str]:
    """What decided ``correct``, a line each: the numbers compared
    beside their limits and, where the run is not correct, why."""
    lines = [f"compared: {name} {value} (limit {limit})" for name, (value, limit) in compared_numbers(ctx).items()]
    return lines + [f"not correct: {why}" for why in ctx.get("why_incorrect") or []]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="sandbox only: tiny widths on the CPU, no result line")
    args = ap.parse_args(argv)

    cell = spec.load_cell(args.workload, rehearsal=args.rehearse)
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ.setdefault(
            "XLA_FLAGS", f"--xla_force_host_platform_device_count={max(cell.chips, 1)}"
        )
    rt = Runtime(args, cell)

    import jax

    from flexflow_tpu.device import enable_compile_cache, require_tpu

    if args.rehearse:
        dev = jax.devices()[0]
    else:
        dev = require_tpu()
        cache_dir = enable_compile_cache()
        # small programs too: every run is a new process, and whatever
        # is not in the cache compiles again in every run's set-up
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
        rt.log(f"compile cache: {cache_dir}")
    n_dev = len(jax.devices())
    if n_dev < cell.chips:
        raise RuntimeError(f"{cell.name} needs {cell.chips} chips, JAX has {n_dev}")
    peaks = None if args.rehearse else stats.chip_peaks(dev.device_kind)
    rt.log(f"{cell.name}: seed {args.seed}, {args.seconds}s, trace {args.trace}, "
           f"device {dev.platform}/{dev.device_kind} x{n_dev}")
    rt.watch_compiles()

    driver = importlib.import_module(f"benchmark.drivers.{cell.driver}")
    ctx = driver.run(cell, rt, peaks)

    device = {"platform": dev.platform, "kind": dev.device_kind, "count": n_dev}
    reduced = rt.reduced_trace(ctx["traced_s"]) if args.trace else None
    if args.rehearse:
        # the same assembly as on the chip, peaks of 1 standing in for
        # the chip's; names only: a CPU run has no metric to show
        ctx["peaks"] = {"bf16_flops_per_s": 1.0, "hbm_bytes_per_s": 1.0}
        found = result_object(cell, ctx, device, reduced, rt.log)["metrics"]
        rt.log(f"readers that found something: {sorted(found)}")
        rt.log(f"rehearsal done: correct={ctx['correct']} attempted={ctx['attempted']} "
               f"failed={ctx['failed']} why={ctx.get('why_incorrect')}; no result line, by design")
        sys.stderr.writelines(line + "\n" for line in compared_lines(ctx))
        return 0 if ctx["correct"] else 1

    if args.trace and (reduced is None or reduced["busy_s"] <= 0.0):
        raise RuntimeError("the traced window holds no device operation")
    ctx["peaks"] = peaks
    result = result_object(cell, ctx, device, reduced, rt.log)
    if reduced is not None:
        rt.log(f"kernels: {reduced['kernel_s']} calls {reduced['kernel_calls']}")
        rt.log(f"longest single gaps: {reduced['longest_gaps']}")
    if not result["correct"]:
        rt.log(f"NOT CORRECT: {ctx.get('why_incorrect')}")
    if ctx.get("compared"):
        result["compared"] = compared_numbers(ctx)  # the last key: each number compared, beside its limit
    print(json.dumps(result), flush=True)
    sys.stderr.writelines(line + "\n" for line in compared_lines(ctx))
    return 0


if __name__ == "__main__":
    sys.exit(main())
