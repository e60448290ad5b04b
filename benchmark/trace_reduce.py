"""From the profiler's ``.xplane.pb`` to numbers: per-device busy time
and idle share, time per operation and per Pallas kernel, collective
time and the part of it that nothing hides, and the longest idle gaps
labelled by what the host was doing.

Read with ``jax.profiler.ProfileData`` alone. What a TPU v5e trace holds
(looked at by hand, PR 22): one plane per chip named ``/device:TPU:<n>``
whose line ``XLA Ops`` has one event per executed HLO instruction, named
by the instruction's text (``%fusion.12 = bf16[...] fusion(...)``; a
Pallas kernel is ``%<kernel name>.<n> = ... custom-call(...)``), whose
line ``XLA Modules`` has one event per program run, and whose line
``Async XLA Ops`` spans each start/done pair (transfers that run beside
the ops, so they are no part of "busy"); a plane ``/host:CPU`` with one
line per host thread of runtime TraceMe events (``PjitFunction(name)``,
transfers, ``np.asarray(jax.Array)``) and whatever ``TraceAnnotation``
the process wrote; a plane ``Task Environment`` whose stats give the
profile's start and stop. Event times are nanoseconds from the start.

The arithmetic works on plain ``(name, start_ns, end_ns)`` tuples so the
tests can hand-work it.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]
Event = Tuple[str, float, float]  # name, start_ns, end_ns

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"
COLLECTIVE = re.compile(
    r"^%?(all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all|"
    r"collective-broadcast|ragged-all-to-all)(-start|-done)?(\.|$)"
)
# the program's own spans (``obs/steptrace.phase``): ``ff.<layer>.<phase>``
PROGRAM_SPAN = "ff."
# runtime bookkeeping that is on every host thread all the time and says
# nothing about what the program was doing
_HOST_NOISE = ("MemoryAllocation", "MemoryDeallocation", "PythonRefManager", "AllocateRawBuffer")


# ------------------------------------------------------------ arithmetic


def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Sorted, disjoint intervals covering the same points."""
    out: List[List[float]] = []
    for a, b in sorted(i for i in intervals if i[1] > i[0]):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def total(intervals: Sequence[Interval]) -> float:
    return sum(b - a for a, b in intervals)


def clip(intervals: Iterable[Interval], lo: float, hi: float) -> List[Interval]:
    return [(max(a, lo), min(b, hi)) for a, b in intervals if min(b, hi) > max(a, lo)]


def subtract(a: Sequence[Interval], b: Sequence[Interval]) -> List[Interval]:
    """The points of ``a`` (disjoint, sorted) not covered by ``b``
    (disjoint, sorted)."""
    out, j = [], 0
    for lo, hi in a:
        cur = lo
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < hi:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < hi:
            out.append((cur, hi))
    return out


def gaps(busy: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    """The idle intervals of ``[lo, hi]`` given the busy union."""
    return subtract([(lo, hi)], busy)


def op_name(event_name: str) -> str:
    """``%fusion.12 = bf16[..] fusion(..)`` -> ``fusion.12``."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def op_family(name: str) -> str:
    """``fusion.12`` -> ``fusion``: instances of one instruction kind."""
    return re.sub(r"\.\d+$", "", name)


def self_times(events: Sequence[Event]) -> List[float]:
    """Each event's duration less what its nested events cover (a
    ``while`` or ``conditional`` spans its body's instructions)."""
    order = sorted(range(len(events)), key=lambda i: (events[i][1], -events[i][2]))
    own = [events[i][2] - events[i][1] for i in range(len(events))]
    stack: List[int] = []
    for i in order:
        _, a, b = events[i]
        while stack and events[stack[-1]][2] <= a:
            stack.pop()
        if stack:
            pa, pb = events[stack[-1]][1], events[stack[-1]][2]
            own[stack[-1]] -= max(0.0, min(b, pb) - max(a, pa))
        stack.append(i)
    return own


def exposed_collective(events: Sequence[Event]) -> Tuple[float, float]:
    """(time in collective instructions, the part of it during which no
    other instruction runs on that device), nanoseconds."""
    coll = union((a, b) for n, a, b in events if COLLECTIVE.match(op_name(n)))
    other = union((a, b) for n, a, b in events if not COLLECTIVE.match(op_name(n)))
    return total(coll), total(subtract(coll, other))


def label_gap(gap: Interval, host_events: Sequence[Event]) -> str:
    """What the host was doing in an idle gap: the innermost of the
    program's own spans (``ff.<layer>.<phase>``, the SHORTEST that
    covers at least half of the gap), else the shortest host event of
    any kind that does (a runtime TraceMe, the benchmark's own span),
    else the one overlapping it most, else ``unattributed``. The
    program's spans come first because they name a layer, and a
    runtime event inside one (``PjitFunction``, a transfer) names only
    the call that layer happened to be in."""
    lo, hi = gap
    best_ff: Optional[Event] = None
    best_cover: Optional[Event] = None
    best_overlap, best_any = 0.0, None
    for ev in host_events:
        n, a, b = ev
        ov = min(b, hi) - max(a, lo)
        if ov <= 0:
            continue
        if ov >= 0.5 * (hi - lo):
            if best_cover is None or b - a < best_cover[2] - best_cover[1]:
                best_cover = ev
            if n.startswith(PROGRAM_SPAN) and (best_ff is None or b - a < best_ff[2] - best_ff[1]):
                best_ff = ev
        if ov > best_overlap:
            best_overlap, best_any = ov, ev
    chosen = best_ff or best_cover or best_any
    return chosen[0] if chosen else "unattributed"


# --------------------------------------------------------------- reading


@dataclasses.dataclass
class DeviceTrace:
    ordinal: int
    ops: List[Event]
    modules: List[Event]


@dataclasses.dataclass
class Trace:
    devices: List[DeviceTrace]
    host: List[Event]
    window_ns: Tuple[float, float]


def read_xplane(path: str) -> Trace:
    from jax.profiler import ProfileData

    return from_profile_data(ProfileData.from_file(path))


def from_profile_data(data) -> Trace:
    devices, host = [], []
    start = stop = None
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            ops, modules = [], []
            for line in plane.lines:
                if line.name not in (OPS_LINE, MODULES_LINE):
                    continue
                dest = ops if line.name == OPS_LINE else modules
                for e in line.events:
                    dest.append((e.name, e.start_ns, e.start_ns + e.duration_ns))
            devices.append(DeviceTrace(int(m.group(1)), ops, modules))
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                for e in line.events:
                    if e.duration_ns > 0 and not e.name.startswith(_HOST_NOISE):
                        host.append((e.name, e.start_ns, e.start_ns + e.duration_ns))
        elif plane.name == "Task Environment":
            stats = dict(plane.stats)
            if "profile_start_time" in stats and "profile_stop_time" in stats:
                start, stop = float(stats["profile_start_time"]), float(stats["profile_stop_time"])
    every = [t for d in devices for ev in d.ops for t in ev[1:]] + [t for ev in host for t in ev[1:]]
    if start is not None and stop > start:
        window = (0.0, stop - start)
    elif every:
        window = (min(every), max(every))
    else:
        window = (0.0, 0.0)
    return Trace(sorted(devices, key=lambda d: d.ordinal), host, window)


# -------------------------------------------------------------- reducing


def reduce_trace(
    trace: Trace, kernels: Sequence[str] = (), window_ns: Optional[Interval] = None, top: int = 10,
) -> Dict:
    """The reduced trace that per-layer metrics and ``breakdown`` read.

    ``window_ns`` restricts everything to a sub-window (default: the
    whole profile). Returns, in SECONDS: ``window_s``; per device
    ``busy_s``, ``idle_share``, ``collective_s``,
    ``collective_exposed_s``; ``busy_s`` (mean over devices) and
    ``idle_share`` (the worst device's); ``kernel_s``: kernel name ->
    summed device time of its events (longest names matched first, so
    ``x_split`` is not counted under ``x``) with ``kernel_calls``;
    ``device_ops``: the ``top`` instruction families by self time summed
    over devices; ``programs``: program name -> device seconds;
    ``idle_gaps``: host label -> idle seconds on the worst device, the
    ``top`` largest, and ``longest_gaps``: the ``top`` single gaps.
    """
    lo, hi = window_ns or trace.window_ns
    span = max(hi - lo, 1e-9)
    by_len = sorted(kernels, key=len, reverse=True)
    kernel_ns = {k: 0.0 for k in kernels}
    kernel_calls = {k: 0 for k in kernels}
    family_ns: Dict[str, float] = {}
    program_ns: Dict[str, float] = {}
    per_device = []
    for dev in trace.devices:
        ops = [(n, max(a, lo), min(b, hi)) for n, a, b in dev.ops if min(b, hi) > max(a, lo)]
        busy = union((a, b) for _, a, b in ops)
        coll, exposed = exposed_collective(ops)
        for (n, a, b), own in zip(ops, self_times(ops)):
            name = op_name(n)
            fam = op_family(name)
            hit = next((k for k in by_len if k in name), None)
            if hit is not None:
                kernel_ns[hit] += b - a
                kernel_calls[hit] += 1
                fam = hit
            family_ns[fam] = family_ns.get(fam, 0.0) + own
        for n, a, b in dev.modules:
            ov = min(b, hi) - max(a, lo)
            if ov > 0:
                key = re.sub(r"\(\d+\)$", "", n)
                program_ns[key] = program_ns.get(key, 0.0) + ov
        per_device.append({
            "ordinal": dev.ordinal,
            "busy_s": total(busy) / 1e9,
            "idle_share": 1.0 - total(busy) / span,
            "collective_s": coll / 1e9,
            "collective_exposed_s": exposed / 1e9,
            "_busy": busy,
        })
    out: Dict = {
        "window_s": span / 1e9,
        "devices": per_device,
        "kernel_s": {k: v / 1e9 for k, v in kernel_ns.items()},
        "kernel_calls": kernel_calls,
        "device_ops": [[k, v / 1e9] for k, v in sorted(family_ns.items(), key=lambda kv: -kv[1])[:top]],
        "programs": {k: v / 1e9 for k, v in sorted(program_ns.items(), key=lambda kv: -kv[1])},
    }
    if not per_device:
        out.update(busy_s=0.0, idle_share=None, idle_gaps=[], longest_gaps=[])
        return out
    worst = max(per_device, key=lambda d: d["idle_share"])
    out["busy_s"] = sum(d["busy_s"] for d in per_device) / len(per_device)
    out["idle_share"] = worst["idle_share"]
    host = [(n, a, b) for n, a, b in trace.host if b > lo and a < hi]
    by_label: Dict[str, float] = {}
    idle = sorted(gaps(worst["_busy"], lo, hi), key=lambda g: g[0] - g[1])
    # every gap counts toward the idle share; only gaps of 20 us and
    # more are worth a label (the rest is the step between instructions)
    small = 0.0
    longest = []
    for g in idle:
        if g[1] - g[0] < 20e3:
            small += g[1] - g[0]
            continue
        label = label_gap(g, host)
        by_label[label] = by_label.get(label, 0.0) + (g[1] - g[0])
        if len(longest) < top:
            longest.append([label, (g[1] - g[0]) / 1e9])
    if small:
        by_label["between instructions (< 20 us each)"] = small
    out["idle_gaps"] = [[k, v / 1e9] for k, v in sorted(by_label.items(), key=lambda kv: -kv[1])[:top]]
    out["longest_gaps"] = longest
    for d in per_device:
        del d["_busy"]
    return out
