"""Step-anatomy profiler: critical-path spans and the conserved account
of the scheduler thread's seconds.

The flight recorder (obs/flight.py) records per-step phase *durations*
and renders them back-to-back — a synthetic layout that cannot show
WHERE inside the step each phase sat. This module answers two
questions:

1. **Where does a step's wall time go?** Every scheduler iteration
   decomposes into first-class host spans — ``schedule`` (expire /
   speculation planning / growth / slot collection), ``admit``
   (queue pop, block acquisition, post-prefill bookkeeping),
   ``prefix_plan`` (radix match + table assembly), ``draft``
   (speculative proposal), ``stage`` (the pipeline's assembly of a
   step's arrays), ``dispatch`` (host arg prep + XLA dispatch),
   ``post`` (a decode dispatch's device-to-host copies, cache swap
   and handle), ``block`` (host parked in ``block_until_ready``),
   ``readback`` (device->host sync + numpy conversion), ``account``
   (the engine's FLOPs and truth-ledger pair for the step), ``bookkeep``
   (token scatter), ``release`` (the consumed step's handle dropped:
   a wait for the successor in flight), ``housekeep``, ``observe``
   (this layer's own record-keeping) — plus an independently measured device-lane
   ``execute`` span. Spans carry real ``perf_counter`` offsets, not
   just durations. A dispatch has CHILDREN, ``dispatch.args`` (host
   arithmetic), ``dispatch.upload`` (host-to-device transfers) and
   ``dispatch.call`` (the jit call): they lie inside their parent, are
   no host-lane spans, and the parent's self time is its seconds less
   theirs.

2. **Is every second of the scheduler's thread accounted for?** The
   always-on aggregator keeps per-``{kind, phase}`` histograms
   (exported as ``flexflow_serving_step_phase_seconds`` on /metrics,
   their monotone totals as ``step_phases`` on ``/v2/stats``), and per
   working iteration the wall less the union of its host-lane spans
   accumulates as the pseudo-phase ``unspanned``. The ``loop`` section
   of ``/v2/stats`` carries the thread's totals: ``wall = working +
   empty + idle_wait + (the loop's own overhead)`` and ``working =
   host-lane phases + unspanned``, both exact under the overlap
   pipeline (where an iteration's ``execute`` span begins in the
   iteration before, so no per-iteration ratio of execute to wall
   means anything).

On-demand detail: :meth:`arm_capture` retains the next K steps' FULL
span lists in a bounded ring; :meth:`to_chrome_trace` renders them as a
two-lane (host tid / device tid) chrome://tracing timeline with real
span offsets — replacing the flight recorder's synthetic sequential
layout for the captured window. Served fleet-aware on
``GET /v2/debug/anatomy?capture=K`` (per-replica units, like the other
debug endpoints) and summarized by ``tools/obsreport.py anatomy``.

One way to open a span: :class:`phase`. ``with phase("sched.admit")``
enters ``jax.profiler.TraceAnnotation("ff.sched.admit")`` — so the span
is an event on the profiler's clock, on the timeline of the device ops,
whenever a trace is running (and an atomic flag test when none is) —
and hands back the ``perf_counter`` stamps of entry and exit, which is
what StepAnatomy, ``engine.phase_time_s`` and the flight record are fed
from. The scheduler, the engine, the prefix cache, the HTTP handler,
the executor and the data loader all open their spans here; the span
catalogue is in README "Step anatomy".

3. **Where do the seconds before the first step go?** Start-up has the
   same kind of account, once a process: :data:`GLOBAL_STARTUP`
   (:class:`StartupAccount`) keeps every closed ``ff.startup.*`` span
   (``import``, ``backend``, ``search`` with its children, ``mesh``,
   ``executor``, ``param_init``, ``engine_build``) as an offset from
   the process's start, and every jit program's first call split by
   JAX's own compile events (``obs/capacity.py`` feeds those). It is
   the ``startup`` section of ``/v2/stats``.

Clock discipline (the PR 6 dual-clock decision): span stamps are
``time.perf_counter`` values — physical profiling data even in
virtual-clock tests. :class:`phase` is the one place they are read,
and, for a span opened with ``cpu=True``, the one place the thread's
CPU clock (``time.thread_time``) is read beside them: wall less CPU is
the time the thread held no core (this module is whitelisted in
analysis/config.py for these two only); StepAnatomy itself only
aggregates the stamps it is handed.

CPU-backend caveat: XLA:CPU completes small programs *inside* the
dispatch call, so the measured ``execute`` span can be near zero on
tiny CPU models — a true statement about that configuration, but not a
prediction of TPU behavior, where dispatch returns early and
``execute`` covers real device compute.

Cost: observe_step is a handful of dict/float ops per scheduler
iteration under one lock; the scheduler times the call itself
(``ff.sched.observe``) and hands the seconds to the next observation,
so the ``observe`` phase is what the record-keeping costs (the
benchmark's ``trace_record_share``). ``enabled=False`` makes every
method a cheap no-op (mirrors ``observability=False``).
"""
from __future__ import annotations

import functools
import math
import os
import threading
import time
from bisect import bisect_left
from collections import deque
from time import perf_counter, thread_time
from typing import Callable, Dict, List, Optional, Sequence, Tuple

from jax.profiler import TraceAnnotation

# span names on the DEVICE lane of the two-lane timeline; everything
# else is host work. With overlap OFF, "block" (host parked in
# block_until_ready) and "execute" (device computing) cover the same
# interval; under the ISSUE 13 pipeline they genuinely diverge — an
# iteration's execute span started during the previous iteration's
# dispatch, and host bookkeeping sits under it on the other lane.
DEVICE_PHASES = frozenset({"execute"})

# a working iteration's wall less the union of its host-lane spans: a
# pseudo-phase of ``step_phases``, so that what no span names is counted
UNSPANNED = "unspanned"

# phase-duration buckets (seconds): step phases live in the us..ms
# range on warm engines; the tail covers cold CI hosts
PHASE_BUCKETS: Tuple[float, ...] = (
    1e-5, 2.5e-5, 5e-5, 1e-4, 2.5e-4, 5e-4,
    1e-3, 2.5e-3, 5e-3, 1e-2, 2.5e-2, 5e-2,
    0.1, 0.25, 1.0,
)

Span = Tuple[str, float, float]  # (phase, t0, t1) — perf_counter stamps


class phase:
    """One span on two clocks: ``with phase("<layer>.<phase>") as p``
    is a ``TraceAnnotation("ff.<layer>.<phase>", **args)`` on the
    profiler's timeline and leaves ``p.t0`` / ``p.t1``, the
    ``perf_counter`` stamps taken just inside it, so the annotation
    encloses the stamped interval by well under a microsecond. ``into``,
    a list, receives ``p.span`` on exit, early returns and exceptions
    included. Request-scoped spans pass ``request=<id>``: the spans of
    one request then share an argument in the trace. ``cpu=True`` reads
    the thread's CPU clock inside the two stamps as well (``p.c0`` /
    ``p.c1``, else None): ``p.seconds - p.cpu_seconds`` is the time the
    thread held no core inside the span (waiting for the interpreter's
    lock or a runtime's, or blocked in a transfer)."""

    __slots__ = ("name", "t0", "t1", "c0", "c1", "_ann", "_into")

    def __init__(self, name: str, into: Optional[List[Span]] = None, cpu: bool = False, **args):
        self.name = name
        self._into = into
        self.c0 = self.c1 = 0.0 if cpu else None
        self._ann = TraceAnnotation("ff." + name, **args)

    def __enter__(self) -> "phase":
        self._ann.__enter__()
        self.t0 = perf_counter()
        if self.c0 is not None:
            self.c0 = thread_time()
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        if self.c0 is not None:
            self.c1 = thread_time()
        self.t1 = perf_counter()
        self._ann.__exit__(exc_type, exc, tb)
        if self._into is not None:
            self._into.append(self.span)
        return False

    @property
    def seconds(self) -> float:
        return self.t1 - self.t0

    @property
    def cpu_seconds(self) -> Optional[float]:
        return None if self.c0 is None else self.c1 - self.c0

    @property
    def span(self) -> Span:
        """``(phase, t0, t1)`` under the name's last component, the
        key of StepAnatomy's per-``{kind, phase}`` histograms."""
        return (self.name.rpartition(".")[2], self.t0, self.t1)


class _PhaseHist:
    """Fixed-bucket histogram for one (kind, phase). No lock of its
    own: every access happens under the owning StepAnatomy._lock.

    Deliberately NOT serving/stats.Histogram: importing
    ``flexflow_tpu.serving.stats`` from here would execute the serving
    package __init__, whose ``server`` module imports ``..obs`` back
    while obs/__init__ is still mid-import of this module — a cycle
    that breaks on the obs names registered after steptrace."""

    __slots__ = ("counts", "count", "sum")

    BOUNDS: Tuple[float, ...] = PHASE_BUCKETS + (math.inf,)

    def __init__(self):
        self.counts = [0] * len(self.BOUNDS)
        self.count = 0
        self.sum = 0.0

    def observe(self, value: float) -> None:
        self.counts[bisect_left(self.BOUNDS, value)] += 1  # the first bound the value does not pass
        self.count += 1
        self.sum += value

    def buckets(self) -> List[Tuple[float, int]]:
        """Cumulative (le, count) pairs in the exposition shape."""
        cum, out = 0, []
        for b, c in zip(self.BOUNDS, self.counts):
            cum += c
            out.append((b, cum))
        return out

    def quantile(self, q: float) -> float:
        """Histogram-approximate quantile: the upper bound of the first
        bucket whose cumulative count reaches q (0 when empty)."""
        if self.count == 0:
            return 0.0
        target = q * self.count
        cum = 0
        for b, c in zip(self.BOUNDS, self.counts):
            cum += c
            if cum >= target:
                return b if math.isfinite(b) else self.BOUNDS[-2]
        return self.BOUNDS[-2]


class StepAnatomy:
    """Span-based step-anatomy aggregator for one scheduler.

    Writers: the scheduler loop thread (``observe_step``,
    ``observe_empty``, ``observe_wait``, ``loop_started`` /
    ``loop_stopped``). Readers: scrape threads (``cumulative``,
    ``loop``, ``report``, ``prom_snapshot``), the debug endpoint
    (``arm_capture``, ``to_chrome_trace``). One lock guards all mutable
    state, so a scrape reads totals of whole iterations.
    """

    def __init__(self, enabled: bool = True, capture_capacity: int = 256):
        self.enabled = enabled
        self.capture_capacity = max(1, capture_capacity)
        self._lock = threading.Lock()
        self._hists: Dict[Tuple[str, str], _PhaseHist] = {}  # guarded-by: _lock
        self.steps_total = 0  # guarded-by: _lock
        self._capture_left = 0  # guarded-by: _lock
        self._captures: deque = deque(maxlen=self.capture_capacity)  # guarded-by: _lock
        self.captures_total = 0  # guarded-by: _lock
        # the loop thread's conserved account (``loop``): seconds and
        # iterations by what the iteration was, monotone since start
        self._loop: Dict[str, float] = {  # guarded-by: _lock
            "working_total_s": 0.0, "working_iterations_total": 0,
            "empty_total_s": 0.0, "empty_iterations_total": 0,
            "cpu_total_s": 0.0, "cpu_wall_total_s": 0.0,
        }
        # the perf_counter stamp up to which ``wall_total_s`` has been
        # counted: None while no loop of the scheduler's own runs (a
        # fleet's loop or a test drives ``step()``), and then the two
        # totals that only such a loop can know are absent
        self._loop_mark: Optional[float] = None  # guarded-by: _lock

    # ---------------------------------------------------------- recording
    def observe_step(
        self,
        kind: str,
        spans: Sequence[Span],
        t_start: float,
        t_end: float,
        tokens: int = 0,
        children: Sequence[Span] = (),
        carried_s: float = 0.0,
        cpu_s: Optional[float] = None,
    ) -> None:
        """Fold one WORKING scheduler iteration into the aggregator.
        ``spans`` are (phase, t0, t1) perf_counter stamps; host-lane
        spans must be disjoint (the conservation invariant tests
        assert), device-lane spans mirror host ``block`` time on the
        other lane and are excluded from the host sum. ``children``
        are the dispatch spans' parts (``args`` / ``upload`` /
        ``call``): they lie inside a host-lane ``dispatch`` span, are
        recorded as ``dispatch.<part>`` and are in no sum of the lane.
        The iteration's wall less the union of its host-lane spans is
        its ``unspanned`` pseudo-phase. ``carried_s``: what the
        observation BEFORE this one cost (the scheduler times this call
        and hands the seconds to the next), counted as ``observe`` time
        of this iteration and on top of its wall, so ``working`` stays
        the sum of the phases. ``cpu_s``: the thread's CPU seconds
        between the two stamps, on the iterations the scheduler read
        them (one in ``CPU_CLOCK_EVERY``): summed beside those
        iterations' wall, ``cpu_wall_total_s``."""
        if not self.enabled:
            return
        wall = max(0.0, t_end - t_start)
        per_phase: Dict[str, float] = {}
        host: List[Tuple[float, float]] = []
        for name, s0, s1 in spans:
            per_phase[name] = per_phase.get(name, 0.0) + max(0.0, s1 - s0)
            if name not in DEVICE_PHASES:
                host.append((s0, s1))
        for name, s0, s1 in children:
            name = "dispatch." + name
            per_phase[name] = per_phase.get(name, 0.0) + max(0.0, s1 - s0)
        # the union, not the sum: a span that overlapped another would
        # otherwise hide as much unspanned time as it double-counts
        host.sort()
        covered, edge = 0.0, t_start
        for s0, s1 in host:
            s0, s1 = max(s0, edge), min(s1, t_end)
            if s1 > s0:
                covered += s1 - s0
                edge = s1
        per_phase[UNSPANNED] = max(0.0, wall - covered)
        if carried_s > 0.0:
            per_phase["observe"] = per_phase.get("observe", 0.0) + carried_s
        with self._lock:
            self.steps_total += 1
            for name, d in per_phase.items():
                h = self._hists.get((kind, name))
                if h is None:
                    h = self._hists[(kind, name)] = _PhaseHist()
                h.observe(d)
            acct = self._loop
            acct["working_total_s"] += wall + carried_s
            acct["working_iterations_total"] += 1
            if cpu_s is not None:
                acct["cpu_total_s"] += cpu_s
                acct["cpu_wall_total_s"] += wall
            self._mark_locked(t_end)
            if self._capture_left > 0:
                self._capture_left -= 1
                self.captures_total += 1
                self._captures.append({
                    "kind": kind,
                    "t_start": t_start,
                    "t_end": t_end,
                    "tokens": int(tokens),
                    "spans": [(n, float(s0), float(s1)) for n, s0, s1 in spans],
                    "children": [(n, float(s0), float(s1)) for n, s0, s1 in children],
                })

    def observe_empty(self, t_start: float, t_end: float) -> None:
        """An iteration in which ``step()`` found nothing to do."""
        if not self.enabled:
            return
        with self._lock:
            self._loop["empty_total_s"] += max(0.0, t_end - t_start)
            self._loop["empty_iterations_total"] += 1
            self._mark_locked(t_end)

    def loop_started(self, t: float) -> None:
        """The scheduler's own loop thread starts at ``t``: from here
        its wall is counted, up to the end of each iteration and wait."""
        if not self.enabled:
            return
        with self._lock:
            self._loop.setdefault("wall_total_s", 0.0)
            self._loop.setdefault("idle_wait_total_s", 0.0)
            self._loop_mark = t

    def observe_wait(self, t_start: float, t_end: float) -> None:
        """The loop thread was parked for work from ``t_start`` to
        ``t_end`` (no loop of the scheduler's own: not counted)."""
        if not self.enabled:
            return
        with self._lock:
            if self._loop_mark is not None:
                self._loop["idle_wait_total_s"] += max(0.0, t_end - t_start)
                self._mark_locked(t_end)

    def _mark_locked(self, t: float) -> None:
        """The loop thread reached ``t``: ``wall_total_s`` grows by the
        seconds since the mark before. What lies between an iteration's
        end and the next one's start (the loop's own overhead, the
        observation of the one before) is in the wall and in no other
        total but ``working``'s ``carried_s``."""
        if self._loop_mark is not None:
            self._loop["wall_total_s"] += max(0.0, t - self._loop_mark)
            self._loop_mark = t

    def loop_stopped(self) -> None:
        with self._lock:
            self._loop_mark = None

    # ------------------------------------------------------------ capture
    def arm_capture(self, k: int) -> int:
        """Retain the next ``k`` steps' full span lists (bounded by the
        capture ring capacity; re-arming replaces the remaining count).
        Returns the armed count — 0 when disabled."""
        if not self.enabled:
            return 0
        k = max(0, min(int(k), self.capture_capacity))
        with self._lock:
            self._capture_left = k
        return k

    def capture_state(self) -> Dict:
        with self._lock:
            return {
                "remaining": self._capture_left,
                "captured": len(self._captures),
                "captured_total": self.captures_total,
                "capacity": self.capture_capacity,
            }

    def captured_steps(self) -> List[Dict]:
        """Locked copy of the retained captures, oldest first."""
        with self._lock:
            return [dict(c) for c in self._captures]

    def to_chrome_trace(self, pid: int = 1, name: str = "step-anatomy") -> Dict:
        """The captured steps as a two-lane chrome://tracing timeline:
        tid 1 = host spans, tid 2 = device spans (``execute``), with
        REAL span offsets (microseconds relative to the oldest captured
        step) — not the flight recorder's synthetic sequential layout.
        Load in chrome://tracing or https://ui.perfetto.dev."""
        captures = self.captured_steps()
        events: List[Dict] = [
            {"name": "process_name", "ph": "M", "pid": pid,
             "args": {"name": name}},
            {"name": "thread_name", "ph": "M", "pid": pid, "tid": 1,
             "args": {"name": "host"}},
            {"name": "thread_name", "ph": "M", "pid": pid, "tid": 2,
             "args": {"name": "device"}},
        ]
        if not captures:
            return {"traceEvents": events, "displayTimeUnit": "ms"}
        t0 = captures[0]["t_start"]
        for i, cap in enumerate(captures):
            children = [("dispatch." + n, s0, s1) for n, s0, s1 in cap["children"]]
            for span, s0, s1 in cap["spans"] + children:  # a child nests in its parent on the host lane
                events.append({
                    "name": span,
                    "ph": "X",
                    "pid": pid,
                    "tid": 2 if span in DEVICE_PHASES else 1,
                    "ts": (s0 - t0) * 1e6,
                    "dur": max(0.0, s1 - s0) * 1e6,
                    "args": {"step": i, "kind": cap["kind"]},
                })
            events.append({
                "name": f"step:{cap['kind']}",
                "ph": "i", "pid": pid, "tid": 1, "s": "t",
                "ts": (cap["t_start"] - t0) * 1e6,
                "args": {"step": i, "tokens": cap["tokens"]},
            })
        return {"traceEvents": events, "displayTimeUnit": "ms"}

    # ---------------------------------------------------------- reporting
    def phases_summary(self) -> Dict[str, Dict[str, Dict]]:
        """kind -> phase -> {count, total_s, mean_s, p50_s} from the
        cumulative per-(kind, phase) histograms."""
        with self._lock:
            items = [(k, h.count, h.sum, h.quantile(0.5))
                     for k, h in sorted(self._hists.items())]
        out: Dict[str, Dict[str, Dict]] = {}
        for (kind, name), count, total, p50 in items:
            out.setdefault(kind, {})[name] = {
                "count": count,
                "total_s": total,
                "mean_s": total / count if count else 0.0,
                "p50_s": p50,
            }
        return out

    def cumulative(self) -> Dict[str, Dict]:
        """``{"<kind>.<phase>": {"count", "total_s"}}`` since start: the
        ``step_phases`` entry of ``/v2/stats``. Monotone, so the delta
        between two snapshots is what happened between them."""
        with self._lock:
            return {
                f"{kind}.{name}": {"count": h.count, "total_s": h.sum}
                for (kind, name), h in sorted(self._hists.items())
            }

    def loop(self) -> Dict[str, float]:
        """The ``loop`` section of ``/v2/stats``: the scheduler
        thread's monotone totals. ``working_total_s`` is the sum of the
        host-lane phases and ``unspanned`` of ``step_phases``;
        ``wall_total_s`` (the loop thread's clock since it started) is
        ``working + empty + idle_wait`` plus the loop's own overhead;
        ``cpu_total_s`` is the thread's CPU clock over the sampled
        working iterations, whose wall is ``cpu_wall_total_s``. Where
        something else drives ``step()``,
        ``wall_total_s`` and ``idle_wait_total_s`` are absent."""
        with self._lock:
            return dict(self._loop)

    def report(self) -> Dict:
        """The ``GET /v2/debug/anatomy`` payload for one unit."""
        return {
            "enabled": self.enabled,
            "steps_observed": self.steps_observed(),
            "phases": self.phases_summary(),
            "loop": self.loop(),
            "capture": self.capture_state(),
        }

    def steps_observed(self) -> int:
        with self._lock:
            return self.steps_total

    def prom_snapshot(self) -> List[Dict]:
        """The ``flexflow_serving_step_phase_seconds`` family's input
        for obs/prom.py: one entry per (kind, phase) with cumulative
        buckets, sorted for deterministic rendering."""
        with self._lock:
            items = [
                (kind, name, h.buckets(), h.sum, h.count)
                for (kind, name), h in sorted(self._hists.items())
            ]
        return [
            {"kind": kind, "phase": name, "buckets": buckets,
             "sum": total, "count": count}
            for kind, name, buckets, total, count in items
        ]

    def register_gauges(self, stats) -> None:
        """Surface the aggregator on a ServingStats: the cumulative
        phase sums join ``/v2/stats`` as ``step_phases`` (not for a
        disabled anatomy; the scheduler adds ``loop`` beside it, with
        the engine's dispatch clocks), the number of observations as a
        gauge on /metrics. A gauge returning None is skipped by the
        exposition — a disabled anatomy emits nothing rather than zeros
        that look like data."""
        if self.enabled:
            stats.add_section("step_phases", self.cumulative)
        stats.add_gauge(
            "step_anatomy_steps_observed",
            lambda: self.steps_observed() if self.enabled else None,
        )


# --------------------------------------------------------------------------
# The start-up account
# --------------------------------------------------------------------------

# seconds of a program's first call that JAX's compile events name
PROGRAM_PARTS = ("trace_s", "lower_s", "compile_s", "cache_load_s")


def _process_start() -> Tuple[float, str]:
    """The process's start as a ``perf_counter`` stamp, and what that
    origin is. ``/proc/self/stat`` counts the start in clock ticks
    since boot, which is ``time.monotonic``'s zero on Linux, and
    ``perf_counter`` reads the same CLOCK_MONOTONIC there: the two are
    read together once and the difference (zero on Linux) carried over.
    The rule is ``benchmark/run.py::process_start_monotonic``'s, so the
    program's zero and the harness's are one instant; where ``/proc``
    has nothing believable the origin is this module's import."""
    now = perf_counter()
    mono = time.monotonic()  # flexlint: disable=clock-discipline
    try:
        with open("/proc/self/stat") as f:
            ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        start = ticks / os.sysconf("SC_CLK_TCK")
        if 0.0 <= mono - start < 60.0:
            return start + (now - mono), "process_start"
    except (OSError, ValueError, IndexError):
        pass
    return now, "import"


def both_hit(so_far: Optional[bool], new: Optional[bool]) -> Optional[bool]:
    """A program hit the persistent cache if every compile it asked the
    cache for did (None: it never asked)."""
    if so_far is None or new is None:
        return new if so_far is None else so_far
    return so_far and new


def _union_s(intervals: List[Tuple[float, float]]) -> float:
    covered, edge = 0.0, -math.inf
    for a, b in sorted(intervals):
        a = max(a, edge)
        if b > a:
            covered += b - a
            edge = b
    return covered


class _OpenSpan:
    """What ``phase(into=)`` appends a start-up span's stamps to."""

    __slots__ = ("account", "name", "parent", "args")

    def __init__(self, account: "StartupAccount", name: str, parent: Optional[str], args: Dict):
        self.account, self.name, self.parent, self.args = account, name, parent, args

    def append(self, span: Span) -> None:
        stack = self.account._stack()
        if self in stack:  # an exception may have skipped a child's exit
            del stack[stack.index(self):]
        self.account.note_span(self.name, span[1], span[2], self.parent, self.args)


class StartupAccount:
    """Where a process's seconds go before its first warm step.

    *Spans.* ``with account.span("search.calibrate"):`` is
    ``phase("startup.search.calibrate")`` (hence
    ``ff.startup.search.calibrate`` in a profiler trace) whose stamps
    come here on exit: kept as ``(name, parent, start_offset_s, seconds,
    args)`` with the offset from the account's origin (the process's
    start), in a list bounded at ``max_spans`` (what no longer fits
    still counts in the totals). The parent is the span open on the
    same thread when this one was opened; a parent's ``self_s`` is its
    seconds less its children's.

    *Programs.* One record a jit program's trace -> lowering -> compile
    or cache load, made by ``obs/capacity.py`` from JAX's own events
    (see there): ``name``, ``at_s`` / ``end_s`` (offsets), the
    :data:`PROGRAM_PARTS`, ``cache_hit``, and ``lump_s`` / ``run_s``
    once :meth:`ProgramRegistry.set_compile_time` has stamped the wall
    of the call that traced it. The cache's answers are kept beside
    them with the module each was about.

    Written a few dozen to a few hundred times a process, at span exits
    and compile events only; nothing here runs on a warm step."""

    def __init__(self, origin: Optional[float] = None, max_spans: int = 512, max_programs: int = 2048):
        if origin is None:
            self._origin, self.origin = _process_start()
        else:
            self._origin, self.origin = float(origin), "given"
        self.max_spans, self.max_programs = max_spans, max_programs
        self._lock = threading.Lock()
        self._spans: List[Tuple] = []  # guarded-by: _lock
        self._overflow: Dict[str, List[float]] = {}  # guarded-by: _lock; name -> [count, total_s, children_s]
        self._programs: List[Dict] = []  # guarded-by: _lock
        self.programs_dropped = 0  # guarded-by: _lock
        self._cache: List[Tuple[float, str, str]] = []  # guarded-by: _lock; (at_s, kind, module)
        self._open = threading.local()

    # ------------------------------------------------------------- clock
    def now(self) -> float:
        """Seconds since the origin."""
        return perf_counter() - self._origin

    def _stack(self) -> List[_OpenSpan]:
        stack = getattr(self._open, "stack", None)
        if stack is None:
            stack = self._open.stack = []
        return stack

    # ------------------------------------------------------------- spans
    def span(self, name: str, **args) -> phase:
        """Open ``ff.startup.<name>``; use as ``with account.span(...)``."""
        stack = self._stack()
        sink = _OpenSpan(self, name, stack[-1].name if stack else None, dict(args))
        stack.append(sink)
        return phase("startup." + name, into=sink, **args)

    def annotate(self, **args) -> None:
        """Arguments known only inside a span (which table resolved the
        calibration, how many graphs the search costed) onto the span
        this thread has open; nothing where none is."""
        stack = self._stack()
        if stack:
            stack[-1].args.update(args)

    def spanned(self, name: str) -> Callable:
        """Decorator: the whole call is one ``span(name)``."""
        def wrap(fn):
            @functools.wraps(fn)
            def run(*a, **kw):
                with self.span(name):
                    return fn(*a, **kw)
            return run
        return wrap

    def note_span(self, name: str, t0: float, t1: float, parent: Optional[str] = None, args: Optional[Dict] = None) -> None:
        """One closed span from its ``perf_counter`` stamps."""
        rec = (name, parent, t0 - self._origin, max(0.0, t1 - t0), args or {})
        with self._lock:
            if len(self._spans) < self.max_spans:
                self._spans.append(rec)
            else:
                self._fold(self._overflow, rec)

    def mark_import(self) -> None:
        """``startup.import``: the origin to now, once (the package's
        ``__init__`` calls this on its last line)."""
        with self._lock:
            if any(s[0] == "import" for s in self._spans):
                return
        self.note_span("import", self._origin, perf_counter())

    @staticmethod
    def _fold(totals: Dict[str, List[float]], rec: Tuple) -> None:
        name, parent, _, seconds, _ = rec
        mine = totals.setdefault(name, [0, 0.0, 0.0])
        mine[0] += 1
        mine[1] += seconds
        if parent is not None:
            totals.setdefault(parent, [0, 0.0, 0.0])[2] += seconds

    # ---------------------------------------------------------- programs
    def add_program(self, record: Dict) -> None:
        with self._lock:
            if len(self._programs) < self.max_programs:
                self._programs.append(record)
            else:
                self.programs_dropped += 1

    def note_cache(self, kind: str, module: str) -> None:
        """The persistent cache was asked (``requests``) or answered
        (``hits`` / ``misses``) about ``module``."""
        at = self.now()
        with self._lock:
            if len(self._cache) < 4 * self.max_programs:
                self._cache.append((at, kind, module))

    # ---------------------------------------------------------- reporting
    def snapshot(self, until_s: Optional[float] = None) -> Dict:
        """The ``startup`` section of ``/v2/stats``. ``until_s`` keeps
        what had ENDED (a span) or begun (a program, a cache answer) by
        that offset: a reader in the trainer's process cuts at the
        window's opening, so what ran after it is out."""
        cut = math.inf if until_s is None else float(until_s)
        with self._lock:
            spans = [s for s in self._spans if s[2] + s[3] <= cut]
            totals = {k: list(v) for k, v in self._overflow.items()}
            programs = [dict(p) for p in self._programs if p["at_s"] < cut]
            cache = [c for c in self._cache if c[0] < cut]
            dropped = self.programs_dropped
        for rec in spans:
            self._fold(totals, rec)
        by_name: Dict[str, Dict] = {}
        intervals = [(s[2], s[2] + s[3]) for s in spans if s[1] is None]
        for p in programs:
            intervals.append((p["at_s"], min(p["end_s"], cut)))
            agg = by_name.get(p["name"])
            if agg is None:
                agg = by_name[p["name"]] = {
                    "calls": 0, **{k: 0.0 for k in PROGRAM_PARTS}, "cache_hit": None, "run_s": None, "at_s": p["at_s"],
                }
            agg["calls"] += 1
            for k in PROGRAM_PARTS:
                agg[k] += p[k]
            agg["cache_hit"] = both_hit(agg["cache_hit"], p["cache_hit"])
            if p["run_s"] is not None:
                agg["run_s"] = (agg["run_s"] or 0.0) + p["run_s"]
        counts = {kind: sum(1 for c in cache if c[1] == kind) for kind in ("requests", "hits", "misses")}
        counts["missed"] = [c[2] for c in cache if c[1] == "misses"][:64]
        return {
            "origin": self.origin,
            "now_s": self.now(),
            "phases": {
                name: {"count": int(n), "total_s": total, "self_s": total - children}
                for name, (n, total, children) in sorted(totals.items())
            },
            "programs": dict(sorted(by_name.items())),
            "programs_dropped": dropped,
            "cache": counts,
            "spanned_s": _union_s(intervals),
            "spans": [list(s) for s in spans],
        }

    def summary(self) -> str:
        """The phases' seconds and the programs' parts on one line, for
        the line ``FFModel.compile`` prints."""
        snap = self.snapshot()
        sums = {k: sum(p[k] for p in snap["programs"].values()) for k in PROGRAM_PARTS}
        return (", ".join(f"{name} {p['total_s']:.2f}" for name, p in snap["phases"].items())
                + "; programs " + ", ".join(f"{k[:-2]} {v:.2f}" for k, v in sums.items()))


# One account a process: its origin is the process's start, and every
# ``ff.startup.*`` span and every program's first call lands here.
GLOBAL_STARTUP = StartupAccount()
