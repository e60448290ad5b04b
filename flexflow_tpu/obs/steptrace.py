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

import math
import threading
from bisect import bisect_left
from collections import deque
from time import perf_counter, thread_time
from typing import Dict, List, Optional, Sequence, Tuple

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
