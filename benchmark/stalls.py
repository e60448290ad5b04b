"""Where a window's lost seconds went: what a driver logs beside its
result so that a run that read low says whether the machine, the
interpreter or the scheduler stood still, and in which phase.

Three views of one window, none of which is a metric:

* the driver's own thread sleeps in steps of 50 ms; a wake that comes
  over ``LATE_S`` late is kept with the CPU seconds the whole process
  used meanwhile. Late and busy (CPU ~ the lateness): a thread held the
  interpreter without a pause (the program, or a collection: those over
  ``LATE_S / 4`` are kept by generation). Late and idle (CPU ~0): the
  process was not run, or a thread slept in a call that keeps the
  interpreter. The kernel's own counts tell the two apart without a
  second process: the seconds the hypervisor took from the guest's
  processors meanwhile (``steal`` of ``/proc/stat``; the machine's) and
  the seconds this thread stood runnable with no processor to run on
  (``run_delay`` of ``/proc/thread-self/schedstat``; the guest's other
  work). With neither, the thread slept on the interpreter's lock;
* the clients' token times, all streams together: the longest silences.
  A silence with no late wake is the scheduler's alone;
* the scheduler's step anatomy (``model.anatomy``): seconds a phase grew
  by inside the window, and the observations of a phase over 0.25 s and
  over 1 s (its two last buckets), so a stall has a phase's name.
"""
from __future__ import annotations

import gc
import os
import time
from typing import Dict, List

import numpy as np

LATE_S = 0.3
TICK_S = 1.0 / os.sysconf("SC_CLK_TCK")


def taken_s() -> tuple:
    """(seconds the hypervisor took from all processors so far, seconds
    this thread waited runnable so far); zeros where the kernel says
    neither."""
    try:
        with open("/proc/stat") as f:
            steal = int(f.readline().split()[8]) * TICK_S
        with open("/proc/thread-self/schedstat") as f:
            return steal, int(f.read().split()[1]) * 1e-9
    except (OSError, IndexError, ValueError):
        return 0.0, 0.0


class Watch:
    def __init__(self, anatomy):
        self.anatomy = anatomy
        # (monotonic at the sleep's start, seconds the sleep took, then meanwhile: process CPU seconds, stolen seconds, runnable seconds)
        self.late: List[tuple] = []
        self.collections: List[tuple] = []  # (monotonic at its start, generation, seconds)
        self._gc_t0 = 0.0

    def _on_gc(self, when: str, info: Dict) -> None:
        if when == "start":
            self._gc_t0 = time.monotonic()
        elif time.monotonic() - self._gc_t0 > LATE_S / 4:
            self.collections.append((self._gc_t0, info["generation"], time.monotonic() - self._gc_t0))

    def open(self) -> None:
        gc.callbacks.append(self._on_gc)
        self._phases_open = self._phases()

    def close(self) -> None:
        gc.callbacks.remove(self._on_gc)
        self._phases_close = self._phases()

    def sleep(self, until: float, step: float = 0.05) -> None:
        """``serve.sleep_until`` that keeps the wakes that came late."""
        last, cpu, taken = time.monotonic(), time.process_time(), taken_s()
        while last < until:
            time.sleep(min(until - last, step))
            now, cpu_now, taken_now = time.monotonic(), time.process_time(), taken_s()
            if now - last > step + LATE_S:
                self.late.append((last, now - last, cpu_now - cpu, taken_now[0] - taken[0], taken_now[1] - taken[1]))
            last, cpu, taken = now, cpu_now, taken_now

    def _phases(self) -> Dict:
        return {
            f"{h['kind']}.{h['phase']}": (h["sum"], h["count"], dict(h["buckets"]))
            for h in self.anatomy.prom_snapshot()
        }

    def report(self, records: List[Dict], t_open: float, t_close: float) -> List[str]:
        at = lambda t: f"+{t - t_open:.2f}s"  # noqa: E731
        times = np.sort(np.array([t for r in records for t in r.get("token_times") or [] if t_open <= t < t_close] or [t_open]))
        edges = np.concatenate([[t_open], times, [t_close]])
        gaps = np.diff(edges)
        silences = [(at(edges[i]), round(float(gaps[i]), 3)) for i in np.argsort(gaps)[::-1][:4]]
        grew, long = {}, {}
        for name, (total, count, buckets) in self._phases_close.items():
            total0, count0, buckets0 = self._phases_open.get(name, (0.0, 0, {}))
            if total - total0 >= 0.2:
                grew[name] = round(total - total0, 2)
            over = [(count - buckets[le]) - (count0 - buckets0.get(le, 0)) for le in (0.25, 1.0)]
            if over[0]:
                long[name] = {"over_0.25s": over[0], "over_1s": over[1]}
        return [
            f"stalls: {len(self.late)} wakes of this thread over {LATE_S} s late "
            f"{[(at(t), round(took, 2), f'cpu {cpu:.2f}s stolen {stolen:.2f}s runnable {ran:.2f}s') for t, took, cpu, stolen, ran in self.late]}"
            f"; collections over {LATE_S / 4:.3f} s "
            f"{[(at(t), f'gen {g}', round(s, 3)) for t, g, s in self.collections]}; the longest silences of all streams together "
            f"{silences}",
            f"scheduler: seconds by phase inside the window {dict(sorted(grew.items(), key=lambda kv: -kv[1]))}; "
            f"phases with an observation over 0.25 s: {long}",
        ]
