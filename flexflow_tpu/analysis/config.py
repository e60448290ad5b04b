"""flexlint policy: which files are exempt from which rule, and why.

This file is the reviewed, centralized counterpart to inline
``# flexlint: disable=`` comments: inline suppressions are for single
statements; entries here are for whole files whose PURPOSE exempts them
(a calibration harness exists to measure physical wall time). Every
entry carries its reason so a reviewer can re-litigate it.
"""
from __future__ import annotations

from typing import Dict, FrozenSet, Union

# --------------------------------------------------------------- clocks
# Wall-clock whitelist for the clock-discipline rule. Keys are
# repo-relative paths (a trailing "/" whitelists the directory); values
# are "*" (any of time.time / time.monotonic / time.perf_counter /
# time.thread_time) or
# the frozenset of allowed function names. Everything else must take an
# injectable clock so virtual-clock tests control time.
CLOCK_WHITELIST: Dict[str, Union[str, FrozenSet[str]]] = {
    # Offline bench/diagnostic harnesses: measuring physical wall time
    # is their job (chaoscheck/obsreport/calib_debug/loadgen/simfleet),
    # and their watchdog waits bound real blocking calls.
    "tools/": "*",
    # Kernel calibration measures device wall time by definition.
    "flexflow_tpu/search/calibration.py": "*",
    # The op profiler is a physical-time measurement instrument.
    "flexflow_tpu/runtime/profiling.py": "*",
    # PR 6 dual-stamp decision: device-step phase DURATIONS are
    # physical profiling data (perf_counter) even in virtual-clock
    # tests; scheduler-plane timestamps still ride the injectable
    # clock. Host spans are opened through obs/steptrace.phase (below),
    # so the engine, the executor, the prefix cache, the HTTP handler
    # and the data loader read no clock of their own; what is left here
    # is the scheduler's iteration wall and the device-lane "execute"
    # stamp, which are not host spans. Only perf_counter is exempt —
    # time.time/monotonic in this file is still a violation — and,
    # since ISSUE 37, thread_time: the thread's CPU clock at the two
    # ends of a working iteration (/v2/stats "loop" cpu_total_s), which
    # is no timestamp at all and can sit on no timeline, virtual or
    # physical; only differences of it are kept.
    "flexflow_tpu/generation/scheduler.py": frozenset({"perf_counter", "thread_time"}),
    # Grammar-compile telemetry (ISSUE 18): compile_seconds is physical
    # profiling data like the engine's phase spans — perf_counter only.
    "flexflow_tpu/generation/constrained/tokens.py": frozenset({"perf_counter"}),
    # Step-anatomy profiler (ISSUE 12) and phase(), the one way a span
    # is opened (ISSUE 23): perf_counter-only physical profiling per
    # the PR 6 dual-clock decision — phase() stamps every host span,
    # StepAnatomy aggregates the stamps, and neither may mix in the
    # scheduler's injectable (possibly virtual) clock. thread_time
    # (ISSUE 37): phase(cpu=True) reads the thread's CPU clock inside
    # its two stamps, so a span's wall less its CPU seconds says how
    # long the thread held no core; a duration, never a timestamp.
    "flexflow_tpu/obs/steptrace.py": frozenset({"perf_counter", "thread_time"}),
    # Durable WAL (ISSUE 19): fsync DURATION is physical profiling data
    # (perf_counter only). Journal-record wall stamps ride the
    # injectable wall_clock passed to WriteAheadLog — time.time /
    # monotonic calls in this file are still violations.
    "flexflow_tpu/runtime/wal.py": frozenset({"perf_counter"}),
}

# Paths where clock-discipline runs in STRICT virtual-time mode: ANY
# reference to a real clock — a call, a bare name, an injectable
# default argument, even perf_counter — is a violation, and the
# whitelist above does not apply. The fleet digital twin
# (flexflow_tpu/sim/) is deterministic by contract: its only time
# source is the event loop's virtual clock, and a single real stamp
# breaks byte-identical replay and the simcheck divergence gate.
CLOCK_STRICT_PATHS = ("flexflow_tpu/sim/",)

# ----------------------------------------------------------- fault sites
# Files the fault-site rule does not police: the registry itself (it
# DEFINES the literals) and this analysis package (rule fixtures).
SITE_RULE_EXCLUDE = (
    "flexflow_tpu/runtime/faults.py",
    "flexflow_tpu/analysis/",
)

# Site literals must start with one of these segments to be treated as
# fault-site names when passed to FaultPlan.on(...) (tests register
# synthetic sites like "site.a"; those live under tests/ which is not
# scanned, but the prefix filter also keeps .on(...) of unrelated APIs
# out of this rule's jurisdiction).
SITE_PREFIXES = (
    "executor.", "elastic.", "checkpoint.", "serving.", "generation.",
    "fleet.",
)
