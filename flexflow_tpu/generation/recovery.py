"""Self-healing layer around the generation engine: journal-replay
recovery, crash supervision, and a step watchdog.

FlexFlow's Legion runtime survives individual task failures by
re-executing tasks from logged state; this module gives the generation
plane the same property. The key observation is that PR 2/3's
determinism work already made every stream *exactly replayable*: the
per-request sampling key is indexed by generated-token count, and
recompute-prefill (the preemption path) reproduces a stream token for
token. Crash recovery therefore needs no device-side checkpoint at all
— only the host-side **generation journal** (prompt, emitted tokens,
sampling/speculation state), which the scheduler keeps anyway.

Three cooperating pieces:

* :class:`GenerationJournal` — the per-request replay log. An entry is
  recorded at admission and discarded when the request leaves its slot
  (finish, fail, preempt, quarantine). After an engine teardown,
  ``drain()`` hands the supervisor everything needed to rebuild every
  live stream by recompute-replay.
* :class:`EngineSupervisor` — wraps every batched device step. On a
  step failure it (1) retries the step once (transient flukes beyond
  the RetryPolicy's retryable set), (2) decides whether the failure is
  *data-dependent* by bisecting the batch with subset probes — a
  request whose subset reproducibly crashes alone is **quarantined**
  (failed alone; the batch survives), (3) otherwise tears the engine
  down (``engine.reset()``: fresh KV cache + allocator, no recompiles)
  and journal-replays every live stream, under an exponential-backoff
  restart budget. NaN/inf logits never raise: the engine's in-jit
  ``isfinite`` reduce surfaces a per-slot blame vector and the poisoned
  request is quarantined directly (partial blame) or the engine is
  restarted (whole-batch blame = not data-dependent).
* :class:`StepWatchdog` — detects *stalled* device steps via a
  heartbeat the scheduler stamps around every device call. A step older
  than ``stall_timeout_s`` trips the per-model circuit breaker (so
  ``/v2/health/*`` and ``ModelReady`` stop reporting a hung device as
  ready), fails deadline-expired requests (handles only — resource
  cleanup stays with the loop thread), and marks the step stale so the
  supervisor discards its late result and restarts when (if) the device
  call finally returns.

Failure classes (the README's failure-semantics table):

  transient device error   -> RetryPolicy retry, invisible
  hard step crash, once    -> supervisor step retry, invisible
  reproducible + isolable  -> quarantine (fails alone, original error)
  NaN logits, some slots   -> quarantine with PoisonedRequestError
  NaN logits, all slots    -> engine restart + journal replay
  crash, not isolable      -> engine restart + journal replay
  stalled step             -> watchdog trip -> restart + journal replay
  restart budget exhausted -> EngineFailedError + breaker OPEN; queued
                              requests are HELD (never failed with the
                              engine's internal error) and admitted
                              again if the breaker's half-open probe
                              succeeds after recovery_s. In fleet mode
                              (scheduler.failover_sink set by
                              serving/fleet.py) nothing is failed at
                              all: every live stream leaves this
                              scheduler and journal-replays onto a
                              surviving replica byte-exactly.

Chaos sites: ``generation.journal_replay`` fires at the top of every
restart, so tests can inject a *double fault* (a crash during recovery)
and watch it consume a second budget unit. All clocks and sleeps are
injectable; tests drive the watchdog with manual ``check()`` calls on a
virtual clock.
"""
from __future__ import annotations

import dataclasses
import random
import threading
import time
from typing import TYPE_CHECKING, Callable, List, Optional, Sequence

from ..runtime import faults
from ..runtime.backoff import backoff_delay
from ..serving.resilience import DeadlineExceededError

if TYPE_CHECKING:  # import cycle: scheduler imports this module
    from .scheduler import ContinuousBatchingScheduler, Request, _Running


class EngineFailedError(RuntimeError):
    """The generation engine is permanently gone (restart budget
    exhausted) — the typed error truly-lost requests receive instead of
    the engine's raw internal traceback. Raised for streams that have
    already emitted tokens: slot-resident ones and replay-requeued
    mid-stream ones (a blind resubmit could duplicate output). FRESH
    queued requests are HELD rather than failed and stay safe to
    resubmit by construction."""


class PoisonedRequestError(RuntimeError):
    """Structured quarantine error: THIS request's data produced
    non-finite logits and it was failed alone so the rest of the batch
    could keep generating. (A request quarantined by CRASH bisection is
    failed with the original device exception instead — the cause is
    more useful to its client than a wrapper.)"""

    def __init__(self, message: str, *, request_id: int, step: str, reason: str):
        super().__init__(message)
        self.request_id = request_id
        self.step = step  # "prefill" | "decode" | "verify"
        self.reason = reason  # "nan_logits"


class StalledStepError(RuntimeError):
    """A device step exceeded the watchdog's stall timeout; its (late)
    result was discarded and the engine restarted."""


@dataclasses.dataclass(frozen=True)
class RecoveryPolicy:
    """Supervisor tuning. ``max_restarts`` engine restarts are allowed
    per sliding ``budget_window_s`` (scheduler clock); each restart
    backs off exponentially with seeded jitter (runtime/backoff.py, the
    same curve as ElasticTrainer and RetryPolicy)."""

    max_restarts: int = 4
    budget_window_s: float = 300.0
    retry_step_once: bool = True
    backoff_base_s: float = 0.02
    backoff_max_s: float = 1.0
    backoff_jitter: float = 0.25
    seed: int = 0
    sleep: Callable[[float], None] = time.sleep


@dataclasses.dataclass(frozen=True)
class WatchdogPolicy:
    """Step-watchdog tuning. ``stall_timeout_s`` is measured on the
    scheduler's clock (virtual in tests); ``poll_s`` is the real-time
    cadence of the background thread started by ``scheduler.start()``."""

    enabled: bool = True
    stall_timeout_s: float = 30.0
    poll_s: float = 0.5


class JournalEntry:
    """One replayable stream: the request object itself carries the
    full replay state (original prompt, every emitted token, the seeded
    sampling key stream, speculation config + adaptive-k EMA)."""

    __slots__ = ("req", "admitted_seq")

    def __init__(self, req: "Request", admitted_seq: int):
        self.req = req
        self.admitted_seq = admitted_seq


class GenerationJournal:
    """Replay log of every slot-resident stream, keyed by request id.

    The journal deliberately holds no device state: replay is
    recompute-prefill of ``original_prompt + generated`` (the preempt
    path), which the per-token-count sampling keys make byte-exact.
    """

    def __init__(self):
        self._lock = threading.Lock()
        self._entries: dict = {}

    def __len__(self) -> int:
        with self._lock:
            return len(self._entries)

    def record(self, req: "Request", admitted_seq: int) -> None:
        with self._lock:
            self._entries[req.id] = JournalEntry(req, admitted_seq)

    def discard(self, req: "Request") -> None:
        with self._lock:
            self._entries.pop(req.id, None)

    def entries(self) -> List[JournalEntry]:
        """Live entries in admission order (FCFS replay order)."""
        with self._lock:
            return sorted(self._entries.values(), key=lambda e: e.admitted_seq)

    def drain(self) -> List[JournalEntry]:
        with self._lock:
            out = sorted(self._entries.values(), key=lambda e: e.admitted_seq)
            self._entries.clear()
            return out

    # Durable-serving hooks (ISSUE 19): no-ops here, overridden by
    # serving.durable.DurableJournal to mirror the journal into a
    # crash-safe WAL. The scheduler calls note_token from every
    # emitted-token bookkeeping path and flush_step once per scheduling
    # iteration (the group-commit boundary) — keeping both on the base
    # class means the scheduler never imports the serving tier.
    def note_token(self, req: "Request", token: int) -> None:
        pass

    def flush_step(self) -> None:
        pass


class EngineSupervisor:
    """Catches engine-loop step failures and turns them into the
    narrowest possible outcome: absorbed retry > quarantine > engine
    restart + journal replay > declared engine death."""

    def __init__(
        self,
        scheduler: "ContinuousBatchingScheduler",
        policy: Optional[RecoveryPolicy] = None,
    ):
        self.scheduler = scheduler
        self.policy = policy or RecoveryPolicy()
        self.stats = scheduler.recovery_stats
        self._rng = random.Random(f"supervisor|{self.policy.seed}")
        self._restart_times: List[float] = []
        self._consecutive = 0  # restarts since the last healthy step
        self._stall_lock = threading.Lock()
        # heartbeat seq the watchdog tripped on; the overlap pipeline's
        # consume arbitration (scheduler._consume_and_finish) and the
        # sequential run_step/resume_step ladders both pop it via
        # _consume_stall  # guarded-by: _stall_lock
        self._stalled_seq: Optional[int] = None  # guarded-by: _stall_lock
        self.failed = False  # restart budget exhausted; engine declared dead

    def note_engine_recovered(self) -> None:
        """A half-open probe succeeded against a declared-dead engine:
        service resumed, so the spent restart budget is forgiven — the
        next engine-level failure gets a full budget instead of an
        instant give-up inside the stale window."""
        self.failed = False
        self._restart_times.clear()
        self._consecutive = 0

    # ------------------------------------------------------------ watchdog
    def mark_stalled(self, seq: int) -> None:
        """Watchdog: the device call with heartbeat ``seq`` is stale;
        its result must be discarded."""
        with self._stall_lock:
            self._stalled_seq = max(self._stalled_seq or 0, seq)

    def _consume_stall(self, since_seq: int) -> bool:
        """True only when the flagged stall belongs to a device call
        issued after ``since_seq`` — i.e. one of the caller's own calls.
        A trip on some OTHER stamped section (an admission prefill's
        cold compile, a bisection probe, the recovery path itself) must
        not condemn a later healthy step: its result was already
        committed, the breaker is open either way, and a genuinely
        wedged device will re-trip on its next supervised step — while
        discarding healthy steps for it would burn restart budget on,
        say, a compile that merely exceeded the stall timeout."""
        with self._stall_lock:
            seq, self._stalled_seq = self._stalled_seq, None
            return seq is not None and seq > since_seq

    # ---------------------------------------------------------------- step
    def run_step(self, kind: str, step_fn, states: Sequence["_Running"], probe):
        """Run one batched device step under supervision.

        Returns the step's output, or None when the failure was fully
        handled here (quarantine or journal replay) — the scheduler must
        then skip its scatter phase; surviving streams either kept their
        slots or sit requeued for recompute-replay.
        """
        sched = self.scheduler
        seq0 = sched._hb_seq  # stalls flagged past this are OUR calls
        try:
            out = sched._device(step_fn)
        except Exception as e:
            # flight recorder: the failing step is now ON the ring, so
            # every downstream incident snapshot contains it
            sched.flight.record_event("step_failed", step=kind, error=repr(e)[:200])
            if self._consume_stall(seq0):
                self._restart_and_replay(e, kind)
                return None
            if getattr(sched.engine, "donate", False):
                # a donating engine's failed jit call consumed its cache
                # input buffers: retrying the same closure (and every
                # bisection probe) would re-pass deleted arrays and blame
                # innocent requests — reset + journal replay is the only
                # sound recovery (byte-exact; documented with donate_cache)
                self._restart_and_replay(e, kind)
                return None
            if not self.policy.retry_step_once:
                self._handle_double_failure(e, kind, states, probe)
                return None
            self.stats.incr("step_retries")
            sched.flight.record_event("step_retry", step=kind)
            try:
                out = sched._device(step_fn)
            except Exception as e2:
                sched.flight.record_event("step_failed", step=kind, error=repr(e2)[:200])
                if self._consume_stall(seq0):
                    self._restart_and_replay(e2, kind)
                    return None
                self._handle_double_failure(e2, kind, states, probe)
                return None
        if self._consume_stall(seq0):
            # the watchdog already tripped the breaker and reaped
            # deadline-expired handles; the step's late result is stale
            # (the engine may have wedged mid-write), so replay instead
            self._restart_and_replay(
                StalledStepError(f"{kind} step exceeded the watchdog stall timeout"),
                kind,
            )
            return None
        self._consecutive = 0  # healthy step: backoff curve restarts
        return out

    def resume_step(self, kind, first_error, step_fn, states, probe, since_seq):
        """A PIPELINED (async-dispatched) step failed. Resume the
        sequential supervision ladder from the point just after a
        synchronous step's first failure, so the outcome AND the
        accounting match ``run_step`` exactly: a retryable error is
        re-run invisibly (the treatment RetryPolicy.run would have
        given it inside the same ``_device`` call), a hard error pays
        one breaker failure, then the supervised retry -> bisect ->
        restart ladder. ``since_seq`` scopes stall flags to the failed
        chain's own device calls (the overlap frontier's ``seq0``)."""
        sched = self.scheduler
        if sched.retry.would_retry(first_error):
            return self.run_step(kind, step_fn, states, probe)
        sched.flight.record_event(
            "step_failed", step=kind, error=repr(first_error)[:200]
        )
        sched.breaker.record_failure()
        if self._consume_stall(since_seq):
            self._restart_and_replay(first_error, kind)
            return None
        if getattr(sched.engine, "donate", False):
            # unreachable from _pipeline_failure (it checks donate first)
            # but kept for any future caller: see run_step
            self._restart_and_replay(first_error, kind)
            return None
        if not self.policy.retry_step_once:
            self._handle_double_failure(first_error, kind, states, probe)
            return None
        self.stats.incr("step_retries")
        sched.flight.record_event("step_retry", step=kind)
        try:
            out = sched._device(step_fn)
        except Exception as e2:
            sched.flight.record_event("step_failed", step=kind, error=repr(e2)[:200])
            if self._consume_stall(since_seq):
                self._restart_and_replay(e2, kind)
                return None
            self._handle_double_failure(e2, kind, states, probe)
            return None
        if self._consume_stall(since_seq):
            self._restart_and_replay(
                StalledStepError(f"{kind} step exceeded the watchdog stall timeout"),
                kind,
            )
            return None
        self._consecutive = 0
        return out

    def _handle_double_failure(self, err, kind, states, probe) -> None:
        """The step failed twice. Bisect with subset probes to decide
        data-dependence: a strict subset that reproducibly fails alone
        is quarantined (batch-of-one keeps PR 1's fail-the-request
        semantics — with one request there is nothing to bisect
        against); anything else is an engine-level fault."""
        blamed = self._bisect(list(states), probe)
        if blamed and (len(blamed) < len(states) or len(states) == 1):
            for s in blamed:
                self.scheduler._quarantine(s, err)
            return
        self._restart_and_replay(err, kind)

    def _bisect(self, states, probe) -> List["_Running"]:
        """Probe subsets of the failed batch (outputs discarded; cache
        writes are idempotent replays of the same step) to isolate
        requests that crash on their own. Probes bypass retry/breaker:
        an expected crash during blame assignment is not device health
        signal."""

        def failing(sub) -> bool:
            try:
                probe(sub)
            except Exception:
                return True
            return False

        def rec(sub):
            if not failing(sub):
                return []
            if len(sub) == 1:
                return list(sub)
            mid = len(sub) // 2
            return rec(sub[:mid]) + rec(sub[mid:])

        return rec(list(states))

    # ------------------------------------------------------------- restart
    def handle_engine_nan(self, kind: str) -> None:
        """Every live slot's logits went non-finite at once: nothing to
        pin on one request (bad params / numeric collapse / device
        fault), so tear down and replay — the cache rezero also clears
        any NaN the batch wrote."""
        self._restart_and_replay(
            RuntimeError(f"non-finite logits across all slots at {kind} step"), kind
        )

    def _restart_and_replay(self, cause: BaseException, kind: str) -> None:
        """Tear the engine down and rebuild every journaled stream by
        recompute-replay, with backoff and a sliding restart budget. A
        failure *during* recovery (the journal_replay chaos site, or a
        still-broken device) is a double fault: it consumes another
        budget unit and backs off further."""
        sched = self.scheduler
        pol = self.policy
        # the overlap frontier's in-flight step (if any) is chained on
        # state this reset is about to tear down: discard it before
        # touching the engine (idempotent; pipeline callers already did)
        sched._discard_frontier()
        # postmortem FIRST: the snapshot must show the engine's last
        # steps (including the step_failed marker) before reset rebuilds
        # the world; attached to the cause so a later give-up's
        # EngineFailedError still carries the first crash's context
        snap = sched.flight.incident(
            "restart", step=kind, error=repr(cause)[:200],
            journal_entries=len(sched.journal),
        )
        if getattr(cause, "flight_snapshot", None) is None:
            try:
                cause.flight_snapshot = snap
            except Exception:
                pass
        while True:
            now = sched.clock()
            self._restart_times = [
                t for t in self._restart_times if now - t <= pol.budget_window_s
            ]
            if len(self._restart_times) >= pol.max_restarts:
                self._give_up(cause)
                return
            self._restart_times.append(now)
            self._consecutive += 1
            pol.sleep(
                backoff_delay(
                    self._consecutive,
                    base_s=pol.backoff_base_s,
                    max_s=pol.backoff_max_s,
                    jitter=pol.backoff_jitter,
                    rng=self._rng,
                )
            )
            try:
                # stamped: a reset that wedges on a dead device must stay
                # visible to the watchdog (deadline reaping keeps running
                # and a fresh trip is flagged for this section's seq)
                with sched._stamped():
                    faults.inject(faults.GENERATION_JOURNAL_REPLAY, sched.journal.entries())
                    sched.engine.reset()
                    sched._rebuild_from_journal()
            except Exception as e:  # double fault: burn another budget unit
                cause = e
                sched.flight.record_event("double_fault", error=repr(e)[:200])
                continue
            self.stats.incr("recoveries")
            sched.flight.record_event(
                "recovery", step=kind, consecutive=self._consecutive
            )
            # recovery proved the device responsive; close the breaker a
            # watchdog trip (or the crash's recorded failures) opened so
            # admission resumes immediately instead of after recovery_s
            sched.breaker.record_success()
            return

    def _give_up(self, cause: BaseException) -> None:
        self.failed = True
        self.stats.incr("engine_failures")
        err = EngineFailedError(
            f"generation engine failed permanently: {self.policy.max_restarts} "
            f"restarts exhausted within {self.policy.budget_window_s}s "
            f"(last cause: {cause!r})"
        )
        err.__cause__ = cause
        err.flight_snapshot = self.scheduler.flight.incident(
            "engine_failed", error=repr(cause)[:200]
        )
        self.scheduler._fail_running_engine_dead(err)
        # queued-but-never-admitted requests are NOT failed: they hold no
        # slot and streamed nothing, so they wait out the outage behind
        # the breaker (admitted by its half-open probe if the device
        # comes back) or expire at their own deadlines
        self.scheduler.breaker.trip()


class StepWatchdog:
    """Detects device steps that neither return nor raise.

    The scheduler stamps ``_heartbeat = (seq, started_at)`` around every
    device call; ``check()`` compares its age against the stall timeout
    on the scheduler's clock. Tripping is per-step (one trip per seq):
    it opens the circuit breaker, marks the supervisor so the step's
    late result is discarded in favor of a journal-replay restart, and
    fails deadline-expired requests' *handles* (slots/blocks stay with
    the loop thread — the only thread allowed to touch them).

    Overlap pipeline stamping (ISSUE 13): an async-dispatched step
    stamps its heartbeat at DISPATCH and is re-stamped when its
    predecessor COMPLETES — the moment it actually starts executing on
    the serial device queue — so each step's heartbeat age measures its
    OWN device time. Without the completion re-stamp, a one-step-deep
    pipeline at long execute times would accumulate dispatch-to-consume
    windows spanning two steps and be misread as a wedged loop
    (regression-tested on a virtual clock in tests/test_overlap.py).
    A consume that never returns leaves the stamp aging until the trip
    fires, exactly like a wedged synchronous call."""

    def __init__(
        self,
        scheduler: "ContinuousBatchingScheduler",
        policy: Optional[WatchdogPolicy] = None,
    ):
        self.scheduler = scheduler
        self.policy = policy or WatchdogPolicy()
        self.stats = scheduler.recovery_stats
        self._last_tripped_seq = -1
        self._thread: Optional[threading.Thread] = None
        self._stop = threading.Event()

    # ------------------------------------------------------------- checks
    def check(self) -> bool:
        """One inspection (tests call this directly on virtual clocks).
        Returns True if a stall was newly detected."""
        sched = self.scheduler
        hb = sched._heartbeat  # (seq, started_at) or None; atomic read
        if hb is None:
            return False
        seq, started = hb
        if sched.clock() - started < self.policy.stall_timeout_s:
            return False
        tripped = seq != self._last_tripped_seq
        if tripped:
            self._last_tripped_seq = seq
            self.stats.incr("watchdog_trips")
            sched.flight.record_event(
                "watchdog_trip", heartbeat_seq=seq,
                stalled_s=sched.clock() - started,
            )
            sched.breaker.trip()  # health stops lying about a hung device
            sched.supervisor.mark_stalled(seq)
        # while the device is wedged the loop thread cannot expire
        # anything, so deadline enforcement moves here (handles only)
        self._reap_expired()
        return tripped

    def _reap_expired(self) -> None:
        sched = self.scheduler
        now = sched.clock()
        with sched._lock:
            queued = list(sched._queue)
        # _running is loop-thread-private; this snapshot is a single
        # C-level copy (GIL-atomic), safe even if the wedged step
        # returns and the loop resumes mutating at this exact moment
        running = [s.req for s in list(sched._running.values())]
        admitting = sched._admitting  # popped for a (possibly wedged) prefill
        for req in queued + running + ([admitting] if admitting else []):
            if (
                req.deadline is not None
                and now >= req.deadline
                and req.handle._fail(
                    DeadlineExceededError("deadline expired during a stalled engine step")
                )
            ):
                sched.stats.incr("expired")

    # ------------------------------------------------------------ lifecycle
    def start(self) -> None:
        if not self.policy.enabled or self._thread is not None:
            return
        self._stop.clear()
        self._thread = threading.Thread(target=self._loop, daemon=True)
        self._thread.start()

    def stop(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=5.0)
            self._thread = None

    def _loop(self) -> None:
        while not self._stop.wait(timeout=self.policy.poll_s):
            try:
                self.check()
            except Exception:
                # the watchdog must never die of a transient inspection
                # race; missing one poll is strictly better than losing
                # stall detection for the process lifetime
                pass
