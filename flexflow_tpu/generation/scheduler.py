"""Continuous batching: iteration-level scheduling of generation
requests (Orca, OSDI'22) over the block KV cache.

Unlike the request-level DynamicBatcher (serving/batcher.py), which
holds a batch's composition fixed for a whole device call, generation
is scheduled per *iteration*: every ``step()`` runs ONE decode across
the engine's fixed batch slots, and between steps the batch recomposes
freely —

* **join-mid-flight**: a queued request is admitted (FCFS) the moment a
  slot AND enough cache blocks are free; it prefils and decodes
  alongside sequences that are hundreds of tokens in;
* **free-on-finish**: a sequence hitting EOS / max-tokens / its
  deadline releases its blocks in the same step, so capacity returns
  immediately instead of at batch boundaries;
* **preempt-by-recompute**: if the cache cannot grow a running
  sequence, the youngest running sequence is evicted — blocks freed,
  prompt + generated-so-far re-queued at the FRONT — and later
  re-prefilled (vLLM's recompute preemption). Seeded sampling keys are
  indexed by generated-token count, so a preempted request's token
  stream continues exactly where it left off.

* **speculation-aware stepping**: a request submitted with a
  SpeculationConfig drafts up to k tokens per iteration (n-gram or
  draft-model drafter) and the step verifies every slot's window in ONE
  fixed-shape engine.verify call — up to k+1 tokens emitted per
  sequence per step, exactly (greedy output is token-for-token the
  non-speculative stream). The scheduler allocates blocks for the whole
  window up front, caps a window's k when the allocator is tight
  (before ever preempting), trims unused trailing blocks after partial
  acceptance, truncates emission at mid-window EOS / budget, and adapts
  each request's k against its acceptance EMA. Speculative and plain
  requests mix freely in one batch (a plain request is a zero-draft
  window whose sampling is bit-identical to the decode step).

Resilience mirrors PR 1's serving semantics: bounded queue
(QueueFullError), per-request deadlines (DeadlineExceededError before
OR during generation), retry-with-backoff for TransientDeviceError,
and a circuit breaker around device steps — all on an injectable clock
so chaos tests run on virtual time. Fault sites: ``generation.prefill``,
``generation.decode_step``, ``generation.verify``, and
``generation.journal_replay`` (runtime/faults.py).

* **self-healing** (recovery.py): every admitted stream is entered in a
  :class:`GenerationJournal`; batched device steps run under an
  :class:`EngineSupervisor` that absorbs one-off crashes (single step
  retry), quarantines poisoned requests (per-slot NaN blame vector from
  the jitted steps, or crash bisection with subset probes) so one bad
  request can no longer fail the whole batch, and recovers engine-level
  failures by ``engine.reset()`` + journal replay over the
  preempt-by-recompute path — byte-exact, because sampling keys index
  by generated-token count. A :class:`StepWatchdog` heartbeat around
  device calls detects stalled steps, trips the breaker (honest
  health), and drives the same restart. An exhausted restart budget
  fails *running* streams with a typed EngineFailedError; queued
  requests are held behind the breaker, never failed with the engine's
  internal error.

* **overlapped decode** (ISSUE 13, on by default; ``overlap=False``
  restores the sequential loop bit-for-bit): steady-state decode runs
  as a two-deep software pipeline — step N+1's fixed-shape jit is
  dispatched (sampled tokens carried device-resident from step N's
  output) while step N's device work completes, token readback is
  double-buffered, and host bookkeeping runs inside N+1's execute
  window. An in-flight *frontier* of at most one outstanding step
  drains deterministically on every non-steady event (admission,
  EOS/finish, preemption pressure, cancel/deadline, speculation,
  crash, watchdog trip, shutdown), so supervisor bisection, NaN blame,
  journal replay, and fleet failover observe exactly the sequential
  semantics — token streams are byte-identical with overlap on/off
  (tests/test_overlap.py). The speculative verify path stays
  sequential by design: drafting needs step N's committed tokens on
  the host, so there is no overlap window. A BLOCK-DIFFUSION engine's
  block step rides the same loop (ISSUE 46; an engine has one kind of
  step, read once from ``engine.diffusion``): forward N+1 goes out on
  the state forward N's program left on the device while the host
  emits what N-1 fixed; what the host must know for the dispatch (is N
  a slot's commit, so where N+1's block starts and whether a spent
  budget ends the request there) it knows one consume earlier, and
  what N fixed it needs only to emit tokens. An EMPTY FREE LIST is not
  pressure (ISSUE 30): with the prefix cache on the pool is full by
  design, so the pipeline's block growth takes its block from the
  cache's unreferenced entries (``engine.reclaim_cached``, as ``_grow``
  does) with the step in flight — no running stream's table names a
  victim. What drains for ``pressure`` is a pool with nothing left to
  evict: the next resorts (capping speculation, preempt-by-recompute)
  mutate running slots and stay sequential. ``/v2/stats`` section
  ``pipeline`` counts the loop's decisions.

The scheduler is synchronous-by-design: ``step()`` does one iteration
and returns, so property tests drive it deterministically; ``start()``
wraps it in a background thread for serving.
"""
from __future__ import annotations

import contextlib
import dataclasses
import itertools
import queue
import threading
import time
from collections import deque
from concurrent.futures import Future
from typing import Callable, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..obs import (
    NULL_JOURNEY,
    NULL_TRACE,
    CacheTelemetry,
    FlightRecorder,
    JourneyContext,
    JourneyRecorder,
    JourneyStats,
    RequestTrace,
    SLOMonitor,
    StepAnatomy,
    TraceRing,
    next_request_id,
)
from ..obs.steptrace import DEVICE_PHASES, GLOBAL_STARTUP, phase
from ..runtime import faults
from ..serving.overload import OverloadConfig, OverloadController, Priority
from ..serving.resilience import (
    CircuitBreaker,
    CircuitOpenError,
    DeadlineExceededError,
    QueueFullError,
    RetryPolicy,
    ShuttingDownError,
)
from ..serving.stats import (
    ConstrainedStats,
    GoodputStats,
    RecoveryStats,
    ServingStats,
    SpeculationStats,
    TokenRate,
)
from .constrained.errors import MaskDeadEndError
from .engine import GenerationEngine, SamplingParams
from .recovery import (
    EngineFailedError,
    EngineSupervisor,
    GenerationJournal,
    PoisonedRequestError,
    RecoveryPolicy,
    StalledStepError,
    StepWatchdog,
    WatchdogPolicy,
)
from .speculative.drafter import SpeculationConfig, build_drafter

_END = object()  # token-stream sentinel

# the engine's host spans: one "device" total in a flight record
_ENGINE_PHASES = frozenset({"dispatch", "block", "readback", "account"})


def _flight_phases(spans, walls: Dict[str, float]) -> Dict[str, float]:
    """A flight record's phase durations: the iteration's host spans
    summed under their names, and ``walls``, what the ring carries that
    is no anatomy span — ``device``, the wall of the supervised device
    step (failed attempts, retries and bisection included, which is
    what an incident needs), and the pipeline's ``dispatch``. The
    engine's own dispatch / block / readback / account sit inside ``device``; the
    device-lane execute span is the record's ``execute_s``."""
    out = dict(walls)
    for name, t0, t1 in spans:
        if name in DEVICE_PHASES or name in _ENGINE_PHASES:
            continue
        out[name] = out.get(name, 0.0) + (t1 - t0)
    return out


class GenerationHandle:
    """Caller's view of one request: a Future of the generated token
    list plus a per-token stream."""

    def __init__(self, request: "Request"):
        self._request = request
        self.future: Future = Future()
        # a SimpleQueue: its put and its get are C calls, where
        # queue.Queue's are ~40 lines of Python under two locks, paid
        # by the scheduler's thread a token and by the stream's reader
        # with the interpreter's lock held
        self._tokens: "queue.SimpleQueue" = queue.SimpleQueue()
        # tokens the scheduler has bookkept and not yet put on the queue
        # (_emit_later): they go out when its thread next parks for the
        # device, and before this handle's end or error whoever settles
        # it, so a reader has them in order and whole
        self._held: List[int] = []
        # settle arbitration: the loop and watchdog threads race to
        # finish/fail a handle; the claim winner owns BOTH the future
        # and the trace, and closes the trace BEFORE the future settles
        # so a client woken by the future never reads a half-open trace
        self._settle_lock = threading.Lock()
        self._settled = False

    def _claim(self) -> bool:
        with self._settle_lock:
            if self._settled or self.future.done():
                return False
            self._settled = True
            return True

    # ----------------------------------------------------------- caller
    def done(self) -> bool:
        return self.future.done()

    def result(self, timeout: Optional[float] = None) -> List[int]:
        return self.future.result(timeout=timeout)

    def cancel(self) -> None:
        """Ask the scheduler to drop this request at its next step."""
        self._request.cancelled = True

    @property
    def trace(self):
        """The request's RequestTrace (NULL_TRACE when observability is
        off) — transports read it to embed postmortems in error
        responses and annotate the transport kind."""
        return self._request.trace

    def trace_dict(self) -> dict:
        return self._request.trace.to_dict()

    def tokens(self, timeout: Optional[float] = None):
        """Iterate generated tokens as they are produced. Raises the
        request's failure if it errors mid-stream."""
        while True:
            item = self._tokens.get(timeout=timeout)
            if item is _END:
                return
            if isinstance(item, BaseException):
                raise item
            yield item

    # -------------------------------------------------------- scheduler
    def _emit(self, token: int) -> None:
        self._release_held()
        self._tokens.put(token)

    def _emit_later(self, token: int) -> None:
        """Keep ``token`` for :meth:`_release_held`. A put wakes the
        stream's reader, and a woken thread takes the interpreter's lock
        the first time the scheduler's thread gives it up: the scheduler
        says when that may be. Scheduler thread only."""
        with self._settle_lock:
            self._held.append(token)

    def _release_held(self) -> int:
        """Put the held tokens on the queue; how many there were. Under
        the settle lock: the scheduler's thread and a settling one (the
        watchdog failing a deadline) put no token twice or out of order."""
        if not self._held:
            return 0
        with self._settle_lock:
            held, self._held = self._held, []
            for token in held:
                self._tokens.put(token)
        return len(held)

    def _finish(self, tokens: List[int]) -> None:
        # idempotent under races: the watchdog thread may reap a
        # deadline while the loop thread is deciding the same request's
        # fate — the loser of the claim must not propagate
        # InvalidStateError into (and kill) the loop
        if not self._claim():
            return
        # trace first: a client thread woken by the settling future may
        # immediately read trace_dict() for its response
        self._request._trace_done("completed", None)
        self._release_held()
        try:
            self.future.set_result(tokens)
        except Exception:
            return
        self._tokens.put(_END)

    def _fail(self, err: BaseException) -> bool:
        """Returns True only if THIS call failed the handle — losers of
        the loop/watchdog race must not double-count in stats."""
        if not self._claim():
            return False
        # the claim winner also closes the trace (BEFORE the future
        # settles), so every terminal path — loop, watchdog reap,
        # shutdown — lands exactly one finished trace in the ring and
        # error responses never embed a half-open trace
        self._request._trace_done(type(err).__name__, err)
        self._release_held()  # what was bookkept before the failure is the stream's
        try:
            self.future.set_exception(err)
        except Exception:
            return False
        self._tokens.put(err)
        self._tokens.put(_END)
        return True


class Request:
    """One generation request. ``prompt`` may grow on preemption (the
    generated prefix is folded in for recompute); ``n_generated`` is the
    TOTAL generated count across preemptions, which also indexes the
    per-request sampling key stream. Ids come from the process-wide
    obs counter so a trace id names exactly one request across every
    serving path (sampling never mixes the id in — determinism is
    seed-only)."""

    def __init__(
        self,
        prompt: List[int],
        sampling: SamplingParams,
        deadline: Optional[float] = None,
        speculation: Optional[SpeculationConfig] = None,
        drafter=None,
        priority: str = Priority.STANDARD,
        grammar=None,
        response_format: Optional[Dict] = None,
    ):
        self.id = next_request_id()
        # overload control (serving/overload.py): the priority class
        # orders admission, preemption victims, and shed order; the
        # release hook returns this request's AdaptiveLimiter slot on
        # terminal settle (set at submit, fired exactly once by the
        # handle's settle-race winner)
        self.priority = priority
        self.priority_rank = Priority.rank(priority)
        self.overload_release: Optional[Callable[[], None]] = None
        # observability: the scheduler swaps in a live RequestTrace (+
        # destination ring) at submit when tracing is enabled
        self.trace = NULL_TRACE
        self.trace_ring = None
        # fleet-wide journey (ISSUE 20): the cross-replica trace context
        # travels ON the request, exactly like the trace — minted (or
        # joined from a remote traceparent) at submit, retargeted at the
        # adopting scheduler on failover/handoff, restored from the WAL
        # admission snapshot on warm restart
        self.journey = NULL_JOURNEY
        self.original_prompt = list(prompt)
        self.prompt = list(prompt)  # prompt + recomputed prefix
        self.sampling = sampling
        self.deadline = deadline  # absolute, scheduler clock
        self.submitted_at = 0.0  # stamped by the scheduler
        # effective budget, possibly clamped to the cache room the
        # scheduler can actually give this sequence
        self.max_new = sampling.max_new_tokens
        self.generated: List[int] = []  # tokens generated so far (total)
        # block diffusion: for every emitted token, the index within its
        # block of the denoising forward that fixed it (the trace and the
        # non-streamed response carry it); and, across a preemption, the
        # block that was in flight (base, _Block): its rows that were
        # fixed out of order stay fixed, so the resumed stream is the
        # unpreempted one
        self.fixed_at: List[int] = []
        self.block_resume = None
        self.cancelled = False
        self.preemptions = 0
        self.replays = 0  # journal-replay recoveries this stream rode out
        self.handle = GenerationHandle(self)
        # seed-only (no request-id mixing): the same seed + prompt +
        # params must reproduce the same tokens, run to run (with
        # temperature speculation: under the same window layout — see
        # speculative/sampling.py on realization-invariance). Folded as
        # 32 bits to match the decode/verify jits' in-jit derivation
        # (engine.derive_keys): prefill and decode MUST agree or a
        # preemption-recompute would fork the stream for seeds outside
        # [0, 2**32); in-range seeds are unchanged.
        self.base_key = jax.random.key(sampling.seed & 0xFFFFFFFF)
        # speculation state: live k adapts inside [1, config.k]; the
        # drafter is a pure function of the prefix, so preemption needs
        # no drafter checkpointing
        self.speculation = speculation if (speculation and speculation.enabled) else None
        self.drafter = drafter if self.speculation else None
        self.spec_k = speculation.k if self.speculation else 0
        self.acc_ema: Optional[float] = None
        self.spec_proposed = 0
        self.spec_accepted = 0
        # capacity observability: admission-wait blame (set while the
        # FCFS head is blocked on cache blocks) and the terminal
        # SLO/goodput sink (set by the scheduler when tracing is on)
        self.cache_wait_start: Optional[float] = None
        self.cache_wait_short = 0
        self.slo_sink = None
        # disaggregated serving: a KVHandoffPayload attached by
        # adopt(imported=...) — the decode-side admission imports these
        # blocks instead of recompute-prefilling; cleared on use (or on
        # rejection, which falls back to recompute)
        self.imported_kv = None
        # constrained decoding (ISSUE 18): the compiled TokenDFA shared
        # across requests under the same grammar, and the per-request
        # automaton cursor. mask_state is rebuilt at admission by
        # re-advancing over `generated` (the journal-replay discipline:
        # preempt-recompute, restart, and failover all reconstruct the
        # same state from the same tokens), so preemption/adopt just
        # drop it. mask_error is a deferred PoisonedRequestError the
        # step loop sweeps into a per-request quarantine — advance
        # failures deep in emit paths must fail ONE stream, not the
        # batch.
        self.grammar = grammar
        self.response_format = response_format
        self.mask_state = None
        self.mask_error: Optional[PoisonedRequestError] = None
        # durable serving (ISSUE 19): the stream's identity in the WAL
        # and on GET /v2/generate/resume/{id} — stable across process
        # restarts (a warm restart pins the journaled id onto the
        # re-admitted request, while self.id is process-local). Set by
        # the DurableJournal at first admission; None when the stream
        # is not durably journaled.
        self.durable_id: Optional[str] = None

    @property
    def n_generated(self) -> int:
        return len(self.generated)

    def _trace_done(self, outcome: str, err: Optional[BaseException]) -> None:
        """Terminal trace hook, called by the handle's settle-race
        winner (exactly once per request)."""
        # limiter slot back first (claim-protected, so exactly once),
        # and unconditionally — observability off must not leak slots
        release, self.overload_release = self.overload_release, None
        if release is not None:
            try:
                release()
            except Exception:
                pass  # limiter accounting must never poison a settle path
        if self.trace is not NULL_TRACE:
            self.trace.mark_finish(outcome, err)
            if self.trace_ring is not None:
                self.trace_ring.add(self.trace)
            if self.slo_sink is not None:
                try:
                    self.slo_sink(self)
                except Exception:
                    pass  # SLO accounting must never poison a settle path
        if self.journey is not NULL_JOURNEY:
            # terminal hop: the span carries the full RequestTrace
            # decomposition + event log, so the stitched journey holds
            # the per-replica story without a second lookup. Recorded
            # even when the trace is NULL (a warm-restored stream has a
            # journey but no trace) — the journey must still end.
            try:
                tr = {} if self.trace is NULL_TRACE else self.trace.to_dict()
                self.journey.hop(
                    "finish", outcome=outcome,
                    n_generated=len(self.generated),
                    queue_time_s=tr.get("queue_time_s"),
                    ttft_s=tr.get("ttft_s"), tpot_s=tr.get("tpot_s"),
                    total_s=tr.get("total_s"),
                    preemptions=tr.get("preemptions"),
                    replays=tr.get("replays"),
                    error=None if err is None else str(err),
                    trace_events=tr.get("events"),
                )
            except Exception:
                pass  # journeys must never poison a settle path

    def sample_key(self) -> jax.Array:
        """Key for the NEXT token: indexed by generated count, so a
        recomputed request continues its exact sampling stream. Used by
        the (admission-time) prefill only — the hot decode/verify steps
        derive the same keys IN-JIT from (seed, count) via
        engine.derive_keys, deleting the host key-assembly phase."""
        return jax.random.fold_in(self.base_key, self.n_generated)

    def update_speculation(self, proposed: int, accepted: int) -> None:
        """Fold one verification window into the adaptive-k state."""
        self.spec_proposed += proposed
        self.spec_accepted += accepted
        cfg = self.speculation
        if cfg is None or proposed <= 0:
            return
        rate = accepted / proposed
        self.acc_ema = (
            rate
            if self.acc_ema is None
            else cfg.ema_alpha * rate + (1.0 - cfg.ema_alpha) * self.acc_ema
        )
        if not cfg.adaptive:
            return
        if self.acc_ema < cfg.low_acceptance:
            self.spec_k = max(1, self.spec_k - 1)
        elif self.acc_ema >= cfg.high_acceptance:
            self.spec_k = min(cfg.k, self.spec_k + 1)

    def finished(self) -> bool:
        return self.n_generated >= self.max_new or self.ended_on_eos()

    def ended_on_eos(self) -> bool:
        """The end of a request that needs no further step under block
        diffusion, where a spent budget still awaits its block's commit."""
        eos = self.sampling.eos_id
        return eos is not None and bool(self.generated) and self.generated[-1] == eos


class _Block:
    """A running sequence's block in flight (block diffusion): the
    block's tokens, which rows are fixed, the denoising forwards it has
    had, and for every row the forward that fixed it (-1: a row that
    entered fixed, from the prompt or a replayed reply)."""

    __slots__ = ("tokens", "fixed", "forwards", "fixed_at")

    def __init__(self, length: int, given: Sequence[int] = ()):
        self.tokens = np.zeros((length,), np.int32)
        self.fixed = np.zeros((length,), bool)
        self.tokens[: len(given)] = given
        self.fixed[: len(given)] = True
        self.forwards = 0
        self.fixed_at = [-1] * length


class _Running:
    """Slot-resident state for an admitted request."""

    __slots__ = (
        "req", "slot", "blocks", "cached_len", "admitted_seq", "step_k",
        "shared_idx", "shared_entries", "blk", "rule",
    )

    def __init__(self, req: Request, slot: int, blocks: List[int], cached_len: int, admitted_seq: int,
                 shared_idx=None, shared_entries=None):
        self.req = req
        self.slot = slot
        self.blocks = blocks
        self.cached_len = cached_len  # cache positions written so far
        self.admitted_seq = admitted_seq  # admission order, for LIFO preemption
        self.step_k = 0  # drafts planned for THIS step (<= req.spec_k)
        # prefix caching (generation/prefix.py): table positions whose
        # blocks are index-owned (refcounted, immutable, freed by the
        # index — never by this sequence) and the held entries
        self.shared_idx = shared_idx if shared_idx is not None else set()
        self.shared_entries = shared_entries if shared_entries is not None else []
        # block diffusion: the block in flight, whose first position is
        # ``cached_len`` (the positions below it are committed)
        self.blk: Optional[_Block] = None
        self.rule = (0, 2.0)  # (rows the static rule fixes a forward, the confidence the dynamic one asks)


class _Frontier:
    """The overlap pipeline's in-flight frontier: AT MOST ONE
    outstanding step (a decode step, or a block-diffusion engine's block
    step). Captures the dispatch-time slot states and, for a decode
    step, the host-side argument arrays (``slots``: ``_collect_slots``'s
    after the token array, reused — bumped by one — for the next
    dispatch, so steady state rebuilds nothing), plus the
    heartbeat seq the watchdog/stall bookkeeping is keyed on. ``seq0``
    is the scheduler's heartbeat seq just BEFORE this dispatch: a stall
    flagged on any later seq belongs to this frontier chain and voids
    its (late) result. Loop-thread only."""

    __slots__ = ("handle", "states", "slots", "sig", "hb_seq", "seq0")

    def __init__(self, handle, states, slots, sig, hb_seq, seq0):
        self.handle = handle
        self.states = states
        self.slots = slots
        self.sig = sig
        self.hb_seq = hb_seq
        self.seq0 = seq0


# how long the loop thread sleeps when a step found no work (a submit
# wakes it sooner), and how many finished request traces
# GET /v2/debug/traces keeps
IDLE_WAIT_S = 0.002
# the thread's CPU clock (time.thread_time) is read on one iteration in
# this many: a system call whose cost grows with the process's threads
# (~30 us a read in a serving process on the chip's host, against 0.3 us
# alone: PERF.md, PR 37), so every iteration would pay 3-4 % of a
# 3 ms step for it. The totals it feeds are of the sampled iterations.
CPU_CLOCK_EVERY = 16
TRACE_RING_SIZE = 256
# why _drain_frontier emptied the overlap pipeline (its callers' reasons)
_DRAIN_REASONS = ("nonsteady", "finish", "pressure", "idle")


class ContinuousBatchingScheduler:
    def __init__(
        self,
        engine: GenerationEngine,
        *,
        max_queue: int = 256,
        clock: Callable[[], float] = time.monotonic,
        breaker: Optional[CircuitBreaker] = None,
        retry: Optional[RetryPolicy] = None,
        speculation: Optional[SpeculationConfig] = None,
        draft_params=None,
        recovery: Optional[RecoveryPolicy] = None,
        watchdog: Optional[WatchdogPolicy] = None,
        observability: bool = True,
        journeys: Optional[bool] = None,
        flight_capacity: int = 512,
        trace_progress_every: int = 8,
        slo_objectives=None,
        pressure_threshold: float = 0.10,
        fault_scope: Optional[str] = None,
        overlap: Optional[bool] = None,
        overload: Optional[OverloadConfig] = None,
    ):
        self.engine = engine
        # fleet integration (serving/fleet.py): fault_scope tags every
        # step's injection sites with this replica's id (so chaos plans
        # can target ONE replica); failover_sink, when set, receives
        # every live request instead of a terminal EngineFailedError
        # when the restart budget exhausts — the fleet journal-replays
        # them onto surviving replicas via adopt()
        self.fault_scope = fault_scope
        self.failover_sink: Optional[Callable] = None
        # disaggregated serving: when set (prefill-pool replicas only),
        # admission ends at the first token — the prompt's KV packs into
        # the wire format and the (request, payload) pair goes to the
        # sink for transfer to the decode pool instead of occupying a
        # decode slot here
        self.handoff_sink: Optional[Callable] = None
        # scheduler-wide default speculation policy (a request's own
        # config overrides it); draft_params backs 'draft_model' drafters
        self.speculation_default = speculation
        self.draft_params = draft_params
        self.max_queue = max_queue
        self.clock = clock
        self.breaker = breaker or CircuitBreaker(clock=clock)
        self.retry = retry or RetryPolicy()
        self._queue: deque = deque()
        self._running: Dict[int, _Running] = {}  # slot -> state
        self._free_slots = list(range(engine.max_batch_slots - 1, -1, -1))
        self._lock = threading.Lock()
        self._wake = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self._alive = False
        self._draining = False
        self._hard_stop = False
        self._stopped = False  # a stopped (started-then-stopped) scheduler rejects submits
        self._admitted_seq = itertools.count()
        # observability (surfaced on /v2/stats via GenerationModel)
        self.stats = ServingStats()
        self.token_rate = TokenRate(clock=time.monotonic)
        self.preemptions = 0
        self.stats.add_gauge("queue_depth", lambda: len(self._queue))
        self.stats.add_gauge("running", lambda: len(self._running))
        self.stats.add_gauge("tokens_generated", lambda: self.token_rate.total)
        self.stats.add_gauge("tokens_per_s", self.token_rate.rate)
        self.stats.add_gauge("preemptions", lambda: self.preemptions)
        # (of the pool that is fuller, where the window layers have one
        # of their own: engine.blocks_in_use; the `cache` section of
        # /v2/stats has both)
        self.stats.add_gauge("cache_blocks_used", lambda: self.engine.blocks_in_use()[0])
        self.stats.add_gauge("cache_blocks_total", lambda: self.engine.blocks_in_use()[1])
        # mesh-native serving (ISSUE 15): mesh geometry + the per-shard
        # cache view — each device holds H/tp heads of every block, so
        # the per-shard byte load is total / tp_degree
        self.stats.add_gauge("mesh_devices", lambda: self.engine.mesh_devices)
        self.stats.add_gauge("tp_degree", lambda: self.engine.tp_degree)
        self.stats.add_gauge(
            "cache_shard_bytes",
            lambda: self.engine.cache_config.total_bytes
            // max(1, self.engine.tp_degree),
        )
        self.stats.add_gauge(
            "cache_shard_heads",
            lambda: self.engine.cache_config.num_heads
            // max(1, self.engine.tp_degree),
        )
        self.stats.add_gauge(
            "cache_occupancy",
            lambda: 1.0 - self.engine.allocator.num_free / max(1, self.engine.allocator.num_total),
        )
        self.stats.add_gauge("recompiles", lambda: sum(self.engine.recompiles().values()))
        self.stats.add_gauge(
            "device_time_s", lambda: sum(self.engine.device_time_s.values())
        )
        # per-request tracing + engine flight recorder (obs/): one
        # RequestTrace per submit, finished traces in a bounded ring
        # (GET /v2/debug/traces); one flight record per scheduler step
        # (GET /v2/debug/timeline, quarantine/restart postmortems).
        # observability=False turns both into no-ops: the reference side
        # of "tracing never changes a stream" (tests/test_observability.py,
        # test_steptrace.py, test_journey.py); no cell or launcher passes it.
        self.obs_enabled = observability
        self.trace_progress_every = trace_progress_every
        self.trace_ring = TraceRing(TRACE_RING_SIZE)
        # fleet-wide journeys (ISSUE 20): one span ring per replica,
        # stitched across the fleet by JourneyIndex at query time. Rides
        # observability by default; ``journeys=False`` keeps tracing on
        # with journeys off (passed by tests/test_journey.py only). The
        # lane label starts as the fault scope (the replica id in fleet
        # mode) and the fleet renames it at spawn.
        self.journey_stats = JourneyStats()
        self.journey_stats.register_gauges(self.stats)
        journeys_on = observability and (journeys is None or bool(journeys))
        self.journeys: Optional[JourneyRecorder] = (
            JourneyRecorder(
                lane=fault_scope or "local", clock=self.clock,
                stats=self.journey_stats,
            )
            if journeys_on else None
        )
        # dual-clock stamps: records carry t (perf_counter, the
        # timeline's single rendering clock) AND t_sched (this
        # scheduler's possibly-virtual clock) for trace correlation
        self.flight = FlightRecorder(
            capacity=flight_capacity, enabled=observability, sched_clock=self.clock
        )
        self._step_info: Dict = {}
        self._step_recorded = False
        # step-anatomy profiler (obs/steptrace.py): first-class host
        # spans + the device execute span per iteration, feeding the
        # flexflow_serving_step_phase_seconds histograms, the conserved
        # account of this thread's seconds (/v2/stats "step_phases" and
        # "loop") and the on-demand two-lane capture on GET
        # /v2/debug/anatomy. _step_spans holds THIS iteration's
        # (phase, t0, t1) perf_counter stamps, as obs/steptrace.phase
        # leaves them — the one account of a step: the flight record's
        # phase durations are summed from it too. _step_children holds
        # the parts of its dispatch spans (inside them, so in no sum of
        # the lane). Loop thread only.
        self.anatomy = StepAnatomy(enabled=observability)
        self.anatomy.register_gauges(self.stats)
        self._step_spans: List = []
        self._step_children: List = []
        # what the anatomy's last observation cost (ff.sched.observe):
        # handed to the next one, so the layer's own cost is a phase
        self._observe_carry = 0.0
        # iterations until the thread's CPU clock is read again
        self._cpu_turn = 0
        # the flight record's walls that are no anatomy span ("device",
        # the pipeline's "dispatch"), for THIS iteration; loop thread only
        self._step_walls: Dict[str, float] = {}
        # admit_stall: how long running streams were held by an
        # admission. _result_t is the readback stamp of the last decode
        # result consumed (None while nothing decodes), _stall_from the
        # one before the admissions now waiting for the next result
        self._result_t: Optional[float] = None
        self._stall_from = 0.0
        self._stalled_admits = 0
        if observability:
            # the cache's own spans (ff.cache.offload / .restore) land
            # in this model's windows: timed where the work happens
            engine.prefix_cache.observe = self.stats.observe
            self.stats.add_section("loop", self._loop_section)
            self.stats.add_section("uploads", engine.upload_stats)
            # where the process's seconds went before it served
            self.stats.add_section("startup", GLOBAL_STARTUP.snapshot)
        engine.register_stats(self.stats)
        self.spec_stats = SpeculationStats()
        self.spec_stats.register_gauges(self.stats)
        # capacity & compute observability (obs/capacity.py, obs/slo.py):
        # block telemetry, MFU/goodput, retrace blame, SLO burn rates —
        # all surfaced as gauges here and on the /v2 debug endpoints
        self.capacity = CacheTelemetry(
            engine.allocator, clock=self.clock,
            pressure_threshold=pressure_threshold, enabled=observability,
            reclaimable=lambda: engine.prefix_cache.evictable_blocks,
        )
        self.capacity.register_gauges(self.stats, lambda: list(self._running.values()))
        # overload control (ISSUE 14, serving/overload.py): priority-
        # aware admission + AIMD concurrency limit (driven by the PR 5
        # queue-time/TTFT windows and the cache-pressure flag above) +
        # the graceful-degradation ladder. The roofline TTFT predictor
        # backs the infeasibility fast-fail: predicted TTFT for a
        # prompt behind `depth` queued requests is (depth + 1) prefills
        # on the PR 7 serving roofline — injectable for pinned tests.
        fm = engine.flops_model
        self.overload = OverloadController(
            clock=self.clock,
            slots=engine.max_batch_slots,
            max_queue=max_queue,
            queue_depth=lambda: len(self._queue),
            queue_p95=lambda: self.stats.window_p95("queue_time"),
            ttft_p95=lambda: self.stats.window_p95("ttft"),
            cache_pressure=lambda: self.capacity.under_pressure,
            ttft_predictor=lambda n, depth: (depth + 1) * fm.roofline_s(
                fm.prefill_flops(n), fm.prefill_bytes(n)
            ),
            stats=self.stats,
            on_transition=self._note_degrade,
            config=overload,
        )
        self.overload.register_gauges(self.stats)
        # per-priority queue accounting (gauge snapshot is racy-ok,
        # like every other scrape-side read of the live deque)
        for p in Priority.ORDER:
            self.stats.add_gauge(
                f"overload_queue_depth_{p}",
                lambda p=p: sum(
                    1 for r in list(self._queue) if r.priority == p
                ),
            )
        engine.prefix_cache.register_gauges(self.stats)
        self.goodput = GoodputStats()
        self.goodput.register_gauges(self.stats)
        self.slo = SLOMonitor(slo_objectives, clock=self.clock)
        self.slo.register_gauges(self.stats)
        # steady-state retrace blame rides the flight ring next to the
        # step that caused it ("decode retraced: batch 8 -> 9")
        self.engine.programs.on_retrace = self._note_retrace
        # cost-model truth (obs/truth.py): predicted-vs-measured step
        # times as perf_* gauges, drift alarms onto the flight ring,
        # full pairs on GET /v2/debug/predictions
        self.engine.ledger.register_gauges(self.stats)
        self.engine.ledger.on_alarm = self._note_drift
        # overlapped decode (ISSUE 13): steady-state decode runs as a
        # two-deep software pipeline — step N+1 dispatched (tokens
        # carried device-resident from step N's output) while step N's
        # device work completes, its readback double-buffered, host
        # bookkeeping hidden inside N+1's execute window. Any non-steady
        # event (admission, finish/EOS, preempt, expiry, speculation,
        # crash, watchdog trip, shutdown) first DRAINS the in-flight
        # frontier deterministically, so recovery/replay/failover all
        # observe exactly the sequential semantics. _pipe (the at-most-
        # one-deep frontier) and its companions are loop-thread-only,
        # like _running; the heartbeat hand-off to the watchdog thread
        # stays the documented GIL-atomic tuple swap.
        self.overlap = True if overlap is None else bool(overlap)
        # a block-diffusion engine's rule (engine.BlockDiffusion): its
        # steps are block steps and no decode step is ever dispatched.
        # An engine has ONE kind of step, and the loop (the sequential
        # body, the overlap pipeline, its drains and its failure ladder)
        # is one: what the kind supplies is bound here, once, so that an
        # iteration of either kind runs no test of the other's. Who is
        # live at the next dispatch and the blocks they need
        # (``_plan``), the dispatch (``_dispatch``), the consume and the
        # scatter of its result, the sequential step with its probe,
        # and which end of a request needs no further step (``_ended``:
        # a block-diffusion request whose budget is spent still awaits
        # its block's commit)
        self.diffusion = engine.diffusion
        self._step_positions = self.diffusion.block_length if self.diffusion else 1
        if self.diffusion is None:
            self._kind, self._ended = "decode", Request.finished
            self._plan, self._dispatch = self._plan_decode, self._dispatch_pipeline
            self._consume, self._scatter, self._step_fns = self._consume_decode, self._scatter_decode, self._decode_step_fns
        else:
            self._kind, self._ended = "block_step", Request.ended_on_eos
            self._plan, self._dispatch = self._plan_block, self._dispatch_block
            self._consume, self._scatter, self._step_fns = self._consume_block, self._scatter_block, self._block_step_fns
        self._pipe: Optional[_Frontier] = None
        # the handles that hold decode tokens bookkept and not yet on
        # their streams' queues (GenerationHandle._emit_later). A put
        # wakes the stream's handler thread, and a woken thread takes
        # the interpreter's lock the first time this one gives it up
        # (any jax.Array's destructor does); this thread has it back
        # only after every woken handler has had its turn (11 ms at 64
        # streams: PERF.md §6, PR 38). Woken at the bookkeeping, they
        # take that turn before the next dispatch, with the device
        # running dry behind it. So a decode step's tokens go out where
        # this thread next parks for the device (_emit_late).
        self._late: List[GenerationHandle] = []
        self.engine.on_dispatched = self._emit_late
        # plain counters (the `pipeline` section of /v2/stats, read by
        # benchmark/layer_metrics/pipelined_step_share.py and by
        # tests/test_overlap.py): dispatches that went through the
        # pipeline, blocks its growth took from the prefix cache,
        # frontier drains by reason, and in-flight steps discarded
        # (recomputed exactly by the next sequential step; tests only)
        self.pipe_dispatches = 0
        self.pipe_reclaims = 0
        self.pipe_drains: Dict[str, int] = dict.fromkeys(_DRAIN_REASONS, 0)
        self.pipe_discards = 0
        self.emits_deferred = 0
        self.release_wait_s = 0.0
        self.stats.add_section("pipeline", self.pipeline_stats)
        # self-healing (recovery.py): journal + supervisor + watchdog.
        # _heartbeat is (seq, started_at) while a device call is in
        # flight — the watchdog's stall signal
        self.recovery_stats = RecoveryStats()
        self.recovery_stats.register_gauges(self.stats)
        # constrained decoding (ISSUE 18): grammar-cache + mask-step
        # telemetry (flexflow_serving_constrained_* on /metrics). The
        # serving layer's GrammarCache shares this object so per-model
        # compile hits/misses land next to the scheduler's masked-step
        # and dead-end counters.
        self.constrained_stats = ConstrainedStats()
        self.constrained_stats.register_gauges(self.stats)
        self.journal = GenerationJournal()
        self.supervisor = EngineSupervisor(self, recovery)
        self.watchdog = StepWatchdog(self, watchdog)
        self._heartbeat = None
        self._hb_seq = 0
        # the request popped for admission but not yet slot-resident:
        # visible to the watchdog's deadline reaper, which otherwise
        # could not see it while its prefill is wedged. _admitting_blocks
        # mirrors its allocation so cache_report can show a provisional
        # residency row while the prefill (possibly a cold compile) runs
        self._admitting: Optional[Request] = None
        self._admitting_blocks: Optional[List[int]] = None

    # ------------------------------------------------------------- submit
    def submit(
        self,
        prompt: Sequence[int],
        sampling: Optional[SamplingParams] = None,
        deadline_s: Optional[float] = None,
        speculation: Optional[SpeculationConfig] = None,
        transport: Optional[str] = None,
        priority: Optional[str] = None,
        grammar=None,
        response_format: Optional[Dict] = None,
        journey: Optional[JourneyContext] = None,
    ) -> GenerationHandle:
        """Enqueue one request (priority-ordered, FCFS within a class).
        Typed rejections mirror the batcher: OverloadedError (a
        QueueFullError subclass, carrying reason / priority /
        retry_after_s) on backpressure, limiter throttling, or
        degradation shedding; InfeasibleError when the roofline-
        predicted TTFT already exceeds the deadline; CircuitOpenError
        while the breaker holds traffic; ShuttingDownError while
        draining; DeadlineExceededError for an already-expired budget.
        A full queue sheds the youngest queued request of the LOWEST
        class that is strictly below the newcomer's (never a mid-stream
        resume) before rejecting the newcomer. ``speculation`` turns on
        (exact) speculative decoding for this request; None falls back
        to the scheduler-wide default. ``transport`` annotates the
        request's trace ("http"/"grpc"). ``priority`` is one of
        Priority.ORDER (default standard). ``grammar`` is a compiled
        constrained-decoding TokenDFA (see generation/constrained/);
        ``response_format`` is the wire spec it came from, kept for
        stream validation and replay provenance."""
        if self._draining:
            raise ShuttingDownError("generation scheduler draining")
        if self._stopped:
            raise ShuttingDownError("generation scheduler stopped")
        sampling = sampling or SamplingParams()
        if not prompt:
            raise ValueError("empty prompt")
        if len(prompt) > self.engine.buckets[-1]:
            raise ValueError(
                f"prompt length {len(prompt)} exceeds max bucket {self.engine.buckets[-1]}"
            )
        room = self.engine.max_seq_len - len(prompt)
        if room < 1:
            raise ValueError(f"prompt fills max_seq_len {self.engine.max_seq_len}")
        if (
            self.engine.cache_config.blocks_for(len(prompt) + 1)
            > self.engine.allocator.num_total
        ):
            raise ValueError("prompt exceeds total cache capacity; can never be admitted")
        if grammar is not None:
            self.engine._refuse("constrained_decoding")
        if self.diffusion is not None:
            # a request's overrides of the rule's defaults: checked here, by the rule itself
            self.diffusion.rows_per_forward(sampling.denoising_steps)
            self.diffusion.threshold_of(sampling.remasking, sampling.threshold)
        if grammar is not None and grammar.vocab_size != self.engine.cfg.vocab_size:
            raise ValueError(
                f"grammar compiled against vocab {grammar.vocab_size}, "
                f"engine vocab is {self.engine.cfg.vocab_size}"
            )
        if deadline_s is not None and deadline_s <= 0:
            self.stats.incr("expired")
            raise DeadlineExceededError("deadline already expired at submit")
        priority = Priority.parse(priority)
        rank = Priority.rank(priority)
        ctl = self.overload
        # chaos hook: force admission-path failures (typically a typed
        # OverloadedError) so tests drive the limiter/shed paths
        # deterministically without generating real pressure
        faults.inject(faults.SERVING_ADMISSION, (priority, len(self._queue)))
        if ctl.degraded_reject(priority):
            raise ctl.overload_error(
                f"degraded: shedding {priority} traffic "
                f"(ladder level {ctl.ladder.level})",
                "degraded", priority,
            )
        if deadline_s is not None:
            predicted = ctl.infeasible(len(prompt), deadline_s)
            if predicted is not None:
                raise ctl.infeasible_error(priority, predicted, deadline_s)
        shed: List = []  # (victim, error) pairs, settled OUTSIDE the lock
        with self._lock:
            # breaker FIRST — before any shed planning, so a submit the
            # breaker is about to refuse can never destroy queued work.
            # ready(), NOT allow(): submit only enqueues — the device
            # call happens at admission, so the half-open probe slot
            # must be claimed by _admit. A submit that claimed it would
            # leave the probe's outcome forever unrecorded and stall
            # held requests for another recovery window.
            if not self.breaker.ready():
                self.stats.incr("rejected")
                raise CircuitOpenError("generation circuit open")
            deadline = None if deadline_s is None else self.clock() + deadline_s
            spec = speculation if speculation is not None else self.speculation_default
            drafter = None
            if spec is not None and spec.enabled:
                # a configuration the engine cannot verify for refuses
                # here, by name, before anything is queued
                self.engine._refuse("speculation")
                # clamp to the engine's compiled verify window so per-
                # request k NEVER changes the jit shape
                if spec.k > self.engine.max_spec_tokens:
                    spec = dataclasses.replace(spec, k=self.engine.max_spec_tokens)
                drafter = build_drafter(
                    spec, draft_params=self.draft_params,
                    max_seq_len=self.engine.max_seq_len,
                )
            req = Request(
                list(prompt), sampling, deadline=deadline,
                speculation=spec, drafter=drafter, priority=priority,
                grammar=grammar, response_format=response_format,
            )
            req.submitted_at = self.clock()
            if self.obs_enabled:
                req.trace = RequestTrace(
                    req.id, clock=self.clock,
                    progress_every=self.trace_progress_every,
                )
                req.trace_ring = self.trace_ring
                req.slo_sink = self._slo_record
                if self.diffusion is not None:
                    req.trace.fixed_at = req.fixed_at  # (the one list: the trace shows it as it grows)
                req.trace.mark_accept(
                    prompt_len=len(prompt),
                    deadline_s=deadline_s,
                    speculative=bool(spec is not None and spec.enabled),
                )
                if transport is not None:
                    req.trace.mark_transport(transport)
                if self.journeys is not None:
                    # a context handed in from ingress (HTTP/gRPC/fleet)
                    # keeps its id and parents onto the ingress span;
                    # otherwise the journey roots here
                    ctx = journey if journey is not None else self.journeys.mint()
                    ctx.recorder = self.journeys
                    req.journey = ctx
                    req.trace.journey_id = ctx.journey_id
                    ctx.hop(
                        "submit", request_id=req.id,
                        prompt_len=len(prompt), priority=priority,
                        transport=transport,
                    )
            # the sequence can never outgrow max_seq_len (its last token
            # would need a cache position past the block table) NOR the
            # TOTAL cache: a sequence needing more blocks than exist
            # would preempt-self forever at the head of the FCFS queue
            cache_room = (
                self.engine.allocator.num_total * self.engine.cache_config.block_size
                - len(prompt)
            )
            req.max_new = min(sampling.max_new_tokens, room, cache_room)
            # degrade level 3+: clamp NEW admissions' budgets per class
            # (running streams keep the budget they were admitted with)
            cap = ctl.max_new_cap(priority)
            if cap is not None:
                req.max_new = min(req.max_new, max(1, cap))
            # overload gates, planned BEFORE any victim is touched: the
            # full shed set (one for queue space when full, at most one
            # more when queued lower-priority work holds the limiter
            # slot — no priority inversion) is feasibility-checked
            # first, so a newcomer the gates will refuse anyway never
            # destroys queued work. Victims' limiter slots release here
            # (under the lock, so the acquire below cannot lose them);
            # their handles settle AFTER the lock drops.
            need = 1 if len(self._queue) >= self.max_queue else 0
            freed = need
            if not ctl.limiter.can_admit(priority, freed=freed):
                freed += 1  # one extra shed, for the limiter slot itself
                if not ctl.limiter.can_admit(priority, freed=freed):
                    raise ctl.overload_error(
                        "admission throttled by the adaptive concurrency "
                        f"limit ({ctl.limiter.limit:.0f})",
                        "limiter", priority,
                    )
            if freed:
                victims = self._shed_victims_locked(rank, freed)
                if len(victims) < freed:
                    if need and not victims:
                        raise ctl.overload_error(
                            f"generation queue full ({self.max_queue})",
                            "queue_full", priority,
                        )
                    raise ctl.overload_error(
                        "admission throttled by the adaptive concurrency "
                        f"limit ({ctl.limiter.limit:.0f})",
                        "limiter", priority,
                    )
                reason = "queue_full" if need else "limiter"
                detail = (
                    f"queue full at {self.max_queue}" if need
                    else f"adaptive limit {ctl.limiter.limit:.0f}"
                )
                for victim in victims:
                    self._queue.remove(victim)
                    release, victim.overload_release = (
                        victim.overload_release, None
                    )
                    if release is not None:
                        try:
                            release()
                        except Exception:
                            pass
                    shed.append((victim, ctl.overload_error(
                        f"shed for a higher-priority admission ({detail})",
                        reason, victim.priority, shed=True,
                    )))
            if not ctl.limiter.try_acquire(priority):
                # unreachable by construction (can_admit held under this
                # lock and inflight only shrinks concurrently); typed
                # anyway rather than trusting the invariant with a hang
                raise ctl.overload_error(
                    "admission throttled by the adaptive concurrency "
                    f"limit ({ctl.limiter.limit:.0f})",
                    "limiter", priority,
                )
            req.overload_release = ctl.limiter.release
            self._queue_insert_locked(req)
        # settle shed victims OUTSIDE the lock: Future.set_exception
        # runs client done-callbacks synchronously, and a callback that
        # re-enters the scheduler must not deadlock on _lock
        for victim, err in shed:
            victim.handle._fail(err)
        self.stats.incr("admitted")
        self._wake.set()
        return req.handle

    # ------------------------------------------------------------ control
    def start(self) -> None:
        if self._alive:
            return
        self._alive = True
        self._draining = False
        self._hard_stop = False
        self._stopped = False
        self._thread = threading.Thread(target=self._loop, daemon=True)
        self._thread.start()
        self.watchdog.start()

    def stop(self, drain: bool = True, timeout: float = 30.0) -> None:
        """Graceful by default: finish queued + running requests, then
        exit. ``drain=False`` fails outstanding work immediately."""
        if self._thread is None:
            # never-started (manual-step) scheduler: honor the drain
            # contract inline — queued futures must not hang forever
            self._draining = True
            if drain:
                while self.has_work() and self.step():
                    pass
            self._abort_all(ShuttingDownError("scheduler stopped"))
            self._draining = False
            self._stopped = True
            return
        self._draining = True
        self._alive = False
        if not drain:
            self._hard_stop = True  # loop exits after the current step
        self._wake.set()
        self._thread.join(timeout=timeout)
        if self._thread.is_alive() and self._heartbeat is None:
            # alive but NOT inside a device call: the drain is starved,
            # not wedged — e.g. an OPEN breaker holding queued requests
            # it cannot admit. Break the loop and fail the leftovers
            # typed below instead of leaking threads + hanging clients.
            self._hard_stop = True
            self._wake.set()
            self._thread.join(timeout=5.0)
        wedged = self._thread.is_alive()
        self._thread = None
        if wedged:
            # a wedged step keeps ownership of the slot/allocator state;
            # touching it here would race the live thread. The watchdog
            # stays alive on purpose: it is the only thing left that can
            # fail deadline-carrying handles stuck behind the zombie step
            return
        if drain:
            # the loop exited; anything still outstanding completes here
            # (watchdog still running: a step wedging during THIS drain
            # is the exact failure class it exists to catch)
            while self.has_work() and self.step():
                pass
        if self.has_work():
            # leftovers that cannot make progress (held behind an open
            # breaker, or drain=False): fail them typed, never hang them.
            # Runs only AFTER the loop exited: _abort_all mutates
            # _running/allocator state the stepping thread owns.
            self._abort_all(ShuttingDownError("scheduler stopped"))
        self.watchdog.stop()
        self._draining = False
        self._stopped = True

    def _abort_all(self, err: BaseException) -> None:
        """Shutdown-only teardown (``err`` is always a typed
        ShuttingDownError). Engine failures never come through here:
        the supervisor journal-replays running streams and HOLDS queued
        requests, so a queued-but-never-admitted request can no longer
        be failed with some other request's engine-internal error."""
        self._discard_frontier()  # shutdown: in-flight results are moot
        with self._lock:
            queued, self._queue = list(self._queue), deque()
        for req in queued:
            if req.handle._fail(err):
                self.stats.incr("failed")
        for state in list(self._running.values()):
            self._release(state)
            if state.req.handle._fail(err):
                self.stats.incr("failed")

    def _fail_running_engine_dead(self, err: EngineFailedError) -> None:
        """Restart budget exhausted: every slot-resident stream is truly
        lost to THIS engine — fail it with the typed EngineFailedError
        (never the raw device traceback). The engine was reset, so
        slot/allocator bookkeeping restarts from empty rather than
        freeing stale block ids into the fresh free list.

        With a ``failover_sink`` installed (fleet mode), the streams are
        not lost at all: every live request — slot-resident, replay-
        requeued mid-stream, and fresh queued — leaves this scheduler
        entirely (journal drained, slots cleared, queue emptied) and is
        handed to the sink, which journal-replays it onto a surviving
        replica (adopt()). The handoff is safe against double emission
        because the requests fully exit this scheduler's bookkeeping
        before the sink runs."""
        self.journal.drain()
        states = sorted(self._running.values(), key=lambda s: s.admitted_seq)
        self._reset_slots()
        self.engine.reset()
        for state in states:
            state.blocks = []
            state.shared_idx = set()
            state.shared_entries = []
        # streams that already completed their budget/EOS at a pipeline
        # consume but were still awaiting release when the engine died:
        # they hold every token — complete them, never fail or migrate
        done_states = [
            s for s in states
            if not s.req.handle.done() and s.req.finished()
        ]
        for s in done_states:
            s.req.handle._finish(list(s.req.generated))
            self.stats.incr("completed")
        states = [s for s in states if s not in done_states]
        sink = self.failover_sink
        if sink is not None:
            with self._lock:
                queued, self._queue = list(self._queue), deque()
            live = [s.req for s in states if not s.req.handle.done()]
            live += [r for r in queued if not r.handle.done()]
            try:
                sink(live, err)
                return
            except Exception:
                # the fleet must never make a dying engine worse: put
                # the taken queue back (ahead of anything submitted
                # meanwhile) and fall through to the single-engine
                # terminal semantics
                with self._lock:
                    for req in reversed(queued):
                        self._queue.appendleft(req)
        for state in states:
            if state.req.handle._fail(err):
                self.stats.incr("failed")
        # replay-requeued MID-STREAM requests (n_generated > 0) are as
        # lost as the slot-resident ones — their clients already hold
        # tokens, so holding them for a possible future probe would
        # hang them instead. Fresh queued requests stay held: they
        # streamed nothing and remain safe to resubmit or admit later.
        # One lock hold for the whole partition: the queue must never
        # look momentarily empty to a concurrent submit, or max_queue
        # backpressure overshoots while the kept requests re-enter.
        with self._lock:
            keep: deque = deque()
            for req in self._queue:
                if req.n_generated > 0:
                    if req.handle._fail(err):
                        self.stats.incr("failed")
                else:
                    keep.append(req)
            self._queue = keep

    def _rebuild_from_journal(self) -> None:
        """Journal-replay after an engine teardown: every live stream is
        requeued at the FRONT (it was admitted before anything waiting)
        with its generated tokens folded into the prompt — the
        preempt-by-recompute path then resumes each token stream
        exactly. Must run after ``engine.reset()``: old block ids must
        not be freed into the fresh allocator."""
        entries = self.journal.drain()
        self._reset_slots()
        replayed = 0
        requeue = []
        for entry in entries:
            req = entry.req
            if req.handle.done():  # reaped (deadline) while the engine was down
                continue
            if req.finished():
                # completed its budget/EOS before the teardown (a
                # pipeline consume can finish a stream whose release
                # was still pending when the restart hit): it already
                # holds every token — complete it, never replay it
                req.handle._finish(list(req.generated))
                self.stats.incr("completed")
                continue
            req.prompt = req.original_prompt + list(req.generated)
            # constrained streams rebuild their automaton cursor at
            # re-admission by re-advancing over `generated` — the
            # journal IS the mask state
            req.mask_state = None
            req.replays += 1
            req.trace.note_replay()
            req.journey.hop(
                "replay", n_generated=req.n_generated,
                reason="engine_restart",
            )
            replayed += req.n_generated
            requeue.append(req)
        with self._lock:
            for req in reversed(requeue):
                self._queue.appendleft(req)
        if replayed:
            self.recovery_stats.incr("replayed_tokens", replayed)
        self._wake.set()

    def steal_queue(self) -> List[Request]:
        """Fleet rescue: atomically take every QUEUED (never slot-
        resident this life, or held behind the breaker) request off this
        scheduler, for adoption elsewhere. Safe against a live loop
        thread — the queue is only popped under the same lock. Slot-
        resident streams are NOT stealable (the loop thread owns them);
        they finish, fail over via the supervisor, or expire."""
        with self._lock:
            stolen, self._queue = list(self._queue), deque()
        return [r for r in stolen if not r.handle.done()]

    def adopt(self, req: Request, *, front: bool = True,
              imported=None) -> None:
        """Cross-replica journal-replay admission (fleet failover): take
        ownership of a Request journaled on a dead sibling scheduler.
        The replay state IS the request object — original prompt, every
        emitted token, the per-token-count seeded sampling keys and
        speculation config — so the recompute-prefill path resumes the
        stream byte-exactly on THIS engine (fleet replicas are built by
        one factory, hence geometrically identical). Bypasses the
        max_queue bound and the breaker on purpose: a migrated stream
        was already admitted once and must not be dropped for
        backpressure it cleared on its original replica. ``front``
        requeues ahead of fresh work (mid-stream requests were admitted
        before anything now waiting).

        ``imported`` (disaggregated serving) attaches a CRC-verified
        :class:`KVHandoffPayload`: admission imports the prefilled
        blocks instead of recompute-prefilling, and any import failure
        falls back to the recompute path — the stream is byte-exact
        either way, so a handoff can degrade but never corrupt."""
        req.imported_kv = imported
        req.prompt = req.original_prompt + list(req.generated)
        req.mask_state = None  # rebuilt from `generated` at admission
        # heterogeneous-adopter guards (unreachable for fleet-built
        # replicas, which share one factory): mirror submit()'s
        # can-never-be-admitted checks, or the adopted stream wedges
        # this queue's FCFS head forever
        room = self.engine.max_seq_len - len(req.prompt)
        cache_room = (
            self.engine.allocator.num_total * self.engine.cache_config.block_size
            - len(req.prompt)
        )
        if (
            len(req.prompt) > self.engine.buckets[-1]
            or room < 1
            or self.engine.cache_config.blocks_for(len(req.prompt) + 1)
            > self.engine.allocator.num_total
        ):
            if req.handle._fail(ValueError(
                f"adopted stream length {len(req.prompt)} can never be "
                f"admitted on this engine (max bucket "
                f"{self.engine.buckets[-1]}, max_seq_len "
                f"{self.engine.max_seq_len}, cache blocks "
                f"{self.engine.allocator.num_total})"
            )):
                self.stats.incr("failed")
            return
        # re-clamp the budget against THIS engine's geometry (total
        # generated = already-emitted + what still fits here)
        req.max_new = min(
            req.max_new, req.n_generated + room, req.n_generated + cache_room
        )
        if req.n_generated > 0 and imported is None:
            # a recompute adoption replays the stream; an imported
            # handoff is the disaggregated steady state and counts only
            # if the import is later rejected (see _admit_imported)
            req.replays += 1
            req.trace.note_replay()
            self.recovery_stats.incr("replayed_tokens", req.n_generated)
        # retarget terminal observability at the adopting scheduler so
        # the finished trace and SLO/goodput accounting land where the
        # stream actually completed
        if req.trace_ring is not None:
            req.trace_ring = self.trace_ring
        if req.slo_sink is not None:
            req.slo_sink = self._slo_record
        if req.journey is not NULL_JOURNEY:
            # retarget the journey at the adopting replica's span ring:
            # from here on, hops land in THIS lane (or nowhere, if this
            # scheduler runs with journeys off — the context stays
            # intact so a later adopter can pick it back up)
            req.journey.recorder = self.journeys
            req.journey.hop(
                "adopt", replica=self.fault_scope,
                imported=imported is not None, front=front,
                n_generated=req.n_generated,
            )
        # retarget overload accounting too: release the dead replica's
        # limiter slot and count the stream against THIS limiter —
        # forced past the limit (a migrated stream was already admitted
        # once and must never be dropped for headroom it cleared
        # elsewhere), so would_admit/pressure see the true load
        release, req.overload_release = req.overload_release, None
        if release is not None:
            try:
                release()
            except Exception:
                pass
        self.overload.limiter.acquire_forced()
        req.overload_release = self.overload.limiter.release
        with self._lock:
            if front:
                self._queue.appendleft(req)
            else:
                self._queue.append(req)
        self._wake.set()

    def _reset_slots(self) -> None:
        """Post-``engine.reset()`` slot bookkeeping: every slot is empty
        and every outstanding block table invalid wholesale (the
        allocator free list was restored, so per-block frees — which
        would double-free — must never follow this)."""
        self._running.clear()
        self._free_slots = list(range(self.engine.max_batch_slots - 1, -1, -1))
        self._result_t, self._stalled_admits = None, 0

    def _quarantine(self, state: _Running, err: BaseException) -> None:
        """Fail ONE poisoned request and keep the batch: blocks freed,
        slot returned, everyone else untouched. The flight recorder's
        trailing window rides the error out as the postmortem."""
        req = state.req
        req.trace.event(
            "quarantine",
            step=getattr(err, "step", None),
            reason=getattr(err, "reason", type(err).__name__),
        )
        if getattr(err, "flight_snapshot", None) is None:
            try:
                err.flight_snapshot = self.flight.incident(
                    "quarantine", request_id=req.id,
                    error=repr(err)[:200],
                )
            except Exception:
                pass  # exceptions with __slots__ cannot carry the dump
        self._release(state)
        if req.handle._fail(err):
            self.stats.incr("failed")
            self.recovery_stats.incr("quarantined")

    def _sweep_mask_errors(self) -> None:
        """Quarantine running slots whose constrained stream parked a
        grammar error during token bookkeeping. _advance_mask never
        raises mid-emit — a dead-ended automaton must not unwind the
        scatter loop and take the batch's other slots with it — so the
        error waits one iteration here, where quarantine is safe: the
        slot is released, the typed error reaches the one caller, and
        everyone else keeps streaming."""
        for state in list(self._running.values()):
            err = state.req.mask_error
            if err is not None:
                state.req.mask_error = None
                self._quarantine(state, err)

    def ready(self) -> bool:
        return not self._draining and self.breaker.ready()

    def has_work(self) -> bool:
        return bool(self._queue) or bool(self._running)

    # ------------------------------------------- capacity / SLO reporting
    def _note_retrace(self, name: str, blame: str) -> None:
        """Program-registry retrace hook: the blame string lands on the
        flight ring in true order with the step that retraced."""
        self.flight.record_event("retrace", program=name, blame=blame)

    def _note_drift(self, alarm: Dict) -> None:
        """Truth-ledger drift hook: the calibration-staleness alarm
        ("decode: predicted 1.8ms, measured p50 3.1ms, error +72%, ...")
        lands on the flight ring next to the steps that proved it."""
        self.flight.record_event(
            "drift", program=alarm["key"], blame=alarm["blame"]
        )

    def _slo_record(self, req: Request) -> None:
        """Terminal SLO/goodput sink (exactly once per request, via the
        handle's settle-race winner). Deadline-goodput counts a token as
        good only when its request COMPLETED in-deadline; the SLO
        windows see every outcome."""
        tr = req.trace
        in_deadline = req.deadline is None or (
            tr.t_finish is not None and tr.t_finish <= req.deadline
        )
        self.goodput.record(
            req.n_generated, good=(tr.outcome == "completed" and in_deadline)
        )
        self.slo.observe(tr.outcome or "unknown", ttft_s=tr.ttft_s, tpot_s=tr.tpot_s)

    def cache_report(self) -> Dict:
        """The ``GET /v2/debug/cache`` payload: allocator state +
        per-request block residency (obs/capacity.py). Read order
        matters for concurrent scrapes: the free count FIRST (so a
        request finishing mid-scrape leaves the residency table at
        worst undercounting ``used``, never claiming freed blocks),
        then the running snapshot, then the in-flight admission — with
        id-dedup in report(), a request can never be counted twice,
        and the undercount window shrinks from the whole prefill to
        the register-then-clear gap."""
        free = self.engine.allocator.num_free
        running = list(self._running.values())
        adm_req, adm_blocks = self._admitting, self._admitting_blocks
        return self.capacity.report(
            running, queue_depth=len(self._queue),
            admitting=(adm_req, adm_blocks)
            if adm_req is not None and adm_blocks else None,
            free=free,
            prefix=self.engine.prefix_cache.snapshot(),
        )

    def _loop(self) -> None:
        # this thread's wall is counted from here (/v2/stats "loop"):
        # every second of it is a working iteration's, an empty one's,
        # a wait's, or the few lines of this loop between them
        self.anatomy.loop_started(time.perf_counter())
        try:
            while (self._alive or (self._draining and self.has_work())) and not self._hard_stop:
                if not self.step():
                    parked = time.perf_counter()
                    self._wake.wait(timeout=IDLE_WAIT_S)
                    self._wake.clear()
                    self.anatomy.observe_wait(parked, time.perf_counter())
        finally:
            self.anatomy.loop_stopped()

    def _loop_section(self) -> Dict[str, float]:
        """``loop`` of ``/v2/stats``: the anatomy's account of this
        thread and, from the same stamps' places as the decode dispatch
        span, that span's wall and CPU seconds over the sampled
        iterations (wall less CPU: the thread held no core)."""
        wall, cpu = self.engine.decode_dispatch_clock
        return dict(self.anatomy.loop(), decode_dispatch_wall_total_s=wall, decode_dispatch_cpu_total_s=cpu)

    # ---------------------------------------------------------- internals
    def _release(self, state: _Running) -> None:
        self.journal.discard(state.req)
        # private blocks go back to the allocator; shared (index-owned)
        # blocks only drop this sequence's refcount — their content
        # stays cached for the next matching prompt
        self.engine.allocator.free(
            [b for i, b in enumerate(state.blocks) if i not in state.shared_idx]
        )
        self.engine.prefix_cache.release(state.shared_entries)
        self.engine.release_slot(state.slot)  # its blocks of the window layers' pool, where there is one
        state.blocks = []
        state.shared_idx = set()
        state.shared_entries = []
        del self._running[state.slot]
        self._free_slots.append(state.slot)

    def _finish(self, state: _Running) -> None:
        self._release(state)
        req = state.req
        self.stats.latency.record(max(0.0, self.clock() - req.submitted_at))
        tpot = req.trace.tpot_s
        if tpot is not None:
            self.stats.observe("tpot", tpot, exemplar=req.journey.journey_id)
        req.handle._finish(list(req.generated))
        self.stats.incr("completed")

    def _expire(self) -> None:
        now = self.clock()
        with self._lock:
            keep: deque = deque()
            for req in self._queue:
                if req.handle.done():
                    pass  # reaped by the watchdog during a stall; just drop
                elif req.cancelled:
                    if req.handle._fail(ShuttingDownError("request cancelled")):
                        self.stats.incr("cancelled")
                elif req.deadline is not None and now >= req.deadline:
                    if req.handle._fail(DeadlineExceededError("deadline expired while queued")):
                        self.stats.incr("expired")
                else:
                    keep.append(req)
            self._queue = keep
        for state in list(self._running.values()):
            req = state.req
            if req.handle.done():
                # failed externally (watchdog deadline reap): resource
                # cleanup belongs to this thread, the counting happened
                # where the handle was failed
                self._release(state)
            elif req.cancelled:
                self._release(state)
                if req.handle._fail(ShuttingDownError("request cancelled")):
                    self.stats.incr("cancelled")
            elif req.deadline is not None and now >= req.deadline:
                self._release(state)
                if req.handle._fail(DeadlineExceededError("deadline expired mid-generation")):
                    self.stats.incr("expired")

    @contextlib.contextmanager
    def _stamped(self):
        """Heartbeat stamp around any section that can wedge on the
        device — the watchdog's only stall signal."""
        self._hb_seq += 1
        self._heartbeat = (self._hb_seq, self.clock())
        try:
            yield
        finally:
            self._heartbeat = None

    def _device(self, fn):
        """Run one device step under retry + breaker accounting, with a
        heartbeat stamped around the call so the watchdog can see a step
        that neither returns nor raises."""
        with self._stamped():
            try:
                out = self.retry.run(fn)
            except Exception:
                self.breaker.record_failure()
                raise
        self.breaker.record_success()
        return out

    def _probe_call(self, fn):
        """Device call for a blame-assignment probe: heartbeat only, no
        retry/breaker (an expected crash while bisecting is not device
        health signal — but a STALL during a probe must still be
        visible to the watchdog)."""
        with self._stamped():
            return fn()

    def _queue_insert_locked(self, req: Request) -> None:
        """Priority-ordered enqueue: ahead of the first FRESH queued
        request of a strictly lower class, FIFO within a class. Resumed
        work (preempted / journal-replayed, requeued at the front by
        appendleft) keeps absolute precedence — a new interactive
        request must not starve a mid-stream resume whose client
        already holds tokens."""
        q = self._queue
        for i, cand in enumerate(q):
            if cand.n_generated > 0 or cand.preemptions > 0 or cand.replays > 0:
                continue
            if cand.priority_rank > req.priority_rank:
                q.insert(i, req)
                return
        q.append(req)

    def _shed_victims_locked(self, rank: int, n: int) -> List[Request]:
        """Up to ``n`` shed victims for a newcomer of ``rank``: fresh
        queued requests of classes strictly below the newcomer's (never
        a mid-stream resume — its client already holds tokens), lowest
        class first, youngest first within a class."""
        cands = [
            (cand.priority_rank, idx, cand)
            for idx, cand in enumerate(self._queue)
            if cand.priority_rank > rank
            and cand.n_generated == 0 and cand.preemptions == 0
            and cand.replays == 0 and not cand.handle.done()
        ]
        cands.sort(key=lambda t: (t[0], t[1]), reverse=True)
        return [cand for _, _, cand in cands[:n]]

    def _shed_queued_best_effort(self) -> None:
        """Degrade level 4: every queued fresh best-effort request
        fails typed (reason "degraded"); resumed best-effort streams
        keep their place — shedding them would cut off clients
        mid-stream."""
        with self._lock:
            victims = [
                r for r in self._queue
                if r.priority_rank == Priority.RANK[Priority.BEST_EFFORT]
                and r.n_generated == 0 and r.preemptions == 0
                and r.replays == 0 and not r.handle.done()
            ]
            for r in victims:
                self._queue.remove(r)
        for r in victims:
            r.handle._fail(self.overload.overload_error(
                "degraded: best-effort shed at ladder level "
                f"{self.overload.ladder.level}",
                "degraded", r.priority, shed=True,
            ))

    def _note_degrade(self, old: int, new: int, pressure: float) -> None:
        """Ladder-transition hook: every level change is a flight-ring
        event next to the steps that caused it."""
        self.flight.record_event(
            "degrade", level=new, prev=old, pressure=round(pressure, 3)
        )

    def _overload_tick(self) -> None:
        """One overload-control iteration (limiter AIMD + ladder), plus
        the ladder's level-4 action: shed queued best-effort work."""
        self.overload.tick()
        if self.overload.ladder.shed_best_effort():
            self._shed_queued_best_effort()

    def _preempt_youngest(self, exclude: Optional[_Running] = None) -> bool:
        """Evict a running sequence for recompute under cache pressure:
        the victim is the youngest member of the LOWEST priority class
        present (vLLM's LIFO recompute victim, priority-ordered): free
        its blocks, fold its generated tokens into the prompt, and
        requeue it at the FRONT. ``exclude`` is the growing sequence:
        it is never the victim here — and neither is anything that
        OUTRANKS it (growing a best-effort stream must not evict an
        interactive one; returning False makes the caller self-preempt
        the grower instead)."""
        victims = [s for s in self._running.values() if s is not exclude]
        if exclude is not None:
            victims = [
                s for s in victims
                if s.req.priority_rank >= exclude.req.priority_rank
            ]
        if not victims:
            return False
        victim = max(victims, key=lambda s: (s.req.priority_rank, s.admitted_seq))
        self.capacity.note_preempt(len(victim.blocks))
        # stash the victim's computed KV in the radix index before the
        # release: its re-admission (and any prefix-sharing request)
        # re-matches the blocks — under continued pressure they offload
        # to the host tier and swap back in instead of recomputing
        self.engine.stash_prefix(victim)
        self._release(victim)
        req = victim.req
        req.block_resume = (victim.cached_len, victim.blk) if victim.blk is not None else None
        req.prompt = req.original_prompt + list(req.generated)
        req.mask_state = None  # rebuilt from `generated` at re-admission
        req.preemptions += 1
        self.preemptions += 1
        req.trace.note_preempt()
        with self._lock:
            self._queue.appendleft(req)
        return True

    def _admit(self) -> bool:
        """FCFS, cache-capacity-aware admission. Returns True if a
        request was admitted (prefilled)."""
        with self._lock:
            if not self._queue or not self._free_slots:
                return False
            # an OPEN breaker holds admission: queued requests wait out a
            # device outage (expiring at their own deadlines) instead of
            # being burned one per step against a dead engine; after
            # recovery_s the next admission is the half-open probe whose
            # success resumes service
            if not self.breaker.allow():
                return False
            req = self._queue[0]
        if req.grammar is not None and req.mask_state is None:
            # constrained stream: rebuild the automaton cursor by
            # re-advancing over every emitted token. First admission
            # starts at the grammar's start state; preempt-recompute,
            # engine restart, and cross-replica adoption all arrive
            # here with mask_state dropped and `generated` intact, so
            # the journal IS the mask state (byte-exact replay). A
            # refused token (replay divergence or an injected
            # generation.mask_advance fault) fails the ONE request
            # typed — the queue and batch are untouched.
            try:
                req.mask_state = req.grammar.state_after(
                    req.generated, req.sampling.eos_id
                )
            except Exception as e:
                with self._lock:
                    if self._queue and self._queue[0] is req:
                        self._queue.popleft()
                self.constrained_stats.incr("dead_end_failures")
                err = PoisonedRequestError(
                    f"request {req.id} could not rebuild its grammar "
                    f"state: {e}",
                    request_id=req.id, step="mask", reason="mask_advance",
                )
                req.trace.event("quarantine", step="mask", reason="mask_advance")
                err.flight_snapshot = self.flight.incident(
                    "quarantine", request_id=req.id, step="mask",
                    reason="mask_advance",
                )
                if req.handle._fail(err):
                    self.stats.incr("failed")
                    self.recovery_stats.incr("quarantined")
                return True
        if req.imported_kv is not None:
            # disaggregated decode pool: the prompt's KV arrived over
            # the handoff wire — import it instead of prefilling
            return self._admit_imported(req)
        # prefix match + block acquisition run OUTSIDE the submit lock:
        # the reclaim path does per-block device reads (host-tier
        # swap-outs) that must neither block concurrent submits nor —
        # via the heartbeat stamp — hide a wedged device from the
        # watchdog. The allocator and prefix index carry their own
        # locks; only the queue/slot mutation below needs _lock.
        # Radix planning is a first-class anatomy phase (prefix_plan):
        # PR 11 made it a real admission cost the waterfall must not
        # hide inside "admit".
        # what the admission's prefill caches: the prompt or, under block
        # diffusion, its whole blocks (the remaining ``P mod B`` tokens
        # enter the first block as rows already fixed)
        head = req.prompt
        if self.diffusion is not None:
            head = req.prompt[: len(req.prompt) // self._step_positions * self._step_positions]
        with self._phase("sched.prefix_plan", request=req.id) as p_plan:
            plan = self.engine.prefix_plan(head)
        with self._phase("sched.admit", request=req.id):
            need = (
                self.engine.cache_config.blocks_for(len(head) + 1)
                - plan.n_resident
            )
            blocks = self.engine.allocator.allocate(need)
            if blocks is None:
                # unreferenced cached prefixes are the reclaim of last
                # resort BEFORE making the head wait (or preempt): LRU
                # entries offload to host and their device blocks free
                with self._stamped():
                    reclaimed = self.engine.reclaim_cached(
                        need - self.engine.allocator.num_free
                    )
                if reclaimed:
                    blocks = self.engine.allocator.allocate(need)
            if blocks is None:
                # admission-rejection blame: remember when the FCFS head
                # first stalled on blocks and how many it is short — the
                # eventual admit stamps "queued Nms waiting for K
                # block(s)" on the request's trace
                if self.obs_enabled and req.cache_wait_start is None:
                    req.cache_wait_start = self.clock()
                req.cache_wait_short = need - self.engine.allocator.num_free
                return False
            with self._lock:
                if not self._queue or self._queue[0] is not req or not self._free_slots:
                    # the head changed while blocks were gathered (fleet
                    # steal_queue / adopt mutate the queue from other
                    # threads): hand the blocks back, retry next iteration
                    self.engine.allocator.free(blocks)
                    return False
                self._queue.popleft()
                slot = self._free_slots.pop()
            # the queue wait ends here, with a slot and blocks in hand:
            # what follows (prefix assembly, the prefill) is service
            popped_at = self.clock()
            if req.cache_wait_start is not None:
                wait_s = max(0.0, popped_at - req.cache_wait_start)
                blame = self.capacity.note_admission_wait(wait_s, req.cache_wait_short)
                req.trace.event(
                    "cache_wait", wait_s=wait_s,
                    blocks_short=req.cache_wait_short, blame=blame,
                )
                req.cache_wait_start = None
        # assemble the block table from the prefix plan: swap-ins + the
        # COW boundary copy are device work, so the watchdog's stall
        # heartbeat covers them like any other step
        with self._phase("sched.prefix_plan", request=req.id) as p_prep:
            with self._stamped():
                prep = self.engine.prepare_prefix(head, plan, blocks, slot=slot)
        with self._phase("sched.admit", request=req.id):
            if prep is None:
                # a mid-assembly swap-in fallback could not replace the
                # lost shared blocks: everything was handed back — requeue
                # the head and retry next iteration
                self._free_slots.append(slot)
                with self._lock:
                    self._queue.appendleft(req)
                return False
            table, shared_idx, entries, prefix_len = prep
            # blocks first, then the request: cache_report treats a set
            # _admitting as implying its blocks are readable (private
            # blocks only — shared ones are the prefix index's to report)
            self._admitting_blocks = [
                b for i, b in enumerate(table) if i not in shared_idx
            ]
            self._admitting = req
        try:
            with self._phase("sched.admit", request=req.id):
                pf_mask = None
                if req.mask_state is not None:
                    # the prefill samples this stream's next token in-jit:
                    # mask it exactly like a decode step would
                    pf_mask = req.mask_state.mask_row(req.sampling.eos_id)
                    self.constrained_stats.incr("masked_steps")
                # a dispatched fold_in; the same key on every retry
                key = req.sample_key()
            with phase("sched.device_step", request=req.id) as p_dev:
                # (block diffusion: a prompt shorter than a block has nothing to cache)
                token = self._device(
                    lambda: self.engine.prefill_one(
                        head, table, req.sampling, key,
                        prefix_len=prefix_len, mask=pf_mask, slot=slot,
                    )
                ) if head else None
        except Exception as e:
            self._admitting = None
            self._admitting_blocks = None
            self.engine.release_admission(table, shared_idx, entries, slot=slot)
            self._free_slots.append(slot)
            if self.supervisor.failed:
                # half-open probe against a still-dead engine: a HELD
                # request must not eat the raw device error for probing.
                # Back to the front; the probe's recorded failure just
                # re-opened the breaker, so admission waits out another
                # recovery window before the next attempt.
                with self._lock:
                    self._queue.appendleft(req)
                return False
            if req.n_generated > 0:
                # a replayed/preempted stream whose consumer already
                # holds tokens: a raw prefill error must not cut it off
                # mid-stream. Requeue it and treat the failure as
                # engine-level — budgeted restart + backoff (give-up
                # fails running streams typed and holds the queue).
                with self._lock:
                    self._queue.appendleft(req)
                self.supervisor._restart_and_replay(e, "prefill")
                return True
            if req.handle._fail(e):
                self.stats.incr("failed")
            return True  # did work (and must not spin on the same head)
        # the prefill's dispatch/block/execute/readback spans join the
        # iteration's anatomy timeline with their real offsets
        execute_s = self._engine_spans() if head else 0.0
        with self._phase("sched.admit", request=req.id):
            if head and not bool(self.engine.last_finite[0]):
                # poisoned prompt: the prefill's logits went non-finite, and
                # a single-sequence step needs no bisection to assign blame
                self._admitting = None
                self._admitting_blocks = None
                self.engine.release_admission(table, shared_idx, entries, slot=slot)
                self._free_slots.append(slot)
                err = PoisonedRequestError(
                    f"request {req.id} produced non-finite logits at prefill",
                    request_id=req.id, step="prefill", reason="nan_logits",
                )
                req.trace.event("quarantine", step="prefill", reason="nan_logits")
                err.flight_snapshot = self.flight.incident(
                    "quarantine", request_id=req.id, step="prefill",
                    reason="nan_logits",
                )
                if req.handle._fail(err):
                    self.stats.incr("failed")
                    self.recovery_stats.incr("quarantined")
                return True
            # the prompt's freshly written full blocks join the radix index
            # AFTER the finiteness gate — poisoned K/V must never become
            # shared content another request could reuse (reuse telemetry
            # also counts here, so failed admissions never inflate it)
            self.engine.register_prefix(
                head, table, shared_idx, entries, prefix_len=prefix_len, slot=slot
            )
            state = _Running(
                req, slot, table, cached_len=len(head),
                admitted_seq=next(self._admitted_seq),
                shared_idx=shared_idx, shared_entries=entries,
            )
            if self.diffusion is not None:
                state.blk = self._first_block(req, len(head))
                state.rule = (self.diffusion.rows_per_forward(req.sampling.denoising_steps),
                              self.diffusion.threshold_of(req.sampling.remasking, req.sampling.threshold))
            self._note_admission()
            self._running[slot] = state
            # clear only AFTER slot registration: cache_report reads
            # _running first and dedupes by request id, so the blocks are
            # visible (as a provisional or real row, never both) for the
            # whole admission — residency keeps summing to used under
            # concurrent scrapes
            self._admitting = None
            self._admitting_blocks = None
            if self.supervisor.failed:  # a dead engine just served a prefill
                self.supervisor.note_engine_recovered()
            self.journal.record(req, state.admitted_seq)
            if req.handle.done():  # watchdog reaped it while the prefill ran
                self._release(state)
                return True
            was_first = req.n_generated == 0
            now = self.clock()
            req.trace.mark_admit(
                slot=slot, prompt_len=len(req.prompt),
                preemptions=req.preemptions, replays=req.replays,
            )
            req.journey.hop(
                "admit", slot=slot, prompt_len=len(req.prompt),
                replica=self.fault_scope, preemptions=req.preemptions,
                replays=req.replays,
            )
            if self.obs_enabled and was_first and req.preemptions == 0 and req.replays == 0:
                # first-life admission only: a recompute re-admission is a
                # scheduling event, not client-visible queueing
                self.stats.observe(
                    "queue_time", max(0.0, popped_at - req.submitted_at),
                    exemplar=req.journey.journey_id,
                )
            if self.diffusion is None:
                self._emit_token(state, token)
                req.trace.note_tokens(1, "prefill")
            req.journey.hop(
                "prefill", prompt_len=len(req.prompt),
                prefix_reused=prefix_len, replica=self.fault_scope,
            )
            if self.obs_enabled and was_first and self.diffusion is None:  # (a diffusion prefill yields no token: _scatter_block)
                # gated like tpot (trace-derived in _finish) so disabling
                # observability drops all three SLO windows together, not
                # a confusing two of three
                self.stats.observe(
                    "ttft", max(0.0, now - req.submitted_at),
                    exemplar=req.journey.journey_id,
                )
            self.flight.record_step(
                "prefill",
                phases=_flight_phases(
                    [p_plan.span, p_prep.span], {"device": p_dev.seconds}
                ),
                execute_s=execute_s, request_id=req.id,
                prompt_len=len(req.prompt), occupancy=len(self._running),
                queue_depth=len(self._queue),
                blocks_free=self.engine.allocator.num_free,
                prefix_reused=prefix_len,
            )
            if self.diffusion is None:
                self.token_rate.record(1)
            if req.finished():
                self._finish(state)
            elif self.handoff_sink is not None:
                # disaggregated prefill pool: this replica's job ends at the
                # first token. Pack the prompt's KV into the CRC-stamped
                # wire format while the blocks are still resident, hand the
                # slot back, and ship (request, payload) to the handoff
                # supervisor — the stream continues on the decode pool.
                with self._stamped():
                    payload = self.engine.pack_kv_blocks(
                        state.blocks, state.cached_len
                    )
                self._release(state)
                req.trace.event(
                    "kv_handoff_pack", n_blocks=len(payload.blocks),
                    payload_bytes=payload.nbytes,
                )
                req.journey.hop(
                    "kv_handoff_pack", n_blocks=len(payload.blocks),
                    payload_bytes=payload.nbytes, replica=self.fault_scope,
                )
                sink = self.handoff_sink
                try:
                    sink(req, payload)
                except Exception as e:
                    # the sink must never kill the loop; a sink crash fails
                    # the stream typed instead of losing it silently
                    if req.handle._fail(e):
                        self.stats.incr("failed")
        return True

    def _admit_imported(self, req: Request) -> bool:
        """Disaggregated decode-pool admission: commit a handed-off
        prompt's KV blocks into this engine's cache (CRC-verified per
        block, resharded onto this engine's head partitioning by the
        jitted block writer) and seat the stream directly in a decode
        slot — no prefill device call. Any failure — injected fault,
        CRC mismatch, geometry mismatch — rejects the import and falls
        back to the recompute-prefill path, which replays the stream
        byte-exactly from the request object."""
        payload = req.imported_kv
        with self._phase("sched.admit", request=req.id) as p_admit:
            need = self.engine.cache_config.blocks_for(payload.n_positions + 1)
            blocks = self.engine.allocator.allocate(need)
            if blocks is None:
                with self._stamped():
                    reclaimed = self.engine.reclaim_cached(
                        need - self.engine.allocator.num_free
                    )
                if reclaimed:
                    blocks = self.engine.allocator.allocate(need)
            if blocks is None:
                if self.obs_enabled and req.cache_wait_start is None:
                    req.cache_wait_start = self.clock()
                req.cache_wait_short = need - self.engine.allocator.num_free
                return False
            with self._lock:
                if not self._queue or self._queue[0] is not req or not self._free_slots:
                    self.engine.allocator.free(blocks)
                    return False
                self._queue.popleft()
                slot = self._free_slots.pop()
            try:
                faults.inject(
                    faults.GENERATION_KV_IMPORT, (req.id, len(payload.blocks))
                )
                if payload.block_size != self.engine.cache_config.block_size:
                    raise ValueError(
                        f"handoff block size {payload.block_size} != this "
                        f"engine's {self.engine.cache_config.block_size}"
                    )
                if len(payload.blocks) < self.engine.cache_config.blocks_for(
                    payload.n_positions
                ):
                    raise ValueError("handoff payload is missing blocks")
                n_import = self.engine.cache_config.blocks_for(payload.n_positions)
                wire = payload.blocks[:n_import]
                for pb in wire:
                    if not pb.verify():
                        raise ValueError(
                            "imported KV block failed CRC verification"
                        )
                # every block CRC-verified BEFORE any device write, then one
                # batched program commits the whole payload — a decode-pool
                # replica pays one dispatch per adopted stream between steps
                with self._stamped():
                    self.engine.import_kv_blocks(blocks[:n_import], wire)
            except Exception as e:
                # reject the import: hand everything back and requeue for
                # the recompute path (this is the replay the clean-handoff
                # adopt() deliberately did not count)
                req.imported_kv = None
                self.engine.allocator.free(blocks)
                with self._lock:
                    self._free_slots.append(slot)
                    self._queue.appendleft(req)
                self.recovery_stats.incr("kv_imports_rejected")
                if req.n_generated > 0:
                    req.replays += 1
                    req.trace.note_replay()
                    self.recovery_stats.incr("replayed_tokens", req.n_generated)
                req.trace.event(
                    "kv_import_rejected", reason=type(e).__name__,
                    n_blocks=len(payload.blocks),
                )
                return True
            req.imported_kv = None
            self.recovery_stats.incr("kv_imports")
            state = _Running(
                req, slot, blocks, cached_len=payload.n_positions,
                admitted_seq=next(self._admitted_seq),
            )
            self._note_admission()
            self._running[slot] = state
            self.journal.record(req, state.admitted_seq)
            if req.handle.done():  # reaped while blocks were in flight
                self._release(state)
                return True
            req.trace.mark_admit(
                slot=slot, prompt_len=len(req.prompt),
                preemptions=req.preemptions, replays=req.replays,
            )
            req.trace.event(
                "kv_import", n_blocks=len(payload.blocks),
                n_positions=payload.n_positions, payload_bytes=payload.nbytes,
            )
            req.journey.hop(
                "admit", slot=slot, prompt_len=len(req.prompt),
                replica=self.fault_scope, imported=True,
                n_blocks=len(payload.blocks),
            )
        self.flight.record_step(
            "kv_import", phases={"admit": p_admit.seconds},
            request_id=req.id, prompt_len=len(req.prompt),
            occupancy=len(self._running), queue_depth=len(self._queue),
            blocks_free=self.engine.allocator.num_free,
        )
        return True

    def _emit_token(self, state: _Running, token: int, later: bool = False) -> None:
        """``later``: a decode step's token, one of a batch's, which its
        stream hears of when this thread next parks for the device
        (:meth:`_emit_late`); a prefill's first token and a verify
        window's go out at once."""
        state.req.generated.append(int(token))
        if later:
            state.req.handle._emit_later(int(token))
            self._late.append(state.req.handle)
        else:
            state.req.handle._emit(int(token))
        # durable serving: the journal mirrors the token delta into its
        # WAL buffer (a no-op on the base journal) — host bookkeeping
        # that the overlap pipeline hides under device execution, like
        # the mask advance below; the write+fsync happens once per step
        # in journal.flush_step()
        self.journal.note_token(state.req, int(token))
        if state.req.mask_state is not None:
            self._advance_mask(state.req, int(token))

    def _advance_mask(self, req: Request, token: int) -> None:
        """Advance a constrained request's automaton over one emitted
        token — host bookkeeping that the overlap pipeline hides under
        device execution. NEVER raises: emit paths run deep inside
        admission/scatter flows where an exception would take down the
        batch, so a refused advance (injected generation.mask_advance
        fault or replay divergence) parks a typed error on the request
        for the step loop's quarantine sweep (_sweep_mask_errors) —
        blast radius of ONE stream. A cleanly exhausted grammar
        (accepting, no live continuation) instead clamps the budget so
        the stream completes this step."""
        ms = req.mask_state
        try:
            ms.advance(token, req.sampling.eos_id)
        except Exception as e:
            reason = (
                "mask_dead_end" if isinstance(e, MaskDeadEndError)
                else "mask_advance"
            )
            self.constrained_stats.incr("dead_end_failures")
            req.mask_error = PoisonedRequestError(
                f"request {req.id} grammar refused emitted token "
                f"{token}: {e}",
                request_id=req.id, step="mask", reason=reason,
            )
            return
        if ms.exhausted() and not ms.done:
            # the grammar has exactly one continuation left (EOS, when
            # the request has one): end the stream deterministically
            # instead of decoding against an everything-banned row
            req.max_new = req.n_generated

    def _plan_speculation(self) -> None:
        """Decide each running sequence's draft count for THIS step:
        its adaptive k, capped by the remaining token budget (never
        draft past max_new), the sequence-length ceiling, and — in
        _grow — cache pressure."""
        for state in self._running.values():
            req = state.req
            if req.drafter is None:
                state.step_k = 0
                continue
            budget = req.max_new - req.n_generated  # >= 1 while running
            pos_room = (self.engine.max_seq_len - 1) - state.cached_len
            state.step_k = max(0, min(req.spec_k, budget - 1, pos_room))
            # degrade ladder: level 1 caps the window, level 2 disables
            # drafting outright — exact either way (PR 3's acceptance
            # rule: any k, including 0, emits the same greedy stream)
            cap = self.overload.spec_cap()
            if cap is not None:
                state.step_k = min(state.step_k, cap)

    def _grow(self) -> None:
        """Ensure every running sequence has cache blocks for its next
        window — up to step_k + 1 new positions. Under pressure, first
        shrink the window (cap speculation), then preempt-by-recompute."""
        for state in list(self._running.values()):
            if self._running.get(state.slot) is not state:
                continue  # preempted earlier in this sweep
            while True:
                # (a block step writes a whole block of positions ahead)
                need = self.engine.cache_config.blocks_for(
                    state.cached_len + state.step_k + self._step_positions
                )
                if len(state.blocks) >= need:
                    break
                # (an unreferenced cached prefix goes before anyone's
                # window shrinks or a live sequence is preempted)
                got, _ = self._take_block()
                if got is not None:
                    state.blocks.extend(got)
                    continue
                if state.step_k > 0:
                    # cap on cache pressure: give up drafts before
                    # evicting anyone
                    state.step_k -= 1
                    continue
                if not self._preempt_youngest(exclude=state):
                    # nothing left to evict but this sequence itself:
                    # recompute it later when capacity returns
                    self._preempt_self(state)
                    break

    def _take_block(self) -> Tuple[Optional[List[int]], int]:
        """One block for a running sequence's growth, sequential
        (``_grow``) or pipelined (``_try_pipeline``): from the free
        list, else one unreferenced cached prefix is evicted (offloaded
        to the host tier below its budget) and the block taken again.
        Returns the block (None: nothing evictable is left) and the
        blocks the eviction freed. Sound with a step in flight: a
        victim has ``refs == 0``, so no running stream's table names it
        and the in-flight step neither reads nor writes it; the step
        that will write it is dispatched after, on that step's cache
        outputs, so the device orders the two; and a swap-out read is
        enqueued on those same outputs, behind it. That read is the one
        part that can wedge, and it wedges behind the step in flight,
        whose own heartbeat stamp stands in for it (``_stamped`` would
        clear that stamp on exit and shift the sequence the step's
        stall flags are scoped by). With no step in flight the reclaim
        takes a stamp of its own for the watchdog."""
        got, freed = self.engine.allocator.allocate(1), 0
        if got is None:
            if self._pipe is not None:
                freed = self.engine.reclaim_cached(1)
            else:
                with self._stamped():
                    freed = self.engine.reclaim_cached(1)
            if freed:
                got = self.engine.allocator.allocate(1)
        return got, freed

    def _preempt_self(self, state: _Running) -> None:
        self.capacity.note_preempt(len(state.blocks))
        self.engine.stash_prefix(state)  # see _preempt_youngest
        self._release(state)
        req = state.req
        req.block_resume = (state.cached_len, state.blk) if state.blk is not None else None
        req.prompt = req.original_prompt + list(req.generated)
        req.mask_state = None  # rebuilt from `generated` at re-admission
        req.preemptions += 1
        self.preemptions += 1
        req.trace.note_preempt()
        with self._lock:
            self._queue.appendleft(req)

    def _collect_slots(self, order, covered=()):
        """Slot-indexed arrays every batched device step needs: the
        seed token (last emitted, not yet cached), its cache position,
        block tables, the live mask, and per-slot sampling params —
        shared by the decode, verify and pipelined assemblies so the
        paths cannot drift. ``seeds``/``counts`` feed the engine's in-jit
        sampling-key derivation (ISSUE 13): byte-identical keys to the
        old host fold_in, with zero host key assembly on the hot path.
        ``covered``: the slots a step in flight covers. Each is one
        token ahead of what the host has bookkept, in position and in
        count (its seed token is that step's output, on the device: the
        one here is stale and its caller passes the device's)."""
        b = self.engine.max_batch_slots
        last = np.zeros((b,), np.int32)
        start = np.zeros((b,), np.int32)
        tables = np.zeros((b, self.engine.max_blocks_per_seq), np.int32)
        active = np.zeros((b,), bool)
        temps = np.zeros((b,), np.float32)
        top_ks = np.zeros((b,), np.int32)
        seeds = np.zeros((b,), np.uint32)
        counts = np.zeros((b,), np.int32)
        for state in order:
            i = state.slot
            req = state.req
            pend = 1 if i in covered else 0
            last[i] = req.generated[-1] if req.generated else req.prompt[-1]
            start[i] = state.cached_len + pend  # next cache position
            tables[i, : len(state.blocks)] = state.blocks
            active[i] = True
            temps[i] = req.sampling.temperature
            top_ks[i] = req.sampling.top_k
            seeds[i] = req.sampling.seed & 0xFFFFFFFF
            counts[i] = req.n_generated + pend
        return last, start, tables, active, temps, top_ks, seeds, counts

    def _decode_mask(self, order):
        """[B, V] grammar-mask rows for one decode step, or None when
        no live slot is constrained — the engine then stages its one
        cached zeros array: no per-step upload, no new program, the
        common case pays an any() over the batch."""
        if not any(s.req.mask_state is not None for s in order):
            return None
        mask = np.zeros(
            (self.engine.max_batch_slots, self.engine.cfg.vocab_size),
            np.float32,
        )
        n = 0
        for state in order:
            ms = state.req.mask_state
            if ms is not None:
                mask[state.slot] = ms.mask_row(state.req.sampling.eos_id)
                n += 1
        self.constrained_stats.incr("masked_steps", n)
        return mask

    def _quarantine_nan(self, kind: str, order) -> bool:
        """Act on the engine's per-slot NaN blame vector after a step
        that returned normally. Partial blame pins the poison on the
        flagged request(s): quarantine them, keep everyone else (their
        tokens from this step are valid and the step is about to scatter
        them). Whole-batch blame is not data-dependent — restart and
        journal-replay instead (returns True: skip the scatter)."""
        ok = self.engine.last_finite
        live = [s for s in order if self._running.get(s.slot) is s]
        blamed = [s for s in live if not bool(ok[s.slot])]
        if not blamed:
            return False
        # the failing step must be ON the flight ring before any
        # quarantine/restart incident freezes its snapshot
        self._flight_step()
        self.flight.record_event(
            "nan_blame", step=kind,
            request_ids=[s.req.id for s in blamed], live=len(live),
        )
        if len(blamed) == len(live) and len(live) > 1:
            self.supervisor.handle_engine_nan(kind)
            return True
        for state in blamed:
            self._quarantine(
                state,
                PoisonedRequestError(
                    f"request {state.req.id} produced non-finite logits at {kind} step",
                    request_id=state.req.id, step=kind, reason="nan_logits",
                ),
            )
        return False

    def _decode_step_fns(self, order):
        """The sequential decode step and its bisection probe over
        ``order``, built from ONE slot collection — shared by the
        sequential iteration and the pipeline-failure re-run so the two
        can never drift. (The old host "sample" phase — per-request
        fold_in + stack — is gone: sampling keys derive in-jit from
        (seed, count).)"""
        b = self.engine.max_batch_slots
        (tokens, positions, tables, active, temps, top_ks, seeds,
         counts) = self._collect_slots(order)
        mask = self._decode_mask(order)

        def step():
            # (engine.decode's two halves called from this frame: the
            # first call traces, and a frame more is paid in set-up)
            return self.engine.finish_decode(self.engine.decode_async(
                tokens, positions, tables, active, temps, top_ks, seeds,
                counts, mask=mask,
            ))

        def probe(subset):
            # blame-assignment probe: same step with only ``subset``
            # active; outputs discarded, cache writes idempotent (the
            # SAME mask as the real step, so bisection re-runs are
            # deterministic for constrained slots too). The slots'
            # convolution state is no idempotent write (a step shifts
            # it): a probe that ran puts back what it found (its
            # engine donates nothing, or no probe would run)
            act = np.zeros((b,), bool)
            for s in subset:
                act[s.slot] = True
            conv = self.engine.cache.state.get("conv")
            self._probe_call(
                lambda: self.engine.decode(
                    tokens, positions, tables, act, temps, top_ks, seeds,
                    counts, mask,
                )
            )
            if conv is not None:
                self.engine.cache.state["conv"] = conv

        return step, probe

    def _step_once(self) -> bool:
        """One sequential step of the engine's kind (a decode step, or
        a block-diffusion engine's block step) across all running slots."""
        if not self._running:
            return False
        with self._phase("sched.schedule"):
            order = sorted(self._running.values(), key=lambda s: s.slot)
            step, probe = self._step_fns(order)
        info = self._step_info
        info["kind"] = self._kind
        with phase("sched.device_step") as p_dev:
            out = self.supervisor.run_step(self._kind, step, order, probe)
        self._step_walls["device"] = p_dev.seconds
        if out is None:
            info["handled_failure"] = True  # quarantined or journal-replayed
        else:
            self._sequential_tail(order, out)
        return True

    def _sequential_tail(self, order, out) -> bool:
        """What follows a sequential step whose result the supervisor
        returned (``_step_once``, and the re-run of a failed pipelined
        step): the engine's spans adopted, NaN blame, the tokens
        scattered and counted. False where the blame took the step (a
        handled failure: nothing was scattered)."""
        info = self._step_info
        info["execute_s"] = self._engine_spans(decode_result=True)
        if self._quarantine_nan(self._kind, order):
            info["handled_failure"] = True
            return False
        with self._phase("sched.bookkeep"):
            n_live, _ = self._scatter(order, out)
        info["emitted"] = n_live
        self.token_rate.record(n_live)
        return True

    def _scatter_decode(self, order, out, defer_finish: bool = False):
        """Scatter one decode step's sampled tokens back onto the slot
        states (shared by the sequential step, the pipeline consume,
        and the pipeline-failure sequential re-run). Returns
        (n_emitted, finished_states). ``defer_finish`` is the pipeline
        case: a finished slot's blocks must not be released while a
        successor step is still in flight over them — the caller drains
        the frontier first, then finishes. A slot that ALREADY finished
        at a previous consume is skipped outright (its token in a
        drained in-flight step is one a sequential scheduler would
        never have decoded)."""
        n_live = 0
        finish = []
        for state in order:
            if self._running.get(state.slot) is not state:
                continue  # preempted/expired between collect and scatter
            if state.req.handle.done():
                continue  # watchdog-reaped mid-step; _expire releases it
            if state.req.finished():
                continue  # finished at a previous pipeline consume
            state.cached_len += 1
            self._emit_token(state, int(out[state.slot]), later=True)
            state.req.trace.note_tokens(1, "decode")
            n_live += 1
            if state.req.finished():
                finish.append(state)
        if not defer_finish:
            for state in finish:
                self._finish(state)
        return n_live, finish

    # ----------------------------------------------------- overlap pipeline
    def _nonsteady(self, now: float) -> bool:
        """True when THIS iteration must run the sequential path (after
        a deterministic frontier drain): any event whose handling
        mutates slot/block state the in-flight step depends on, or
        whose semantics are defined sequentially — admission, finish,
        cancel/deadline, speculation, shutdown, a declared-dead
        engine."""
        if self._draining or self._hard_stop or self.supervisor.failed:
            return True
        if self._queue:
            with self._lock:
                queued = list(self._queue)
            for req in queued:
                if req.handle.done() or req.cancelled or (
                    req.deadline is not None and now >= req.deadline
                ):
                    return True  # queue expiry needs the sequential sweep
            if self._free_slots and self.breaker.ready():
                return True  # an admission could actually place
        for s in self._running.values():
            req = s.req
            if (
                req.handle.done()
                or req.cancelled
                or self._ended(req)
                or (req.deadline is not None and now >= req.deadline)
                or req.drafter is not None
                # constrained slots are non-steady by construction: the
                # pipeline dispatches step N+1 with step N's token still
                # device-resident, and the host cannot advance the
                # automaton (= build N+1's mask row) over a token it has
                # not seen. Sequential stepping keeps constrained
                # streams byte-identical overlap on/off — the existing
                # drafter clause rides the same reasoning.
                or req.grammar is not None
            ):
                return True
        return False

    def _emit_late(self) -> None:
        """Put the decode tokens bookkept since the last call on their
        streams' queues. Called where the next thing this thread does
        is park for the device: before a frontier is consumed (in steady
        state right after the dispatch of step N+2, with step N's
        tokens, and the park is ``block(N+1)``; at a drain or the stream
        tail without a dispatch before it), by the engine between the
        dispatch and the wait of a blocking call (``on_dispatched``: a
        drained or a sequential step's tokens go out behind the
        admission's prefill or the next step), before a frontier is
        discarded, and at the end of an iteration that no dispatch is
        sure to follow. So the handler threads take their turn at the
        interpreter's lock while the device works, and not between a
        step's bookkeeping and the next dispatch. A handle that is
        settled first releases its own tokens (``_finish``, ``_fail``):
        a stream reads them in order and whole before its end or error.
        Bookkeeping, in its span."""
        if not self._late:
            return
        late, self._late = self._late, []
        with self._phase("sched.bookkeep"):
            self.emits_deferred += sum(h._release_held() for h in late)

    def _discard_frontier(self) -> None:
        """Drop the in-flight step WITHOUT bookkeeping: its sampled
        tokens are never emitted, so the next sequential step recomputes
        them byte-identically (the step's K/V writes are idempotent
        rewrites of the same positions from the same inputs). Used when
        the in-flight result is tainted (NaN blame, stall, failure) or
        moot (shutdown, engine reset). Swallows the step's own error —
        the caller decides how the failure is handled. The tokens of
        the step BEFORE it were bookkept and are owed to their streams:
        no dispatch may follow, so they go out first."""
        self._emit_late()
        f, self._pipe = self._pipe, None
        if f is None:
            return
        try:
            jax.block_until_ready((f.handle.out, f.handle.ok))
        except Exception:
            # restore the pre-step cache refs so a sequential re-run
            # reads intact inputs — but only while this step's outputs
            # are still current: a predecessor's consume failure may
            # already have rolled the whole chain back to OLDER intact
            # refs, and restoring forward would resurrect errored
            # arrays. (Non-donating engines; a donating engine only
            # reaches here on the reset + replay path.)
            h = f.handle
            if h.prev_k is not None and self.engine.cache.k is h.ck:
                self.engine.rollback_decode(h)
        self.pipe_discards += 1
        self._heartbeat = None

    def _drain_frontier(self, reason: str) -> None:
        """Deterministically empty the pipeline before a non-steady
        event: consume the in-flight step with FULL bookkeeping (tokens
        emitted, finishes resolved), so the scheduler state afterwards
        is exactly what a sequential scheduler would hold at the same
        point in every stream. Never raises — device failures take the
        pipeline-failure path (sequential supervisor semantics)."""
        f, self._pipe = self._pipe, None
        if f is None:
            return
        self.pipe_drains[reason] += 1
        try:
            self._consume_and_finish(f)
        except Exception as e:
            self._pipeline_failure(e, f.seq0)

    def _consume_decode(self, f: "_Frontier"):
        return self.engine.consume_decode(f.handle)

    def _consume_and_finish(self, f: "_Frontier"):
        """Consume one in-flight step: blocked (double-buffered)
        readback, watchdog/stall arbitration, NaN blame, token scatter
        — then, if any stream finished, drain the successor frontier
        before releasing its blocks. Returns tokens emitted, or None
        when a failure was fully handled here (restart or whole-batch
        blame). Device errors propagate to the caller's
        pipeline-failure handling."""
        self._emit_late()
        faults.inject(faults.GENERATION_ASYNC_READBACK, (self._kind, len(f.states)))
        with phase("sched.device_step") as p_dev:
            out = self._consume(f)
        self._add_wall("device", p_dev.seconds)
        # completion stamp (satellite: dispatch AND completion): the
        # successor — if any — only starts device work now, so its
        # heartbeat age and execute span are measured from here; a
        # one-deep pipeline at long execute times is therefore never
        # misread as a wedged loop, while a consume that never returns
        # ages its own dispatch stamp until the watchdog trips
        nf = self._pipe
        if nf is not None:
            self._heartbeat = (nf.hb_seq, self.clock())
            nf.handle.t_started = time.perf_counter()
        else:
            self._heartbeat = None
        self._step_info["execute_s"] = (
            self._step_info.get("execute_s", 0.0)
            + self._engine_spans(decode_result=True)
        )
        if self.supervisor._consume_stall(f.seq0):
            # the watchdog tripped while this chain was in flight: the
            # late result is stale — discard everything and replay
            # (exactly run_step's post-success stall arbitration). The
            # flight record marks the restart-inflated iteration, like
            # every handled failure.
            self._step_info["handled_failure"] = True
            self._discard_frontier()
            self.supervisor._restart_and_replay(
                StalledStepError(f"{self._kind} step exceeded the watchdog stall timeout"),
                self._kind,
            )
            return None
        ok = self.engine.last_finite
        live = [s for s in f.states if self._running.get(s.slot) is s]
        if any(not bool(ok[s.slot]) for s in live):
            # the successor was dispatched from this step's (poisoned)
            # token carry: discard it wholesale, then apply the standard
            # blame rules — partial blame quarantines and keeps the
            # survivors' tokens from THIS step, whole-batch restarts
            self._discard_frontier()
            if self._quarantine_nan(self._kind, f.states):
                self._step_info["handled_failure"] = True
                return None
        with self._phase("sched.bookkeep"):
            n_live, finish = self._scatter(f.states, out, defer_finish=True)
            self.token_rate.record(n_live)
        # the consumed step's handle goes HERE, in a span, and not
        # wherever the frame that holds the frontier returns. No free
        # waits for the device (0.03 ms an array beside a program in
        # flight or none: chip_smoke.py --release-probe), but every
        # jax.Array's destructor gives up the interpreter's lock, and
        # this thread has it back only after the threads that were
        # waiting for it have had their turn. The span reads that turn:
        # the streams' handlers are woken elsewhere (_emit_late), so it
        # is short unless they have not finished the one before
        with self._phase("sched.release") as p_rel:
            f.handle = None
        self.release_wait_s += p_rel.seconds
        if finish:
            # finish/EOS is a non-steady event: the successor step may
            # still be writing into the finishing streams' blocks —
            # drain it (bookkept under spans of its own; its tokens for
            # finished slots are skipped by the scatter) before any
            # release
            if self._pipe is not None:
                self._drain_frontier("finish")
            with self._phase("sched.bookkeep"):
                for st in finish:
                    if self._running.get(st.slot) is st and not st.req.handle.done():
                        self._finish(st)
        return n_live

    def _dispatch_pipeline(self, live, prev: Optional["_Frontier"]) -> "_Frontier":
        """Dispatch the next decode step without blocking. With an
        unconsumed predecessor, the token array is its device-resident
        output (no host round trip at all) and the argument arrays are
        the predecessor's, bumped in place — steady state rebuilds
        nothing and uploads nothing: the bumped positions and counts
        are what the predecessor's program returned, which the engine
        kept on the device and finds equal (``uploads``
        ``carried_hits_total``); a step of another composition uploads
        what differs."""
        with self._phase("sched.stage"):
            sig = tuple((s.slot, s.req.id, len(s.blocks)) for s in live)
            covered = {s.slot for s in prev.states} if prev is not None else set()
            tokens_host = tokens_dev = None
            if prev is not None and prev.sig == sig:
                slots = prev.slots
                for s in live:  # same composition: everyone advances by one
                    slots[0][s.slot] += 1  # positions
                    slots[-1][s.slot] += 1  # counts
            else:
                tokens_host, *slots = self._collect_slots(live, covered)
            if prev is not None:
                tokens_host, tokens_dev = None, prev.handle.out
        hb_prev = self._heartbeat
        seq0 = prev.seq0 if prev is not None else self._hb_seq
        self._hb_seq += 1
        seq = self._hb_seq
        window = None
        if self.engine.window_config is not None:
            # the window layers' blocks behind each sequence's window go
            # back, and the block a position starts is taken, with the
            # predecessor in flight: scheduling work, in its span
            with self._phase("sched.schedule"):
                positions, _tables, active = slots[:3]
                window = self.engine.advance_windows(positions, active)
        self._heartbeat = (seq, self.clock())  # dispatch stamp
        try:
            handle = self.engine.decode_async(tokens_host, *slots, tokens_dev=tokens_dev, window=window)
        except Exception:
            self._heartbeat = hb_prev  # the step never went in flight
            self._hb_seq = seq  # seq stays burned; stall flags on it are void
            raise
        self._step_spans += [("dispatch", handle.t0, handle.t_disp), handle.post]
        self._step_children += handle.children
        self._add_wall("dispatch", handle.t_disp - handle.t0)
        return _Frontier(handle, list(live), slots, sig, seq, seq0)

    def _pipeline_failure(self, e: BaseException, since_seq: int) -> None:
        """A pipelined dispatch or consume failed. Discard what is in
        flight (restoring pre-step cache refs when possible), then give
        the failed step the EXACT sequential treatment from the point
        after its first failure (supervisor.resume_step): retryable
        errors re-run invisibly, hard errors pay the breaker-accounted
        retry -> bisect -> restart ladder. A donating engine skips
        straight to reset + journal replay — its failed step consumed
        its own input buffers."""
        self.flight.record_event("pipeline_failure", error=repr(e)[:200])
        self._discard_frontier()
        self._step_info["handled_failure"] = True
        if self.engine.donate:
            self.supervisor._restart_and_replay(e, self._kind)
            return
        order = [
            s for s in sorted(self._running.values(), key=lambda s: s.slot)
            if not s.req.handle.done() and not self._ended(s.req)
        ]
        if not order:
            return
        step, probe = self._step_fns(order)
        out = self.supervisor.resume_step(self._kind, e, step, order, probe, since_seq)
        if out is not None and self._sequential_tail(order, out):
            self._step_info["handled_failure"] = False

    def pipeline_stats(self) -> Dict:
        """The ``pipeline`` section of ``/v2/stats``: monotone totals of
        the overlap loop's decisions. ``decode_steps_total`` is every
        decode step dispatched (``engine.step_counts``) and
        ``block_steps_total`` every block step (an engine has one kind:
        the other reads 0), of which ``pipelined_steps_total`` went
        through the pipeline's dispatch (``_dispatch_pipeline``,
        ``_dispatch_block``: sent out beside the step before them);
        ``reclaims_total`` the blocks the pipeline's growth took from
        the prefix cache; ``drains_total`` the frontier drains by reason
        (``_drain_frontier``'s callers); ``emits_deferred_total`` the
        decode tokens that went to their streams where the thread next
        parked for the device (``_emit_late``) and ``emits_pending`` the
        handles that hold some still (0 after an iteration that leaves
        nothing running); ``release_wait_total_s`` the seconds this
        thread spent dropping consumed steps' handles (the
        ``ff.sched.release`` spans, counted with observability off
        too). Read by a scrape's thread:
        ``pipe_drains`` has its keys from the start, so the copy never
        meets a dict that changes size."""
        return {
            "decode_steps_total": self.engine.step_counts["decode"],
            "block_steps_total": self.engine.step_counts.get("block_step", 0),
            "pipelined_steps_total": self.pipe_dispatches,
            "reclaims_total": self.pipe_reclaims,
            "drains_total": dict(self.pipe_drains),
            "emits_deferred_total": self.emits_deferred,
            "emits_pending": len(self._late),
            "release_wait_total_s": self.release_wait_s,
        }

    def _plan_decode(self, order, covered):
        """The slots live at the NEXT decode dispatch, and whether the
        pool fell short of their growth. Budget-predicted finishes
        are excluded (sequential would have freed them before this
        step); EOS cannot be predicted and is handled at consume.
        Their block tables grow for the dispatch positions. An
        empty free list is the prefix cache's steady state, not
        pressure: the block comes from the cache's unreferenced
        entries, as in _grow, with the step in flight. Only a
        pool with nothing left to evict is short (and nobody
        grows after it): capping speculation and preempting
        mutate running slots, so that pressure drains and is
        handled sequentially. ``covered``: the slots of the step in
        flight, each one token ahead of what the host has bookkept."""
        live, short, blocks_for = [], False, self.engine.cache_config.blocks_for
        for s in order:
            pend = 1 if s.slot in covered else 0
            if s.req.n_generated + pend >= s.req.max_new:
                continue
            live.append(s)
            need = blocks_for(s.cached_len + pend + 1)
            if len(s.blocks) < need:
                short = self._grow_to(s, need, short)
        return live, short

    def _plan_block(self, order, covered):
        """``_plan_decode`` for a block step. What the step in flight
        FIXED is not known, and is not needed: that forward is a slot's
        commit iff every row of its block was fixed on entry to it,
        which is the host's ``blk.fixed`` since the consume before. A
        slot whose commit is in flight starts its next block at the
        next dispatch (a whole block of positions further on) or, its
        budget spent, ends with that commit and is left out (every
        token of the block left when its last row was fixed, so the
        count is final); an end-of-sequence token cannot be predicted
        and is handled at consume."""
        live, short, b, blocks_for = [], False, self._step_positions, self.engine.cache_config.blocks_for
        for s in order:
            committing = s.slot in covered and bool(s.blk.fixed.all())
            if committing and s.req.n_generated >= s.req.max_new:
                continue
            live.append(s)
            need = blocks_for(s.cached_len + b * committing + b)
            if len(s.blocks) < need:
                short = self._grow_to(s, need, short)
        return live, short

    def _grow_to(self, s: _Running, need: int, short: bool) -> bool:
        """The pipeline's block growth for one slot, to ``need`` blocks
        (``_take_block`` says why it is sound with a step in flight).
        Returns ``short``: nobody grows once the pool had nothing left
        to evict."""
        while not short and len(s.blocks) < need:
            got, freed = self._take_block()
            self.pipe_reclaims += freed
            if got is None:
                short = True
            else:
                s.blocks.extend(got)
        return short

    def _try_pipeline(self) -> Optional[bool]:
        """One overlapped iteration (decode steps, or a block-diffusion
        engine's block steps). Returns None when the
        iteration must run sequentially instead (the frontier is
        guaranteed drained by then); True when pipelined work happened.
        Steady state: dispatch step N+1 (token carry from step N's
        device output), then consume step N — its bookkeeping runs
        inside N+1's execute window instead of on the critical path."""
        now = self.clock()
        if self._nonsteady(now):
            # drain, then fall through to the sequential body in the
            # SAME iteration: the non-steady event (an admission, an
            # expiry, a verify step) must not wait an extra step —
            # join-mid-flight latency and TTFT keep their sequential
            # semantics. The drained consume's tokens/spans ride this
            # iteration's record.
            self._drain_frontier("nonsteady")
            return None
        order = sorted(self._running.values(), key=lambda s: s.slot)
        if not order:
            if self._pipe is not None:  # defensive: should be unreachable
                self._drain_frontier("idle")
            return None
        info = self._step_info
        f = self._pipe
        with self._phase("sched.schedule"):
            live, short = self._plan(order, {s.slot for s in f.states} if f is not None else set())
        if f is None and (short or not live):
            return None
        info["kind"] = self._kind
        if short:
            self._drain_frontier("pressure")
            return True
        new_f = None  # (the stream tail: nothing left to dispatch — consume only)
        if live:
            try:
                new_f = self._dispatch(live, f)
            except Exception as e:
                # dispatch failed host-side; the in-flight predecessor is
                # healthy — consume it first, then give the failed step the
                # sequential recovery treatment
                if f is not None:
                    self._pipe = None
                    try:
                        self._consume_and_finish(f)
                    except Exception as e2:
                        self._pipeline_failure(e2, f.seq0)
                        return True
                # the predecessor (if any) consumed cleanly and cleared its
                # own stall flags; only trips from here on concern the re-run
                self._pipeline_failure(e, self._hb_seq)
                return True
            self.pipe_dispatches += 1
        self._pipe = new_f
        if f is None:
            info["emitted"] = 0  # warm-up: tokens arrive next iteration
            return True
        try:
            n = self._consume_and_finish(f)
        except Exception as e:
            self._pipeline_failure(e, f.seq0)
            return True
        if n is not None:
            info["emitted"] = n
        return True

    def _trim_blocks(self, state: _Running) -> None:
        """Return trailing blocks a partially-accepted window no longer
        covers (their positions hold rejected-draft garbage the next
        window would rewrite anyway). Keeps allocator accounting exact
        when acceptance stops short of a block boundary. cached_len + 1,
        not cached_len: the next step always writes position cached_len,
        so trimming its block would hand it to a queued request at
        _admit and force an avoidable preemption one step later."""
        keep = max(1, self.engine.cache_config.blocks_for(state.cached_len + 1))
        if len(state.blocks) > keep:
            extra = state.blocks[keep:]
            del state.blocks[keep:]
            self.engine.allocator.free(extra)
            self.capacity.note_trim(len(extra))

    def _verify_mask(self, order, window, n_draft) -> Optional[np.ndarray]:
        """(batch, window, vocab) additive grammar bias for ONE verify
        step, or None when nothing running is constrained (the engine
        stages its cached all-zeros array — no new program, no upload).

        Position j of the window samples the token that FOLLOWS the
        first j window tokens, so row 0 is the current automaton
        state's mask and row j+1 is the mask at the state reached by
        consuming draft tokens 0..j — exactly the states a masked
        sequential decode would pass through if it accepted that
        prefix. Masking draft scoring and target sampling with the
        same rows is what keeps speculative acceptance byte-identical
        to the unspeculated constrained stream."""
        if not any(s.req.mask_state is not None for s in order):
            return None
        mask = np.zeros(
            (self.engine.max_batch_slots, self.engine.spec_window,
             self.engine.cfg.vocab_size),
            np.float32,
        )
        n = 0
        for state in order:
            ms = state.req.mask_state
            if ms is None:
                continue
            i = state.slot
            eos = state.req.sampling.eos_id
            mask[i, 0] = ms.mask_row(eos)
            draft = [int(t) for t in window[i, 1 : 1 + max(0, int(n_draft[i]))]]
            for j, st in enumerate(ms.states_along(draft, eos)):
                mask[i, j + 1] = ms.dfa.mask_row(st, eos)
            n += 1
        self.constrained_stats.incr("masked_steps", n)
        return mask

    def _verify_once(self) -> bool:
        """One speculative verification step across all running slots:
        draft (host), verify the batch × (k+1) window (ONE fixed-shape
        device call), then emit each slot's accepted run — truncated at
        mid-window EOS and the request's budget."""
        if not self._running:
            return False
        b = self.engine.max_batch_slots
        w = self.engine.spec_window
        info = self._step_info
        info["kind"] = "verify"
        with self._phase("sched.schedule"):
            order = sorted(self._running.values(), key=lambda s: s.slot)
            (last, start, tables, _active, temps, top_ks, seeds,
             counts) = self._collect_slots(order)
        window = np.zeros((b, w), np.int32)
        window[:, 0] = last
        n_draft = np.full((b,), -1, np.int32)  # -1 = inactive slot
        with self._phase("sched.draft"):
            for state in order:
                i = state.slot
                req = state.req
                draft: List[int] = []
                if state.step_k > 0 and req.drafter is not None:
                    try:
                        # original_prompt, NOT prompt: after a preemption the
                        # recompute prompt already folds in generated tokens
                        draft = list(
                            req.drafter.propose(
                                req.original_prompt + req.generated, state.step_k
                            )
                        )[: state.step_k]
                    except Exception:
                        # a dying drafter must not kill the scheduler loop:
                        # verification is exact with ANY draft, so a failed
                        # proposal degrades to a plain (zero-draft) step
                        self.stats.incr("drafter_errors")
                if req.mask_state is not None and draft:
                    # grammar-banned draft tokens would be rejected by the
                    # masked target anyway; trimming to the longest legal
                    # prefix just stops them wasting verify positions
                    draft = req.mask_state.filter_draft(draft, req.sampling.eos_id)
                window[i, 1 : 1 + len(draft)] = draft
                n_draft[i] = len(draft)
        # the per-window key matrix derives in-jit from (seed, count) —
        # the old host "sample" phase (vmapped fold_in + stack per
        # request) no longer exists
        info["drafted"] = int(np.maximum(n_draft, 0).sum())
        wmask = self._verify_mask(order, window, n_draft)

        def step():
            return self.engine.verify(
                window, start, n_draft, tables, temps, top_ks, seeds, counts,
                mask=wmask,
            )

        def probe(subset):
            nd = np.full((b,), -1, np.int32)  # everyone else inactive
            for s in subset:
                nd[s.slot] = n_draft[s.slot]
            self._probe_call(
                lambda: self.engine.verify(
                    window, start, nd, tables, temps, top_ks, seeds, counts,
                    mask=wmask,
                )
            )

        with phase("sched.device_step") as p_dev:
            result = self.supervisor.run_step("verify", step, order, probe)
        self._step_walls["device"] = p_dev.seconds
        if result is None:
            info["handled_failure"] = True
            return True  # failure handled: quarantined or journal-replayed
        info["execute_s"] = self._engine_spans(decode_result=True)
        out, n_emitted = result
        if self._quarantine_nan("verify", order):
            info["handled_failure"] = True
            return True
        n_accepted = 0
        n_live_tokens = 0
        with self._phase("sched.bookkeep"):
            for state in order:
                if self._running.get(state.slot) is not state:
                    continue  # preempted/expired between collect and scatter
                if state.req.handle.done():
                    continue  # watchdog-reaped mid-step; _expire releases it
                req = state.req
                i = state.slot
                m = int(n_emitted[i])
                toks = [int(t) for t in out[i, :m]]
                # budget truncation: never emit past max_new
                toks = toks[: req.max_new - req.n_generated]
                # mid-window EOS: keep through the FIRST eos, drop the rest
                eos = req.sampling.eos_id
                if eos is not None and eos in toks:
                    toks = toks[: toks.index(eos) + 1]
                accepted = max(0, m - 1)  # drafts the target agreed with
                n_accepted += accepted
                req.update_speculation(proposed=int(max(0, n_draft[i])), accepted=accepted)
                req.trace.note_speculation(int(max(0, n_draft[i])), accepted)
                emitted = 0
                for t in toks:
                    self._emit_token(state, t)
                    emitted += 1
                    if req.mask_state is not None and (
                        req.mask_error is not None or req.finished()
                    ):
                        # constrained stream ended mid-window — a parked
                        # advance error or the exhaustion clamp. The rest of
                        # the accepted run was sampled at states past the
                        # grammar's end: drop it, never surface or cache it.
                        break
                self.spec_stats.record_window(
                    proposed=int(max(0, n_draft[i])), accepted=accepted, emitted=emitted
                )
                req.trace.note_tokens(emitted, "verify")
                state.cached_len += emitted
                self._trim_blocks(state)
                n_live_tokens += emitted
                if req.finished():
                    self._finish(state)
        info["accepted"] = n_accepted
        info["emitted"] = n_live_tokens
        self.token_rate.record(n_live_tokens)
        return True

    # ------------------------------------------------------- block diffusion
    def _first_block(self, req: Request, base: int) -> _Block:
        """The block an admission starts with, at position ``base`` (the
        prefill cached everything below it): what a preemption kept of
        the block that was in flight there, else the remaining tokens
        of ``req.prompt`` (the prompt's last ``P mod B``, and after a
        replay the reply's emitted tokens too) as rows already fixed
        before mask tokens."""
        kept, req.block_resume = req.block_resume, None
        if kept is not None and kept[0] == base:
            return kept[1]
        # (a kept block below ``base``: all its rows had left, so the prefill was its commit)
        return _Block(self._step_positions, req.prompt[base:])

    def _collect_blocks(self, order, chained: bool = False):
        """Slot-indexed arrays of a block step: every live slot's block
        (tokens, fixed rows), its base and forwards, its table, and the
        request's sampling parameters and rule. ``chained``: for a step
        that follows one still in flight, whose blocks are on the device
        alone (``engine.block_async``'s ``prev``): None in their place."""
        b, width = self.engine.max_batch_slots, self._step_positions
        tables = np.zeros((b, self.engine.max_blocks_per_seq), np.int32)
        active = np.zeros((b,), bool)
        temps, top_ks, seeds = np.zeros((b,), np.float32), np.zeros((b,), np.int32), np.zeros((b,), np.uint32)
        n_fix, threshold = np.zeros((b,), np.int32), np.full((b,), 2.0, np.float32)
        tokens = fixed = base = forwards = None
        if not chained:
            tokens, fixed = np.zeros((b, width), np.int32), np.zeros((b, width), bool)
            base, forwards = np.zeros((b,), np.int32), np.zeros((b,), np.int32)
        for state in order:
            i, sp, blk = state.slot, state.req.sampling, state.blk
            if not chained:
                tokens[i], fixed[i], base[i], forwards[i] = blk.tokens, blk.fixed, state.cached_len, blk.forwards
            tables[i, : len(state.blocks)] = state.blocks
            active[i] = True
            temps[i], top_ks[i], seeds[i] = sp.temperature, sp.top_k, sp.seed & 0xFFFFFFFF
            n_fix[i], threshold[i] = state.rule
        return tokens, fixed, base, forwards, tables, active, temps, top_ks, seeds, n_fix, threshold

    def _block_step_fns(self, order):
        """``_decode_step_fns`` for a block step: the sequential step
        and its bisection probe over ``order``, from one collection."""
        tokens, fixed, base, forwards, tables, active, *params = self._collect_blocks(order)

        def step():
            # (engine.block_step's two halves called from this frame, as the decode step's are)
            return self.engine.finish_block(
                self.engine.block_async(tokens, fixed, base, forwards, tables, active, *params)
            )

        def probe(subset):
            # blame-assignment probe: the same step with only ``subset`` active; its
            # results are discarded and its writes are the block's provisional rows
            act = np.zeros_like(active)
            for s in subset:
                act[s.slot] = True
            self._probe_call(lambda: self.engine.block_step(tokens, fixed, base, forwards, tables, act, *params))

        return step, probe

    def _dispatch_block(self, live, prev: Optional["_Frontier"]) -> "_Frontier":
        """``_dispatch_pipeline`` for a block step. With an unconsumed
        predecessor the slots' blocks, flags, bases and forward counts
        are its program's device results, handed on as they are (the
        host holds those of the step BEFORE it): nothing of the state
        is compared or uploaded, whatever the live set; a table that
        grew, or a slot left out, uploads what differs."""
        with self._phase("sched.stage"):
            args = self._collect_blocks(live, chained=prev is not None)
        hb_prev = self._heartbeat
        seq0 = prev.seq0 if prev is not None else self._hb_seq
        self._hb_seq += 1
        seq = self._hb_seq
        self._heartbeat = (seq, self.clock())  # dispatch stamp
        try:
            handle = self.engine.block_async(*args, prev=prev.handle if prev is not None else None)
        except Exception:
            self._heartbeat = hb_prev  # the step never went in flight
            self._hb_seq = seq  # seq stays burned; stall flags on it are void
            raise
        self._step_spans.append(("dispatch", handle.t0, handle.t_disp))
        self._step_children += handle.children
        self._add_wall("dispatch", handle.t_disp - handle.t0)
        return _Frontier(handle, list(live), None, None, seq, seq0)

    def _consume_block(self, f: "_Frontier"):
        """Consume a block step, telling the engine which of its slots
        still run: a request that ended on its end-of-sequence token at
        the consume before (its release waits for this step, which was
        dispatched with it) is work a sequential loop never does, and
        the rule's counters leave it out."""
        running = np.zeros((self.engine.max_batch_slots,), bool)
        for s in f.states:
            running[s.slot] = self._running.get(s.slot) is s and not s.req.ended_on_eos()
        return self.engine.consume_block(f.handle, running)

    def _scatter_block(self, order, result, defer_finish: bool = False):
        """Scatter one block step's result back onto the slot states
        (``_scatter_decode`` for a block step: the same callers, the
        same answer, the same ``defer_finish``): each slot's newly final
        tokens emitted IN POSITION ORDER: a token leaves when it and
        every earlier position of the reply are fixed, several a step or
        none. A slot whose forward was its block's commit moves on to
        the next block; a request whose budget is spent ends with its
        last block's commit (the plain loop runs every block whole and
        drops the tokens past the budget), one that emits its
        end-of-sequence token ends there, inside the block: the rows a
        step already in flight ran for it are skipped."""
        n_emitted, finish, now = 0, [], self.clock()
        for state in order:
            if self._running.get(state.slot) is not state:
                continue  # preempted/expired between collect and scatter
            req, i, blk = state.req, state.slot, state.blk
            if req.handle.done():
                continue  # watchdog-reaped mid-step; _expire releases it
            if req.ended_on_eos():
                continue  # ended at a previous pipeline consume
            if result["commit"][i]:
                # the block's K/V is now what later blocks read
                state.cached_len += self._step_positions
                state.blk = _Block(self._step_positions)
                if req.finished():
                    finish.append(state)
                continue
            for j in np.nonzero(result["chosen"][i])[0]:
                blk.tokens[j], blk.fixed[j], blk.fixed_at[j] = result["tokens"][i, j], True, blk.forwards
            blk.forwards += 1
            # in order: the reply's next token is row (prompt + emitted - base) of the block
            first, emitted = req.n_generated == 0, 0
            while req.n_generated < req.max_new:
                j = len(req.original_prompt) + req.n_generated - state.cached_len
                if j >= self._step_positions or not blk.fixed[j]:
                    break
                req.fixed_at.append(blk.fixed_at[j])
                self._emit_token(state, int(blk.tokens[j]), later=True)
                emitted += 1
                if req.generated[-1] == req.sampling.eos_id:
                    break
            if not emitted:
                continue
            n_emitted += emitted
            req.trace.note_tokens(emitted, "block_step")
            if self.obs_enabled and first and req.preemptions == 0 and req.replays == 0:
                self.stats.observe("ttft", max(0.0, now - req.submitted_at), exemplar=req.journey.journey_id)
            if req.generated[-1] == req.sampling.eos_id:
                finish.append(state)  # an end inside a block: with what has left
        if not defer_finish:
            for state in finish:
                self._finish(state)
        return n_emitted, finish

    def _phase(self, name: str, **args) -> phase:
        """Open one host span of THIS iteration (obs/steptrace.phase:
        an ``ff.<name>`` event on the profiler's clock and perf_counter
        stamps for the anatomy profiler, from which the flight record's
        phase durations are summed). Loop thread only."""
        return phase(name, into=self._step_spans, **args)

    def _add_wall(self, key: str, seconds: float) -> None:
        """Add to one of THIS iteration's flight-record walls (a
        pipelined iteration may consume, and dispatch, more than once)."""
        self._step_walls[key] = self._step_walls.get(key, 0.0) + seconds

    def _engine_spans(self, decode_result: bool = False) -> float:
        """Adopt the engine's last step's dispatch/block/execute/
        readback spans into this iteration's anatomy span list.
        ``decode_result``: the step was a decode (or verify) whose
        result the loop has just consumed, which ends the stall of
        every admission made since the result before it. Returns the
        device-execute seconds for the flight record's ``execute_s``
        field."""
        spans = self.engine.last_step_spans
        self._step_spans.extend(spans)
        self._step_children.extend(self.engine.last_step_children)
        if decode_result:
            t = max(s1 for name, _, s1 in spans if name == "readback")  # the readback's end (the engine's accounting follows it)
            if self._stalled_admits:
                with self._phase("sched.observe"):
                    for _ in range(self._stalled_admits):
                        self.stats.observe("admit_stall", max(0.0, t - self._stall_from))
                self._stalled_admits = 0
            self._result_t = t
        return sum(s1 - s0 for name, s0, s1 in spans if name == "execute")

    def _note_admission(self) -> None:
        """An admission is about to take a slot. If streams are
        decoding, they are held from the last decode result consumed
        until the next one: ``admit_stall``, observed once per such
        admission when that next result arrives."""
        if self.obs_enabled and self._running and self._result_t is not None:
            if not self._stalled_admits:
                self._stall_from = self._result_t
            self._stalled_admits += 1

    def _flight_step(self) -> None:
        """Write THIS iteration's step record (idempotent per step):
        normally at the end of step(), but flushed early when NaN blame
        is about to freeze an incident snapshot — the failing step must
        be on the ring its own postmortem is cut from."""
        if self._step_recorded or not self.flight.enabled:
            return
        self._step_recorded = True
        with self._phase("sched.observe"):
            info = dict(self._step_info)
            self.flight.record_step(
                info.pop("kind", "admit"),
                phases=_flight_phases(self._step_spans, self._step_walls),
                occupancy=len(self._running),
                queue_depth=len(self._queue),
                blocks_free=self.engine.allocator.num_free,
                **info,
            )

    # ---------------------------------------------------------------- step
    def step(self) -> bool:
        """One scheduling iteration: expire, admit (join-mid-flight),
        plan speculation, grow/preempt, then decode — or verify, when
        any running request speculates. Returns True if any work
        happened. Each working iteration writes one flight-recorder
        step record with its phase decomposition (admission prefills
        record their own entries inside _admit). With a fault_scope
        (fleet replica), the whole iteration — including the supervisor
        recovery path — runs inside that injection scope so chaos plans
        can target this replica alone."""
        if self.fault_scope is None:
            return self._step_impl()
        with faults.scope(self.fault_scope):
            return self._step_impl()

    def _step_impl(self) -> bool:
        info = self._step_info = {}
        self._step_spans = []
        self._step_children = []
        self._step_walls = {}
        self._step_recorded = False
        if not self._running:
            self._result_t = None  # nothing decodes: no stream to hold
        t0 = time.perf_counter()
        # wall against CPU, on one iteration in CPU_CLOCK_EVERY: the
        # thread's CPU clock at the iteration's two ends and, read by the
        # engine, at its decode dispatch's two ends (four reads)
        sampled = self.obs_enabled and self._cpu_turn == 0
        self._cpu_turn = (self._cpu_turn + 1) % CPU_CLOCK_EVERY
        self.engine.cpu_stamps = sampled
        cpu0 = time.thread_time() if sampled else None
        admitted = 0
        # overlapped decode: steady-state iterations pipeline
        # dispatch/consume; any non-steady event drains the frontier
        # and falls through to the sequential body
        r = self._try_pipeline() if self.overlap else None
        if r is not None:
            did, kind = r, self._kind
        else:
            with self._phase("sched.schedule"):
                self._expire()
                self._sweep_mask_errors()
            # admit as many as fit THIS iteration — they decode together
            # below. Admission spans (admit / prefix_plan / the prefill's
            # dispatch-execute-readback) are recorded inside _admit.
            while self._admit():
                admitted += 1
            with self._phase("sched.schedule"):
                self._plan_speculation()
                self._grow()
            if admitted:
                info["admitted"] = admitted
            speculating = any(s.step_k > 0 for s in self._running.values())
            stepped = self._verify_once() if speculating else self._step_once()
            did, kind = stepped or admitted > 0, "admit"
        if did:
            # before housekeep, whose overload tick may shed requests:
            # the record's occupancy and queue depth are the step's own
            self._flight_step()
        with self._phase("sched.housekeep"):
            # durable group commit: one write+fsync for every journal
            # record this iteration buffered (admits, token deltas,
            # ends) — off the device dispatch path (under the pipeline
            # it rides the execute window like the other host
            # bookkeeping), a no-op on the base journal
            self.journal.flush_step()
            # integrate time-at-pressure AFTER the step's allocations,
            # so the pressure flag reflects the state the next interval
            # runs in (injectable clock: virtual-clock tests integrate
            # exactly); the overload control plane ticks on the fresh
            # pressure flag
            self.capacity.tick()
            self._overload_tick()
        if self._late and self._pipe is None and not (did and self._running):
            # nothing is in flight and no stream is left to dispatch a
            # step for: held tokens do not wait for one
            self._emit_late()
        if not self.obs_enabled:
            return did
        if did:
            # one anatomy observation per working iteration: host spans
            # + the device execute lane, under the iteration's step kind
            # (admission work inside a decode iteration charges the
            # decode critical path — which is exactly where it sits).
            # The iteration ends where the observation begins; what the
            # observation costs is timed here and carried into the next
            # one as ``observe`` time (the tracing layer's own cost).
            cpu_s = time.thread_time() - cpu0 if sampled else None
            with phase("sched.observe") as p_obs:
                self.anatomy.observe_step(
                    info.get("kind", kind), self._step_spans, t0, p_obs.t0,
                    tokens=int(info.get("emitted", 0)) + admitted,
                    children=self._step_children,
                    carried_s=self._observe_carry, cpu_s=cpu_s,
                )
            self._observe_carry = p_obs.seconds
        else:
            # an iteration that found nothing to do: counted, not dropped
            self.anatomy.observe_empty(t0, time.perf_counter())
        return did
