"""GenerationModel: a streaming-generation servable next to
InferenceModel.

Where `InferenceModel` is a one-shot compiled graph behind the
request-level DynamicBatcher, a GenerationModel owns a
ContinuousBatchingScheduler (generation/scheduler.py) — requests join
the running decode batch at iteration granularity and stream tokens
back as they are produced. The HTTP front end serves it on
``POST /v2/models/{name}/generate`` (JSON, or SSE when streaming) and
the gRPC front end on ``ModelStreamInfer``; both reuse PR 1's status
mapping (backpressure 503/RESOURCE_EXHAUSTED, expired deadline
504/DEADLINE_EXCEEDED, open breaker 503/UNAVAILABLE) because the
scheduler raises the same typed ResilienceErrors as the batcher.

The scheduler it owns is self-healing (generation/recovery.py):
engine-loop crashes are journal-replayed, poisoned requests are
quarantined alone, and a stalled device step trips the breaker via the
step watchdog — so ``ready()`` (and therefore ``/v2/health/ready``,
``/v2/models/{name}/ready`` and gRPC ModelReady) reflects a hung or
dead engine instead of lying. Recovery counters (``recoveries``,
``replayed_tokens``, ``quarantined``, ``watchdog_trips``, ...) ride the
model's stats block on ``GET /v2/stats``. Pass ``recovery=`` /
``watchdog=`` (RecoveryPolicy / WatchdogPolicy) through
``scheduler_kwargs`` to tune restart budgets and stall timeouts.
"""
from __future__ import annotations

import logging
from typing import Dict, List, Optional, Sequence

from ..generation.constrained import (
    GrammarCache,
    GrammarError,
    default_vocabulary,
)
from ..generation.engine import GenerationEngine, SamplingParams
from ..generation.scheduler import ContinuousBatchingScheduler, GenerationHandle
from ..generation.speculative import SpeculationConfig


_log = logging.getLogger(__name__)


class GenerationModel:
    """One servable generation engine: name + scheduler + health view."""

    def __init__(
        self,
        engine: GenerationEngine,
        name: str = "generator",
        vocabulary: Optional[Sequence[str]] = None,
        **scheduler_kwargs,
    ):
        self.engine = engine
        self.name = name
        self.scheduler = ContinuousBatchingScheduler(engine, **scheduler_kwargs)
        # response_format grammars compile against THIS model's token
        # texts; no tokenizer ships with the engine, so the synthetic
        # default vocabulary stands in unless the deployment passes one
        self.vocabulary: List[str] = list(
            vocabulary
            if vocabulary is not None
            else default_vocabulary(engine.cfg.vocab_size)
        )
        self.grammar_cache = GrammarCache(
            self.vocabulary, stats=self.scheduler.constrained_stats
        )
        # durable serving (ISSUE 19): set by enable_durability(); when
        # attached, every admission journals into the WAL and
        # GET /v2/generate/resume/{id} can re-attach clients
        self.durable = None

    # --------------------------------------------------------- lifecycle
    def start(self) -> None:
        # the one start-up line: what the paged decode call of each
        # attention kind lowered to (static per program; /v2/stats
        # `kernels` says the same for as long as the server lives)
        _log.info(
            "generation model %r starts: paged attention %s", self.name,
            ", ".join(
                f"{kind}: {low['body']} body at group {low['group']}"
                + (f", {low['columns_per_step']} columns a step over {low['grid_steps']} grid steps a call"
                   if "grid_steps" in low else "")
                for kind, low in self.engine.attention_kernels.items()
            ),
        )
        self.scheduler.start()

    def stop(self, drain: bool = True) -> None:
        self.scheduler.stop(drain=drain)
        if self.durable is not None:
            self.durable.close()

    def enable_durability(self, config) -> "Durability":
        """Attach a crash-safe WAL journal to this model's scheduler
        (serving/durable.py). Call before traffic; follow with
        ``self.durable.warm_restart()`` to replay a predecessor's
        journal from the same directory."""
        from .durable import Durability  # late: keeps the tier optional

        self.durable = Durability(
            self.scheduler, config, grammar_cache=self.grammar_cache
        )
        return self.durable

    def ready(self) -> bool:
        return self.scheduler.ready()

    @property
    def breaker(self):
        return self.scheduler.breaker

    @property
    def stats(self):
        return self.scheduler.stats

    @property
    def recovery_stats(self):
        return self.scheduler.recovery_stats

    @property
    def trace_ring(self):
        """Recently finished RequestTraces (GET /v2/debug/traces)."""
        return self.scheduler.trace_ring

    @property
    def journeys(self):
        """This replica's journey span recorder (None when journeys
        are off) — one lane in the fleet's stitched timeline
        (GET /v2/debug/journey/{id})."""
        return self.scheduler.journeys

    @property
    def journey_spool(self):
        """The on-disk journey span ring (set by enable_durability)
        keeping pre-crash spans joinable after process death."""
        sched = self.scheduler
        rec = sched.journeys
        return rec.spool if rec is not None else None

    def journey_recorders(self):
        """Uniform shape with Fleet/DisaggregatedFleet so the server's
        journey index builds the same way over any generation unit."""
        rec = self.scheduler.journeys
        return [rec] if rec is not None else []

    def journey_spools(self):
        spool = self.journey_spool
        return [spool] if spool is not None else []

    @property
    def flight(self):
        """The engine flight recorder (GET /v2/debug/timeline)."""
        return self.scheduler.flight

    @property
    def capacity(self):
        """KV-cache block telemetry (GET /v2/debug/cache)."""
        return self.scheduler.capacity

    @property
    def anatomy(self):
        """The step-anatomy profiler: phase histograms, device-bubble
        accounting, overlap headroom, and the on-demand two-lane
        capture (GET /v2/debug/anatomy)."""
        return self.scheduler.anatomy

    @property
    def programs(self):
        """The engine's jit program registry (GET /v2/debug/programs)."""
        return self.engine.programs

    @property
    def slo(self):
        """The SLO burn-rate monitor (GET /v2/slo)."""
        return self.scheduler.slo

    @property
    def ledger(self):
        """Cost-model truth ledger: per-step (predicted, measured)
        pairs + drift alarms (GET /v2/debug/predictions)."""
        return self.engine.ledger

    @property
    def goodput(self):
        return self.scheduler.goodput

    @property
    def overload(self):
        """The overload controller: priority-aware admission, the AIMD
        concurrency limiter, and the degradation ladder
        (GET /v2/overload)."""
        return self.scheduler.overload

    def overload_report(self):
        return self.scheduler.overload.report()

    def cache_report(self):
        return self.scheduler.cache_report()

    def readiness_rationale(self) -> Dict:
        """Why (or why not) this model is ready: breaker state, watchdog
        evidence, and SLO burn — the three health inputs. A breaching
        SLO explains degradation in the rationale without flipping
        readiness (a latency regression is not an outage)."""
        rs = self.scheduler.recovery_stats
        return {
            "ready": self.ready(),
            "breaker": self.breaker.state,
            "draining": self.scheduler._draining,
            "watchdog_trips": rs.watchdog_trips,
            "engine_failures": rs.engine_failures,
            "slo_breaching": self.scheduler.slo.breaching(),
            # degraded-but-up: a nonzero ladder level explains reduced
            # QoS in the rationale without flipping readiness
            "degrade_level": self.scheduler.overload.ladder.level,
        }

    # --------------------------------------------------------------- run
    def submit(
        self,
        prompt: Sequence[int],
        sampling: Optional[SamplingParams] = None,
        deadline_s: Optional[float] = None,
        speculation: Optional[SpeculationConfig] = None,
        transport: Optional[str] = None,
        priority: Optional[str] = None,
        response_format: Optional[Dict] = None,
        journey=None,
    ) -> GenerationHandle:
        grammar = None
        if response_format is not None:
            # compiles (or cache-hits) BEFORE the request joins the
            # queue: a malformed grammar is the submitter's 400, it
            # never reaches the batch
            grammar = self.grammar_cache.get(response_format)
        handle = self.scheduler.submit(
            prompt, sampling, deadline_s=deadline_s, speculation=speculation,
            transport=transport, priority=priority,
            grammar=grammar, response_format=response_format,
            journey=journey,
        )
        if self.durable is not None:
            # pre-assign the durable id at submit (admission journals
            # later) so the HTTP response can carry the resume handle
            # from its very first byte
            self.durable.track(handle._request)
        return handle

    def generate(
        self,
        prompt: Sequence[int],
        sampling: Optional[SamplingParams] = None,
        timeout: Optional[float] = None,
        speculation: Optional[SpeculationConfig] = None,
        response_format: Optional[Dict] = None,
    ) -> List[int]:
        """Blocking single-request generation (deadline = timeout)."""
        handle = self.submit(
            prompt, sampling, deadline_s=timeout, speculation=speculation,
            response_format=response_format,
        )
        return handle.result(timeout=timeout)

    @staticmethod
    def sampling_from(params: Dict) -> SamplingParams:
        """Build SamplingParams from a request-level dict (HTTP JSON body
        fields / gRPC parameters map), ignoring unknown keys."""
        defaults = SamplingParams()
        eos = params.get("eos_id")
        # a block-diffusion model's rule for this request (the body's
        # "parameters"; absent: the engine's defaults)
        rule = params.get("parameters") if isinstance(params.get("parameters"), dict) else {}
        steps, threshold = rule.get("denoising_steps"), rule.get("threshold")
        return SamplingParams(
            max_new_tokens=int(params.get("max_new_tokens", defaults.max_new_tokens)),
            temperature=float(params.get("temperature", defaults.temperature)),
            top_k=int(params.get("top_k", defaults.top_k)),
            eos_id=None if eos is None else int(eos),
            seed=int(params.get("seed", defaults.seed)),
            denoising_steps=None if steps is None else int(steps),
            remasking=rule.get("remasking"),
            threshold=None if threshold is None else float(threshold),
        )

    @staticmethod
    def speculation_from(params: Dict) -> Optional[SpeculationConfig]:
        """Build a SpeculationConfig from the request's ``speculation``
        block (HTTP JSON body / gRPC parameters map), ignoring unknown
        keys. Absent block (or ``enabled: false``) -> None (the
        scheduler's default policy applies)."""
        block = params.get("speculation")
        if not isinstance(block, dict):
            return None
        if not bool(block.get("enabled", True)):
            return SpeculationConfig(enabled=False)
        defaults = SpeculationConfig()
        return SpeculationConfig(
            enabled=True,
            k=int(block.get("k", defaults.k)),
            method=str(block.get("method", defaults.method)),
            max_ngram=int(block.get("max_ngram", defaults.max_ngram)),
            min_ngram=int(block.get("min_ngram", defaults.min_ngram)),
            adaptive=bool(block.get("adaptive", defaults.adaptive)),
        )

    @staticmethod
    def response_format_from(params: Dict) -> Optional[Dict]:
        """Pull the request's ``response_format`` block (HTTP JSON body
        / gRPC parameters map). Absent -> None (unconstrained). A
        present-but-malformed block raises :class:`GrammarError` — a
        ValueError, so both front ends map it to 400/INVALID_ARGUMENT."""
        block = params.get("response_format")
        if block is None:
            return None
        if not isinstance(block, dict):
            raise GrammarError(
                f"response_format must be an object, got {type(block).__name__}"
            )
        return block

    def metadata(self) -> Dict:
        cfg = self.engine.cfg
        cc = self.engine.cache_config
        sup = self.scheduler.supervisor
        wd = self.scheduler.watchdog
        return {
            "name": self.name,
            "platform": "flexflow_tpu_generation",
            "recovery": {
                "max_restarts": sup.policy.max_restarts,
                "budget_window_s": sup.policy.budget_window_s,
                "watchdog_enabled": wd.policy.enabled,
                "stall_timeout_s": wd.policy.stall_timeout_s,
                "engine_resets": self.engine.resets,
            },
            "observability": {
                "enabled": self.scheduler.obs_enabled,
                "trace_ring": self.scheduler.trace_ring.capacity,
                "flight_capacity": self.scheduler.flight.capacity,
                "progress_every": self.scheduler.trace_progress_every,
                "anatomy": self.scheduler.anatomy.enabled,
                "journeys": self.scheduler.journeys is not None,
            },
            "compute": {
                "chip": self.engine.flops_model.chip.name,
                "peak_tflops": self.engine.flops_model.peak_flops / 1e12,
                "mfu": self.engine.mfu(),
                "model_tflops_total": self.engine.total_flops() / 1e12,
            },
            # ISSUE 15: mesh geometry + the search-chosen (or pinned)
            # tensor-parallel serving layout with every scored candidate
            "serving_strategy": self.engine.serving_strategy_block(),
            "slo": {
                "objectives": [o.name for o in self.scheduler.slo.objectives],
                "breaching": self.scheduler.slo.breaching(),
            },
            "max_batch_slots": self.engine.max_batch_slots,
            "max_spec_tokens": self.engine.max_spec_tokens,
            "max_seq_len": self.engine.max_seq_len,
            "prompt_buckets": list(self.engine.buckets),
            "vocab_size": cfg.vocab_size,
            "cache": {
                "num_blocks": cc.num_blocks,
                "block_size": cc.block_size,
                "usable_tokens": cc.usable_tokens,
                "bytes": cc.total_bytes,
            },
            "prefix_cache": {
                "enabled": self.engine.prefix_cache.enabled,
                "host_budget_bytes": self.engine.prefix_cache.host_budget_bytes,
            },
            "constrained": {
                "formats": ["json_schema", "regex"],
                "grammar_cache_entries": len(self.grammar_cache),
                "vocabulary_tokens": len(self.vocabulary),
            },
            "durable": {
                "enabled": self.durable is not None,
                "fingerprint": (
                    self.durable.fingerprint if self.durable is not None else None
                ),
                "wal_segments": (
                    self.durable.wal.segment_count()
                    if self.durable is not None
                    else 0
                ),
            },
            "inputs": [{"name": "tokens", "shape": (-1,), "datatype": "INT32"}],
            "outputs": [{"name": "tokens", "shape": (-1,), "datatype": "INT32"}],
        }
