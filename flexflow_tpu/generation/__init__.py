"""Autoregressive generation engine: KV-cache decode, prefill/decode
split, and continuous batching.

The reference's inference story is a one-shot compiled graph behind a
Triton backend (SURVEY §2.9) — no token generation at all. This package
is the TPU-native serving answer for decoder transformers:

* :mod:`cache` — a preallocated, block-structured KV cache (vLLM /
  PagedAttention-style block tables, SOSP'23) sized against a memory
  budget, with a host-side block allocator;
* :mod:`decoder` — a pure-JAX decoder-only transformer (pre-LN, causal)
  whose full-context forward and incremental cached decode provably
  produce the same logits;
* :mod:`engine` — prefill/decode split with shape-bucketed, separately
  jitted steps (steady-state decode never recompiles) and greedy /
  temperature / top-k sampling;
* :mod:`scheduler` — Orca-style iteration-level continuous batching
  (OSDI'22): requests join the running batch at any decode step,
  finished sequences free their cache blocks immediately, FCFS
  admission is cache-capacity aware, and cache exhaustion preempts by
  recompute.

* :mod:`prefix` — cross-request prefix caching: a radix index over
  token-block content with refcounted copy-on-write blocks and a
  host-RAM offload tier (swap-in vs recompute decided on the cost-model
  roofline, CRC-verified, chaos-covered). Admission matches the longest
  cached prefix and prefills only the suffix; streams are byte-identical
  with caching on or off.

* :mod:`speculative` — speculative decoding (SpecInfer / Leviathan et
  al.): model-free n-gram and small-draft-model drafters, ONE
  fixed-shape batched verification step over the block cache
  (chunked-append attention), exact greedy acceptance and
  distribution-preserving rejection sampling, with per-request
  adaptive k driven by the scheduler.

* :mod:`recovery` — the self-healing layer: per-request generation
  journal (exact recompute-replay of any stream after an engine
  teardown), an engine supervisor (step retry, poisoned-request
  quarantine via NaN blame vectors + crash bisection, crash-restart
  budget with exponential backoff), and a step watchdog that detects
  stalled device steps and trips the circuit breaker so health
  endpoints stop lying about a hung device.

Serving integration lives in :mod:`flexflow_tpu.serving.generation`
(`GenerationModel`), wired through the same deadline / backpressure /
circuit-breaker paths as `InferenceModel`, with per-token streaming over
HTTP (SSE) and gRPC.
"""
from .cache import BlockAllocator, CacheConfig, KVCache
from .decoder import DecoderParams, forward_full, init_decoder_params
from .engine import BlockDiffusion, GenerationEngine, SamplingParams
from .prefix import PrefixCache, PrefixEntry
from .sharding import ServingLayout
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
from .scheduler import (
    ContinuousBatchingScheduler,
    GenerationHandle,
    Request,
)
from .speculative import (
    Drafter,
    DraftModelDrafter,
    NgramDrafter,
    SpeculationConfig,
)

__all__ = [
    "BlockAllocator",
    "BlockDiffusion",
    "CacheConfig",
    "ContinuousBatchingScheduler",
    "DecoderParams",
    "Drafter",
    "DraftModelDrafter",
    "EngineFailedError",
    "EngineSupervisor",
    "GenerationEngine",
    "GenerationHandle",
    "GenerationJournal",
    "KVCache",
    "NgramDrafter",
    "PoisonedRequestError",
    "PrefixCache",
    "PrefixEntry",
    "RecoveryPolicy",
    "Request",
    "SamplingParams",
    "SpeculationConfig",
    "StalledStepError",
    "StepWatchdog",
    "WatchdogPolicy",
    "forward_full",
    "init_decoder_params",
]
