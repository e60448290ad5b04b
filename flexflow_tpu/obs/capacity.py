"""Capacity & compute observability: where the HBM blocks go and how
much of the chip serving actually uses.

PR 5 answered the *time* dimension (traces, flight recorder, /metrics);
this module answers the *resource* dimension with three pieces:

* :class:`CacheTelemetry` — KV-cache block accounting beyond the
  occupancy gauge: per-request block residency (built on demand from
  the scheduler's slot state, so the hot path pays nothing), internal
  fragmentation (allocated slots minus live tokens — blocks held for
  lookahead and block-rounding), preempt-reclaim / trim counters,
  time-at-pressure integrated on the scheduler's injectable clock, and
  admission-wait blame ("queued 120ms waiting for 3 blocks") threaded
  into request traces. Served on ``GET /v2/debug/cache`` and as
  ``flexflow_serving_cache_*`` Prometheus series.

* :class:`ServingFlops` — the serving-side analog of the search cost
  model's roofline accounting (search/cost_model.py): per-step *model*
  FLOPs for prefill / decode / verify derived from the decoder config,
  measured against :class:`~flexflow_tpu.parallel.machine.TPUChipSpec`
  peaks. Convention follows MFU literature: only model-shaped work
  counts — true prompt lengths and live context positions, never bucket
  padding or inactive slots — so serving MFU is comparable to the
  training MFU the benchmark's ``mfu`` metric reports. Work the device executed but
  clients never benefited from (recovery replay, bisection probes, step
  retries) DOES count, in both the FLOPs numerator and the device-time
  denominator: MFU measures hardware utilization, not client benefit —
  the client-useful fraction is ``goodput_ratio``, and replay volume is
  visible as ``replayed_tokens``/``step_retries``.

* :class:`ProgramRegistry` — every traced jit program (engine prefill
  buckets, decode, verify, plus the executor's train/eval programs via
  :data:`GLOBAL_PROGRAMS`) with its static argument signature, trace
  count, and the wall of its first call split by JAX's own compile
  events into trace, lowering, backend compile or cache load, and the
  rest (argument preparation and the first run). The same records are
  the ``programs`` of the process's start-up account
  (:data:`~flexflow_tpu.obs.steptrace.GLOBAL_STARTUP`), where programs
  no registry owns appear under their function's name. A steady-state
  retrace diffs the new
  abstract arguments against the registered signature and produces a
  human-readable *blame* string ("decode retraced: tokens int32[4] ->
  int32[5]") — attached to the flight recorder and served on
  ``GET /v2/debug/programs``. ``trace_counts`` says *that* a program
  retraced; the registry says *why*.

Everything here is host-side Python arithmetic: no device calls, no
extra dispatches, and the per-step cost is a handful of integer adds
(no reader measures it alone: every cell runs with it on). The compile
listeners fire on JAX's compile events only, ``note_trace`` at trace
time only: a warm step runs neither.
"""
from __future__ import annotations

import dataclasses
import re
import threading
import time
from collections import deque
from typing import Callable, Dict, List, Optional, Sequence

import jax.monitoring

from ..core.types import DataType
from ..parallel.machine import TPUChipSpec
from .steptrace import GLOBAL_STARTUP, PROGRAM_PARTS, both_hit

# --------------------------------------------------------------------------
# KV-cache block telemetry
# --------------------------------------------------------------------------


class CacheTelemetry:
    """Block-level cache accounting for one continuous-batching
    scheduler.

    The scheduler calls the ``note_*`` hooks from its loop thread only
    (plain int arithmetic, no locks needed under the GIL); ``report``
    builds the residency table on demand from the live slot states, so
    steady-state steps never touch per-request dicts.

    ``pressure_threshold``: the free-block fraction at or below which
    the cache counts as "under pressure"; :meth:`tick` integrates the
    time spent there on the scheduler's (possibly virtual) clock.
    """

    def __init__(
        self,
        allocator,
        clock: Callable[[], float] = time.monotonic,
        pressure_threshold: float = 0.10,
        enabled: bool = True,
        reclaimable: Optional[Callable[[], int]] = None,
    ):
        self.allocator = allocator
        self.clock = clock
        self.enabled = enabled
        self.pressure_threshold = pressure_threshold
        # blocks reclaimable on demand (unreferenced cached prefixes —
        # generation/prefix.py): available for admission, so a warm but
        # idle cache does not read as pressure
        self.reclaimable = reclaimable or (lambda: 0)
        # cumulative counters (loop-thread writes only)
        self.preempt_reclaimed_blocks = 0
        self.preempt_reclaims = 0
        self.trimmed_blocks = 0
        self.trims = 0
        self.admission_waits = 0  # distinct blocked->admitted episodes
        self.admission_wait_s = 0.0  # total time requests sat blocked on blocks
        self.last_wait_blame: Optional[str] = None
        self.time_at_pressure_s = 0.0
        self._last_tick: Optional[float] = None
        self._was_under = False

    @property
    def under_pressure(self) -> bool:
        """The most recent tick's pressure flag (free + reclaimable at
        or below the threshold) — the AdaptiveLimiter's cache-pressure
        input."""
        return self._was_under

    # ------------------------------------------------------------- hooks
    def tick(self) -> None:
        """Integrate time-at-pressure; called once per scheduler step."""
        if not self.enabled:
            return
        now = self.clock()
        if self._last_tick is not None and self._was_under:
            self.time_at_pressure_s += max(0.0, now - self._last_tick)
        total = self.allocator.num_total
        available = self.allocator.num_free + self.reclaimable()
        self._was_under = available <= total * self.pressure_threshold
        self._last_tick = now

    def note_preempt(self, n_blocks: int) -> None:
        if not self.enabled:
            return
        self.preempt_reclaims += 1
        self.preempt_reclaimed_blocks += n_blocks

    def note_trim(self, n_blocks: int) -> None:
        if not self.enabled:
            return
        self.trims += 1
        self.trimmed_blocks += n_blocks

    def note_admission_wait(self, wait_s: float, blocks_short: int) -> str:
        """One blocked->admitted episode completed; returns the blame
        string the scheduler attaches to the request's trace."""
        blame = (
            f"queued {wait_s * 1e3:.0f}ms waiting for "
            f"{max(1, blocks_short)} block(s)"
        )
        if not self.enabled:
            return blame
        self.admission_waits += 1
        self.admission_wait_s += max(0.0, wait_s)
        self.last_wait_blame = blame
        return blame

    # ------------------------------------------------------------ reports
    def fragmentation_slots(self, running: Sequence) -> int:
        """Internal fragmentation: token slots allocated but not holding
        live cache entries (lookahead + block rounding), summed over the
        running set."""
        bs = self.allocator.config.block_size
        return sum(max(0, len(s.blocks) * bs - s.cached_len) for s in running)

    def register_gauges(self, stats, running_fn: Callable[[], List]) -> None:
        """Prometheus series (``flexflow_serving_cache_*``): counters
        ride as gauges like the scheduler's other cumulative metrics."""
        alloc = self.allocator
        stats.add_gauge(
            "cache_frag_slots", lambda: self.fragmentation_slots(running_fn())
        )
        stats.add_gauge("cache_free_low_water", lambda: alloc.low_water)
        stats.add_gauge("cache_free_high_water", lambda: alloc.high_water)
        stats.add_gauge("cache_blocks_allocated_total", lambda: alloc.total_allocated)
        stats.add_gauge("cache_blocks_freed_total", lambda: alloc.total_freed)
        stats.add_gauge(
            "cache_preempt_reclaimed_blocks", lambda: self.preempt_reclaimed_blocks
        )
        stats.add_gauge("cache_trimmed_blocks", lambda: self.trimmed_blocks)
        stats.add_gauge("cache_pressure_time_s", lambda: self.time_at_pressure_s)
        stats.add_gauge("cache_admission_waits", lambda: self.admission_waits)
        stats.add_gauge("cache_admission_wait_s", lambda: self.admission_wait_s)

    def report(
        self, running: Sequence, queue_depth: int = 0, admitting=None,
        free: Optional[int] = None, prefix: Optional[Dict] = None,
    ) -> Dict:
        """The ``GET /v2/debug/cache`` payload: allocator state,
        watermarks, counters, and the per-request residency table.

        Residency invariant (tests/test_capacity.py): the table's
        PRIVATE block counts (``blocks - shared_blocks``) plus the
        prefix index's resident blocks sum to exactly ``used`` —
        shared blocks are counted once by the index however many
        sequences reference them. That includes an admission in
        flight — blocks are allocated BEFORE the prefill device call
        (seconds, on a cold compile), so ``admitting`` = (request,
        blocks) renders as a provisional ``"admitting": True`` row
        rather than a phantom block leak. Deduped by request id against
        ``running`` so a request is never counted twice. The invariant
        is exact whenever the loop thread is between transitions;
        callers racing the loop pass ``free`` read BEFORE snapshotting
        ``running`` so a request finishing mid-scrape makes the table
        at worst UNDERcount ``used`` by that one request's blocks
        (blocks counted used, row already gone) — never report freed
        blocks as still resident."""
        alloc = self.allocator
        cfg = alloc.config
        bs = cfg.block_size
        if free is None:
            free = alloc.num_free
        residency = []
        for s in sorted(running, key=lambda s: s.slot):
            allocated_slots = len(s.blocks) * bs
            # shared blocks are index-owned (prefix cache): counted in
            # the prefix tier's residency, not as this request's private
            # footprint — with sharing, per-row block counts can
            # legitimately sum past ``used``
            shared = len(getattr(s, "shared_idx", ()) or ())
            residency.append({
                "request_id": s.req.id,
                "slot": s.slot,
                "blocks": len(s.blocks),
                "shared_blocks": shared,
                "allocated_slots": allocated_slots,
                "live_tokens": s.cached_len,
                "frag_slots": max(0, allocated_slots - s.cached_len),
                "n_generated": s.req.n_generated,
                "preemptions": s.req.preemptions,
            })
        if admitting is not None:
            adm_req, adm_blocks = admitting
            if adm_req.id not in {r["request_id"] for r in residency}:
                allocated_slots = len(adm_blocks) * bs
                residency.append({
                    "request_id": adm_req.id,
                    "slot": None,
                    "blocks": len(adm_blocks),
                    "shared_blocks": 0,  # private (pre-prefill) blocks only
                    "allocated_slots": allocated_slots,
                    "live_tokens": 0,  # prefill still running
                    "frag_slots": allocated_slots,
                    "n_generated": adm_req.n_generated,
                    "preemptions": adm_req.preemptions,
                    "admitting": True,
                })
        total = alloc.num_total
        return {
            "config": {
                "num_blocks": cfg.num_blocks,
                "block_size": bs,
                "usable_tokens": cfg.usable_tokens,
                "bytes_per_block": cfg.bytes_per_block,
                "total_bytes": cfg.total_bytes,
            },
            "blocks": {
                "total": total,
                "free": free,
                "used": total - free,
                "low_water": alloc.low_water,
                "high_water": alloc.high_water,
                "allocated_total": alloc.total_allocated,
                "freed_total": alloc.total_freed,
                "reset_reclaimed_total": alloc.total_reset_reclaimed,
            },
            "fragmentation_slots": sum(r["frag_slots"] for r in residency),
            "occupancy": (total - free) / max(1, total),
            "pressure": {
                "threshold": self.pressure_threshold,
                "under_pressure": self._was_under,
                "time_at_pressure_s": self.time_at_pressure_s,
            },
            "counters": {
                "preempt_reclaims": self.preempt_reclaims,
                "preempt_reclaimed_blocks": self.preempt_reclaimed_blocks,
                "trims": self.trims,
                "trimmed_blocks": self.trimmed_blocks,
                "admission_waits": self.admission_waits,
                "admission_wait_s": self.admission_wait_s,
                "last_wait_blame": self.last_wait_blame,
            },
            "queue_depth": queue_depth,
            "residency": residency,
            # prefix-cache tiering (generation/prefix.py): the
            # conservation invariant becomes
            #   sum(row private blocks) + prefix resident == used
            # with host-tier bytes accounted separately from HBM
            "prefix_cache": prefix or {},
        }


# --------------------------------------------------------------------------
# Serving FLOPs model (MFU / achieved TFLOP/s)
# --------------------------------------------------------------------------


class ServingFlops:
    """Analytic per-step FLOPs for the generation engine's three
    programs, in the cost model's roofline idiom (search/cost_model.py
    counts the same matmul terms per op; here they are folded into one
    decoder-layer constant so the hot path pays two multiplies).

    Per useful token (matmuls only, fwd):
      qkv + out projections  8 * E^2          per layer
      FFN (two matmuls)      4 * E * F        per layer
      LM head                2 * E * V        once
    Per (token, live context position):
      QK^T + AV              4 * E            per layer

    MFU = model FLOPs / device seconds / chip peak for the cache dtype
    (bf16 vs f32 peak, exactly the cost model's dtype dispatch).
    """

    def __init__(
        self,
        num_layers: int,
        hidden_size: int,
        ff_size: int,
        vocab_size: int,
        dtype: DataType = DataType.FLOAT,
        chip: Optional[TPUChipSpec] = None,
    ):
        e, f, l, v = hidden_size, ff_size, num_layers, vocab_size
        self.per_token_flops = l * (8 * e * e + 4 * e * f) + 2 * e * v
        self.per_ctx_flops = l * 4 * e
        self.chip = chip or TPUChipSpec()
        self.peak_flops = (
            self.chip.bf16_flops
            if dtype in (DataType.BFLOAT16, DataType.HALF)
            else self.chip.f32_flops
        )
        # byte model for the roofline's memory leg (obs/truth.py pairs
        # predicted step time with measured): each step streams the
        # weights once and touches the KV cache per live context position
        self.dtype_bytes = 2 if dtype in (DataType.BFLOAT16, DataType.HALF) else 4
        self.param_count = 2 * v * e + l * (4 * e * e + 2 * e * f)
        self.param_bytes = self.param_count * self.dtype_bytes
        self.kv_bytes_per_pos = 2 * l * e * self.dtype_bytes  # k + v
        # sliding-window layers (from_config): what they add per (token,
        # position of the `window` behind it), beside the full layers' above
        self.window = 0
        self.window_ctx_flops = 0
        self.window_kv_bytes_per_pos = 0
        # state-space layers (from_config): a live sequence's recurrent state over all of them, in bytes
        self.state_bytes_per_seq = 0
        # what a decode step READS of a cached position: its K/V once a layer that attends it (cross layers
        # read the one layer's that stores it again: from_config); None: what it stores, `kv_bytes_per_pos`
        self.kv_read_bytes_per_pos: Optional[int] = None
        # a cross-decoder (from_config): of `per_token_flops` / `per_ctx_flops`, what a prefill runs on a
        # prompt's last row alone (its layers and the head)
        self.last_row_token_flops = 0
        self.last_row_ctx_flops = 0

    @classmethod
    def from_config(cls, cfg, dtype: DataType = DataType.FLOAT, chip=None) -> "ServingFlops":
        """Build from a TransformerConfig (the engine's ``cfg``). A
        ``DecoderConfig`` (generation/decoder.py) whose layers differ
        among themselves has its constants summed layer by layer: a
        token's matmuls through the operator and the feed-forward each
        layer really has (of the experts, the ``experts_per_token`` a
        token is routed to), every weight's bytes (a decode step of a
        full batch reads every expert), K/V for the attention layers
        alone, and by kind of attention layer: a full layer attends a
        token's whole context, a sliding-window layer the ``window``
        positions behind it (``windowed``). A latent layer: its five
        matrices, attention in the absorbed form (per head and attended
        position ``latent_width`` multiply-adds for the score and
        ``kv_lora_rank`` for the value), and ONE row a position in the
        cache, at its stored width; the shared experts every token goes
        through; of the routed experts the bytes of those held here. An
        ssm layer: its projections and convolution, 5 flops a state value
        a token for the recurrence, and the float32 state a decode step
        reads and writes for every live sequence (``state_bytes_per_seq``);
        ungated experts or experts in a latent: two matrices an expert at
        the latent's width, the picks that land on a held expert, the two
        latent projections. A Mamba-1 layer (``mamba``): its four projections and convolution, 6 flops a
        state value a token and the float32 state; a ``gmu`` layer its two matrices; a ``cross`` layer
        ``W_q`` and ``W_o`` and the attended positions, its K/V READ again and stored nowhere
        (``kv_read_bytes_per_pos``); differential attention scores two halves and weighs twice the
        width (6 flops a query head's width a position, not 4); the layers from ``cross_from`` on and the
        head are a prefill's on ONE row a prompt (``last_row_*``). A shortcut expert branch (``shortcut_experts``): beside the layer's
        dense feed-forward, the router, the picks that land on a held
        expert and the identity experts' picks at ``2 E`` flops each."""
        model = cls(
            num_layers=cfg.num_layers,
            hidden_size=cfg.hidden_size,
            ff_size=cfg.ff_size,
            vocab_size=cfg.vocab_size,
            dtype=dtype,
            chip=chip,
        )
        if not hasattr(cfg, "layer_types"):
            return model
        e, v = cfg.hidden_size, cfg.vocab_size
        q, kv = cfg.num_heads * cfg.dim_per_head, cfg.kv_heads * cfg.dim_per_head
        flops, params, n_attn, n_window, n_ssm = 2 * e * v, v * e * (1 if cfg.tied_head else 2), 0, 0, 0
        n_latent = len(cfg.latent_layers)
        n_cross = n_mamba = 0
        bias = (q + 2 * kv + e) if getattr(cfg, "attention_bias", False) else 0
        cross_from = getattr(cfg, "cross_from", cfg.num_layers)
        last_row = 2 * e * v if cross_from < cfg.num_layers else 0  # (the head, with the cross-decoder)
        for l in range(cfg.num_layers):
            before = flops
            if cfg.operator(l) == "mamba":
                di, n, r = cfg.ssm_inner, cfg.ssm_state_size, cfg.dt_rank
                op = e * 2 * di + cfg.ssm_conv_kernel * di + di * (r + 2 * n) + r * di + di * e
                params += 2 * di + di * n + di  # the convolution's bias, the step's, A_log and D
                n_mamba += 1
            elif cfg.operator(l) == "gmu":
                op = 2 * e * cfg.ssm_inner
            elif cfg.operator(l) == "cross":
                op = 2 * e * q
                params += bias - 2 * kv
                n_cross += 1
            elif cfg.operator(l) == "latent":
                h, qk = cfg.num_heads, cfg.qk_nope_head_dim + cfg.qk_rope_head_dim
                op = (e * cfg.q_lora_rank + cfg.q_lora_rank * h * qk + e * cfg.latent_width
                      + cfg.kv_lora_rank * h * (cfg.qk_nope_head_dim + cfg.v_head_dim) + h * cfg.v_head_dim * e)
            elif cfg.operator(l) == "attention":
                op = 2 * e * q + 2 * e * kv
                params += bias
                n_attn += 1
            elif cfg.operator(l) == "window":
                op = 2 * e * q + 2 * e * kv
                params += bias
                n_window += 1
            elif cfg.operator(l) == "ssm":
                # the two projections and the convolution; the recurrence is counted apart (ssm_token_flops)
                op = e * (cfg.ssm_inner + cfg.ssm_conv_width + cfg.ssm_heads) + cfg.ssm_conv_kernel * cfg.ssm_conv_width + cfg.ssm_inner * e
                n_ssm += 1
            elif cfg.operator(l) == "ffn":
                op = 0  # the layer is its feed-forward alone
            else:
                op = 4 * e * e + cfg.conv_kernel * e
            kind = cfg.ffn_kind(l)
            if kind == "none":
                ffn = ffn_params = 0
            elif kind == "experts" and (cfg.moe_latent_size or cfg.expert_activation != "swiglu" or cfg.shared_ff_size):
                # ungated experts (two matrices) and/or experts in a latent: the two latent projections, of a
                # token's k picks those that land on a held expert, the shared expert on the hidden size itself
                mats = 3 if cfg.expert_activation == "swiglu" else 2
                ew = cfg.moe_latent_size or e
                per_expert = mats * ew * cfg.moe_ff_size
                shared = mats * e * (cfg.shared_ff_size or cfg.num_shared_experts * cfg.moe_ff_size) if cfg.num_shared_experts else 0
                outside = (2 * e * ew if cfg.moe_latent_size else 0) + shared + e * cfg.num_experts
                landed = cfg.experts_per_token * cfg.held_experts / cfg.num_experts
                ffn, ffn_params = int(landed * per_expert) + outside, cfg.held_experts * per_expert + outside
            elif kind == "experts":
                per_expert = 3 * e * cfg.moe_ff_size
                ffn, ffn_params = (cfg.experts_per_token + cfg.num_shared_experts) * per_expert + e * cfg.num_experts, (
                    (cfg.held_experts + cfg.num_shared_experts) * per_expert + e * cfg.num_experts)
            else:
                ffn = ffn_params = (3 if kind == "swiglu" else 2) * e * cfg.ff_size
            if cfg.shortcut(l):
                # a routed branch BESIDE the dense feed-forward: the router over all its outputs; of a
                # token's k picks those that land on a held expert (uniform picks: k x held / outputs)
                # and those on an identity expert, which is E multiply-adds a pick and no weight
                per_expert, outputs = 3 * e * cfg.moe_ff_size, cfg.router_outputs
                landed, zero = (cfg.experts_per_token * n / outputs for n in (cfg.held_experts, cfg.zero_experts))
                ffn += int(landed * per_expert + zero * e) + e * outputs
                ffn_params += cfg.held_experts * per_expert + e * outputs
            flops += 2 * (op + ffn)
            params += op + ffn_params
            if l >= cross_from:
                last_row += flops - before
        per_head = 6 if getattr(cfg, "differential", False) else 4  # (two half-width scores, a double-width value)
        model.per_token_flops = flops
        model.per_ctx_flops = (n_attn + n_cross) * per_head * q
        model.last_row_token_flops, model.last_row_ctx_flops = last_row, n_cross * per_head * q
        model.param_count = params
        model.param_bytes = params * model.dtype_bytes
        model.kv_bytes_per_pos = 2 * n_attn * kv * model.dtype_bytes
        if n_cross:
            model.kv_read_bytes_per_pos = 2 * (n_attn + n_cross) * kv * model.dtype_bytes
        if n_mamba:
            values = cfg.ssm_inner * cfg.ssm_state_size
            model.per_token_flops += n_mamba * 6 * values
            model.state_bytes_per_seq = n_mamba * values * 4
        if n_latent:
            from ..ops.kernels.decode_attention import latent_row_width

            model.per_ctx_flops = n_latent * 2 * cfg.num_heads * (cfg.latent_width + cfg.kv_lora_rank)
            model.kv_bytes_per_pos = n_latent * latent_row_width(cfg.latent_width) * model.dtype_bytes
        if n_ssm:
            # a token's recurrence in every ssm layer (decay, rank-1 update, the product with C: 5 flops a
            # state value) and a live sequence's float32 state, which a decode step reads AND writes
            values = cfg.ssm_inner * cfg.ssm_state_size
            model.per_token_flops += n_ssm * 5 * values
            model.state_bytes_per_seq = n_ssm * values * 4
        model.window = getattr(cfg, "window", 0) if n_window else 0
        model.window_ctx_flops = n_window * per_head * q
        model.window_kv_bytes_per_pos = 2 * n_window * kv * model.dtype_bytes
        return model

    def windowed(self, n_tokens: int, context_sum: int) -> int:
        """Positions the window layers attend for ``n_tokens`` tokens
        whose contexts sum to ``context_sum``: no token more than the
        window (an upper estimate where some contexts are shorter)."""
        return min(context_sum, n_tokens * self.window) if self.window else 0

    def prefill_flops(self, prompt_len: int) -> float:
        """One prompt of ``prompt_len`` true tokens (bucket padding is
        not useful work); causal context sum = n(n+1)/2."""
        n = max(0, prompt_len)
        w = min(n, self.window)
        in_window = w * (w + 1) // 2 + (n - w) * w  # sum over positions of min(position + 1, window)
        whole = n * self.per_token_flops + self.per_ctx_flops * (n * (n + 1) // 2) + self.window_ctx_flops * in_window
        # (a cross-decoder's layers and the head run on the last row alone: the other n - 1 rows' share comes off)
        return whole - max(0, n - 1) * self.last_row_token_flops - self.last_row_ctx_flops * (n * (n - 1) // 2)

    def decode_flops(self, n_active: int, context_sum: int) -> float:
        """One decode step: ``n_active`` live tokens attending to
        ``context_sum`` total live context positions."""
        return (n_active * self.per_token_flops + self.per_ctx_flops * context_sum
                + self.window_ctx_flops * self.windowed(n_active, context_sum))

    def verify_flops(self, n_tokens: int, context_sum: int) -> float:
        """One verify step: ``n_tokens`` live window tokens (committed +
        drafts across slots) with ``context_sum`` live attended
        positions (window token j at position p attends to p+1)."""
        return (n_tokens * self.per_token_flops + self.per_ctx_flops * context_sum
                + self.window_ctx_flops * self.windowed(n_tokens, context_sum))

    def block_flops(self, n_slots: int, context_sum: int, block: int) -> float:
        """One block-diffusion forward: ``n_slots`` live slots of
        ``block`` rows each, every row attending its slot's context
        (``context_sum``: the slots' contexts added up, each its block's
        last position + 1): a verify step's arithmetic at ``block`` rows
        a slot."""
        return self.verify_flops(n_slots * block, context_sum * block)

    # ------------------------------------------ predicted step time (truth)
    def block_bytes(self, n_slots: int, context_sum: int, block: int) -> float:
        """HBM bytes of one block-diffusion forward: weights once, a
        slot's K/V read once for all its ``block`` rows, the block's
        rows written (at every forward: the K/V is provisional until the
        commit)."""
        return self.param_bytes + self.kv_bytes_per_pos * (context_sum + n_slots * block)

    def prefill_bytes(self, prompt_len: int) -> float:
        n = max(0, prompt_len)
        return self.param_bytes + (self.kv_bytes_per_pos + self.window_kv_bytes_per_pos) * n

    def decode_bytes(self, n_active: int, context_sum: int) -> float:
        """HBM bytes for one decode step: weights once, KV read per live
        context position, KV write per active token."""
        read = self.kv_bytes_per_pos if self.kv_read_bytes_per_pos is None else self.kv_read_bytes_per_pos
        return (self.param_bytes + read * context_sum + self.kv_bytes_per_pos * n_active
                + self.window_kv_bytes_per_pos * (self.windowed(n_active, context_sum) + n_active)
                + 2 * self.state_bytes_per_seq * n_active)

    def verify_bytes(self, n_tokens: int, context_sum: int) -> float:
        return (self.param_bytes + self.kv_bytes_per_pos * (context_sum + n_tokens)
                + self.window_kv_bytes_per_pos * (self.windowed(n_tokens, context_sum) + n_tokens))

    def roofline_s(self, flops: float, bytes_hbm: float) -> float:
        """The search cost model's roofline applied to one serving step
        — the PREDICT side of the truth ledger, sharing the same derate
        constants so serving error and search error are comparable."""
        from ..search.cost_model import (  # lazy: avoid import cycle at load
            HBM_EFFICIENCY,
            KERNEL_OVERHEAD,
            MXU_EFFICIENCY,
        )

        t_compute = flops / (self.peak_flops * MXU_EFFICIENCY)
        t_memory = bytes_hbm / (self.chip.hbm_bandwidth * HBM_EFFICIENCY)
        return max(t_compute, t_memory) + KERNEL_OVERHEAD


# --------------------------------------------------------------------------
# Jit program registry + retrace blame
# --------------------------------------------------------------------------


@dataclasses.dataclass
class ProgramEntry:
    """``compile_s`` is the lump: the wall of the host call that traced
    the program (``set_compile_time``). ``cycle`` is the newest record
    of its trace -> lowering -> compile or cache load from JAX's events
    (``trace_s``, ``lower_s``, ``compile_s``: the backend's, ``cache_load_s``,
    ``cache_hit``, and ``run_s`` = the lump less those four), shared
    with the start-up account; None until the program has compiled."""

    name: str
    signature: Dict[str, str]
    traces: int = 1
    compile_s: Optional[float] = None
    last_blame: Optional[str] = None
    cycle: Optional[Dict] = None
    retrace: Optional[Dict] = None  # the newest record in ``retraces``, until its call's wall is stamped

    def split(self) -> Dict:
        """The cycle's parts under the names a registry reports them
        by (``compile_s`` stays the lump there)."""
        c = self.cycle or {}
        return {
            "trace_s": c.get("trace_s"), "lower_s": c.get("lower_s"), "compile_s_backend": c.get("compile_s"),
            "cache_load_s": c.get("cache_load_s"), "cache_hit": c.get("cache_hit"), "run_s": c.get("run_s"),
        }


# --- JAX's compile events, by thread -------------------------------------
# JAX times three sections of a program's first call and says so through
# jax.monitoring: a scalar event when a section opens and a duration
# event when it closes, on the thread that does the work. The trace of a
# jit that calls jits (or runs an eager op, which compiles) opens
# sections INSIDE its own: only a section at depth one is a program's,
# and of those inside it only the backend's seconds are taken out of it
# (a compile is a compile, wherever it ran).
_TRACE = "/jax/core/compile/jaxpr_trace_duration"
_LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_BACKEND = "/jax/core/compile/backend_compile_duration"  # compile, or the persistent cache's load
_SECTIONS = (_TRACE, _LOWER, _BACKEND)
_CACHE_EVENTS = {
    "/jax/compilation_cache/compile_requests_use_cache": "requests",
    "/jax/compilation_cache/cache_hits": "hits",
    "/jax/compilation_cache/cache_misses": "misses",  # fired when the compiled program is written
}


class _CompilingThread(threading.local):
    depth = 0  # sections open on this thread
    noted = None  # (registry, name): the outermost note_trace inside the open top-level trace
    owner = None  # ... of the cycle being built
    cycle = None  # the record of the program this thread is compiling
    module = ""  # the open backend section's program (a backend section holds no other)
    hit = None  # what the cache said inside it: None (not asked), False, True
    inner = (0.0, 0.0, None)  # compile_s, cache_load_s, hit of the backend sections inside the open top-level one


_COMPILING = _CompilingThread()


def _close_cycle(t: _CompilingThread) -> None:
    rec, owner = t.cycle, t.owner
    t.cycle = t.owner = None
    if rec is None:
        return
    if rec["end_s"] is None:  # no compile followed (an executable the process already held)
        rec["end_s"] = rec["at_s"] + sum(rec[k] for k in PROGRAM_PARTS)
    if owner is not None:
        rec["name"] = owner[1]
        owner[0]._credit(owner[1], rec)
    GLOBAL_STARTUP.add_program(rec)


def _on_section_open(event: str, _value: float, **kw) -> None:
    if event not in _SECTIONS:
        return
    t = _COMPILING
    t.depth += 1
    if t.depth == 1:
        t.inner = (0.0, 0.0, None)
        if event == _TRACE:
            t.noted = None
    if event == _BACKEND:
        t.module, t.hit = str(kw.get("fun_name", "")), None


def _on_cache_event(event: str, **_kw) -> None:
    kind = _CACHE_EVENTS.get(event)
    if kind is None:
        return
    t = _COMPILING
    GLOBAL_STARTUP.note_cache(kind, t.module)
    if kind != "misses":
        t.hit = kind == "hits"


def _on_section_close(event: str, seconds: float, **kw) -> None:
    if event not in _SECTIONS:
        return
    t = _COMPILING
    t.depth = max(0, t.depth - 1)
    compile_s, load_s, hit = t.inner
    if t.depth:
        if event == _BACKEND:  # inside another program's section
            t.inner = (compile_s + (0.0 if t.hit else seconds), load_s + (seconds if t.hit else 0.0), both_hit(hit, t.hit))
            t.module = ""
        return
    now = GLOBAL_STARTUP.now()
    if event == _TRACE:
        _close_cycle(t)  # one still open: nothing compiled after its trace
    if t.cycle is None:
        t.owner, t.noted = t.noted, None
        t.cycle = {
            "name": str(kw.get("fun_name", "?")).removeprefix("jit_"), "at_s": now - seconds, "end_s": None,
            **{k: 0.0 for k in PROGRAM_PARTS}, "cache_hit": None, "lump_s": None, "run_s": None,
        }
    rec = t.cycle
    if event == _BACKEND:
        # a hit's whole section (the key's hashing, the read, the load)
        # is the cache's; anything else compiled
        rec["cache_load_s" if t.hit else "compile_s"] += seconds
        rec["cache_hit"], rec["end_s"] = both_hit(rec["cache_hit"], t.hit), now
        t.module = ""
        _close_cycle(t)
    else:
        rec["trace_s" if event == _TRACE else "lower_s"] += seconds - compile_s - load_s
        rec["compile_s"] += compile_s
        rec["cache_load_s"] += load_s
        rec["cache_hit"] = both_hit(rec["cache_hit"], hit)


# once a process (this module is imported once)
jax.monitoring.register_scalar_listener(_on_section_open)
jax.monitoring.register_event_listener(_on_cache_event)
jax.monitoring.register_event_duration_secs_listener(_on_section_close)


def _summarize(x) -> str:
    """Compact signature for one traced argument: ``dtype[shape]`` for
    arrays, a leaf-count/element-count digest for pytrees, a string as
    it is, ``repr`` for other static scalars."""
    if isinstance(x, str):
        return x
    shape = getattr(x, "shape", None)
    if shape is not None:
        dt = getattr(x, "dtype", "?")
        return f"{dt}[{','.join(str(d) for d in shape)}]"
    try:
        import jax

        leaves = [l for l in jax.tree_util.tree_leaves(x) if hasattr(l, "shape")]
    except Exception:
        leaves = []
    if leaves:
        elems = sum(int(_prod(l.shape)) for l in leaves)
        return f"pytree({len(leaves)} leaves, {elems} elems)"
    return repr(x)[:40]


def _prod(shape) -> int:
    out = 1
    for d in shape:
        out *= int(d)
    return out


class ProgramRegistry:
    """Registry of traced jit programs with retrace blame.

    ``note_trace(name, args)`` is called from INSIDE the traced Python
    body (it only runs when XLA traces, the same property the engine's
    ``trace_counts`` relies on). The first trace registers the
    program's argument signature; any later trace diffs against it and
    produces a blame string naming exactly which argument changed shape
    or dtype — the answer "decode retraced: tokens int32[8] ->
    int32[9]" instead of a bare retrace counter.

    ``on_retrace(name, blame)`` (optional) fires on every retrace; the
    scheduler points it at the flight recorder. Exceptions in the
    callback are swallowed: a logging hook must never break tracing.
    """

    def __init__(self, max_retraces: int = 64,
                 clock: Callable[[], float] = time.time):
        # injectable epoch clock for retrace-record stamps (wall time is
        # the right default — operators correlate retraces with logs —
        # but virtual-clock tests must be able to pin it)
        self._clock = clock
        self._lock = threading.Lock()
        self.entries: Dict[str, ProgramEntry] = {}  # guarded-by: _lock
        self.retraces: deque = deque(maxlen=max_retraces)  # guarded-by: _lock
        # programs that compiled and whose call's wall is not stamped yet
        # (written under the lock; a caller tests membership without it)
        self.unstamped: set = set()
        self.on_retrace: Optional[Callable[[str, str], None]] = None

    def note_trace(self, name: str, args: Dict[str, object]) -> Optional[str]:
        """Record one trace of ``name``; returns the blame string when
        this is a retrace, else None."""
        sig = {k: _summarize(v) for k, v in args.items()}
        with self._lock:
            entry = self.entries.get(name)
            if entry is None:
                self.entries[name] = ProgramEntry(name=name, signature=sig)
                self._noted(name)
                return None
            entry.traces += 1
            diffs = []
            for k in sig:
                old = entry.signature.get(k)
                if old != sig[k]:
                    diffs.append(f"{k} {old if old is not None else '<absent>'} -> {sig[k]}")
            for k in entry.signature:
                if k not in sig:
                    diffs.append(f"{k} {entry.signature[k]} -> <absent>")
            if diffs:
                blame = f"{name} retraced: " + ", ".join(diffs)
            else:
                blame = (
                    f"{name} retraced: identical signature "
                    "(jit cache eviction or weak-type change)"
                )
            entry.signature = sig
            entry.last_blame = blame
            entry.retrace = {
                "t": self._clock(),
                "program": name,
                "blame": blame,
                "traces": entry.traces,
            }
            self.retraces.append(entry.retrace)
            cb = self.on_retrace
        self._noted(name)
        if cb is not None:
            try:
                cb(name, blame)
            except Exception:
                pass  # observability must never break tracing
        return blame

    def _noted(self, name: str) -> None:
        """This thread is inside ``name``'s traced body: the compile
        events that follow on it are this program's. The OUTERMOST
        program of a trace keeps them (a jit that calls an instrumented
        jit holds the inner one's trace in its own)."""
        t = _COMPILING
        if t.depth and t.noted is None:
            t.noted = (self, name)

    def _credit(self, name: str, cycle: Dict) -> None:
        """``cycle`` is what JAX's events said of ``name``'s compile; a
        retrace's record carries the same split beside its blame."""
        with self._lock:
            entry = self.entries.get(name)
            if entry is None:
                return
            entry.cycle = cycle
            self.unstamped.add(name)
            if entry.retrace is not None:
                entry.retrace.update(entry.split())

    def set_compile_time(self, name: str, seconds: float) -> None:
        """Stamp the wall time of the host call that triggered the
        program's (re)trace: the lump, ``compile_s``. Its named parts
        are the cycle's ``trace_s + lower_s + compile_s + cache_load_s``
        (JAX's events, on this thread, since ``note_trace``); what is
        left, ``run_s``, is the call's argument preparation, its first
        run and its readback. Ends the attribution: an event that fires
        on this thread afterwards is another program's."""
        t = _COMPILING
        if t.owner == (self, name):
            _close_cycle(t)
        t.noted = None
        with self._lock:
            entry = self.entries.get(name)
            if entry is None:
                return
            entry.compile_s = seconds
            self.unstamped.discard(name)
            cycle = entry.cycle
            if cycle is not None and cycle["lump_s"] is None:
                cycle["lump_s"] = seconds
                cycle["run_s"] = seconds - sum(cycle[k] for k in PROGRAM_PARTS)
                cycle["end_s"] = max(cycle["end_s"], GLOBAL_STARTUP.now())
            if entry.retrace is not None:
                entry.retrace.update(entry.split(), compile_s=seconds)
                entry.retrace = None

    def instrument(self, name: str, fn: Callable, **static: str) -> Callable:
        """Wrap ``fn`` for ``jax.jit`` so every trace self-registers
        (the wrapper body runs at trace time only — zero steady-state
        cost). Used for the executor's train/eval programs, where
        arguments are anonymous pytrees. ``static`` names what the
        program was built as beside its arguments (the train step's
        ``loss_form``) and rides in its signature. The wrapper takes the
        program's own name (``executor[3].train_window[16]`` ->
        ``train_window_16``), which is what jit names the XLA module
        by: a device trace then shows ``jit_train_step``, not one
        ``jit_traced`` for every program."""

        def traced(*args, **kwargs):
            sig = {f"arg{i}": a for i, a in enumerate(args)}
            sig.update(kwargs)
            sig.update(static)
            self.note_trace(name, sig)
            return fn(*args, **kwargs)

        traced.__name__ = traced.__qualname__ = (
            re.sub(r"\W+", "_", name.rpartition(".")[2]).strip("_") or "traced"
        )
        return traced

    def remove_namespace(self, prefix: str) -> None:
        """Drop every program named ``prefix`` or ``prefix.*`` (and its
        retrace records). Executors register under per-instance
        namespaces and evict them via a weakref finalizer, so a process
        that builds executors in a loop does not grow the global
        registry without bound."""
        dot = prefix + "."
        with self._lock:
            for name in [n for n in self.entries
                         if n == prefix or n.startswith(dot)]:
                del self.entries[name]
                self.unstamped.discard(name)
            kept = [r for r in self.retraces
                    if not (r["program"] == prefix or r["program"].startswith(dot))]
            self.retraces.clear()
            self.retraces.extend(kept)

    def snapshot(self) -> List[Dict]:
        with self._lock:
            return [
                {
                    "name": e.name,
                    "traces": e.traces,
                    "compile_s": e.compile_s,
                    **e.split(),
                    "signature": dict(e.signature),
                    "last_blame": e.last_blame,
                }
                for e in sorted(self.entries.values(), key=lambda e: e.name)
            ]

    def recent_retraces(self) -> List[Dict]:
        with self._lock:
            return [dict(r) for r in self.retraces]

    def trace_count(self, name: str) -> int:
        """Traces recorded for one program (0 if never traced) — callers
        compare before/after a host call to tell compiles from
        steady-state runs (the truth ledger excludes compile calls)."""
        with self._lock:
            entry = self.entries.get(name)
            return entry.traces if entry is not None else 0

    def total_retraces(self) -> int:
        with self._lock:
            return sum(max(0, e.traces - 1) for e in self.entries.values())


# Executor programs register here (runtime/executor.py); the server
# merges this registry into GET /v2/debug/programs under "executor".
GLOBAL_PROGRAMS = ProgramRegistry()
