"""Generation engine: prefill/decode split over the block KV cache.

XLA has no dynamic shapes, so naive generation recompiles on every
prompt length and batch size. The engine compiles a FIXED family of
programs instead:

* **prefill** — one jitted program per *prompt-length bucket* (prompt
  padded up to the bucket; per-sequence length masking keeps logits
  identical to the unpadded forward). A handful of buckets covers every
  prompt, and a bucket compiles at most once.
* **decode** — ONE jitted program, period: always ``max_batch_slots``
  sequences (inactive slots masked to scratch block 0), always the same
  block-table width. Steady-state decode NEVER recompiles, whatever
  joins or leaves the batch (tests/test_generation.py::
  test_steady_state_decode_never_recompiles; the benchmark's ``correct``
  needs zero programs traced inside a window).

``trace_counts`` counts actual retraces (the Python body only runs at
trace time), so tests and the benchmark can assert the compile behavior
instead of trusting it.

Sampling (greedy / temperature / top-k) runs inside the jitted steps —
per-slot parameters are arrays, so mixed sampling configs share one
program — and since ISSUE 13 the per-slot PRNG keys ALSO derive in-jit
from (seed, generated-token count), bit-identical to the old host
fold_in, so the hot loop assembles no keys at all.

ISSUE 13's overlap support: :meth:`GenerationEngine.decode_async` /
:meth:`GenerationEngine.consume_decode` split one decode step into a
non-blocking dispatch (token array carried device-resident from the
previous step, async host copy of the results started at
dispatch-return) and a later consume — the scheduler's two-deep
pipeline. Slot-constant args stage device-resident (``_stage``), and
``donate_cache`` aliases the decode/verify jits' KV-cache inputs to
their outputs (in-place update; auto on accelerators).

A BLOCK-DIFFUSION configuration (``DecoderConfig.block_mask`` = B with a
:class:`BlockDiffusion` rule, given at construction) has ``block_step``
in the decode program's place: ONE jitted program over every slot's
block of B rows that see each other, with the generation rule (the
candidates, their confidences, the rows fixed, commits) inside it after
the head and the slots' blocks, flags, bases and forward counts carried
on the device from step to step. The step is split like the decode
step: :meth:`GenerationEngine.block_async` dispatches without blocking
(given an unconsumed predecessor it takes that step's device results as
its state, whatever the host has bookkept) and
:meth:`GenerationEngine.consume_block` collects a result one scheduler
iteration later; :meth:`GenerationEngine.block_step` is the pair back to
back (``scheduler._scatter_block`` emits what became final, in position
order). Its prefill caches the prompt's whole blocks under the block
mask and computes no logits.

ISSUE 15 made the engine MESH-NATIVE: pass ``tp_degree`` /
``mesh_devices`` / ``mesh`` and the decoder weights + KV cache shard
along the head axis over a ``"model"`` mesh axis
(generation/sharding.py), every jit is built with explicit
out-shardings, every non-sharded input commits replicated through one
staging path (call-stable input shardings — the retrace contract), and
the serving TP degree is chosen by the Unity-style search + cost model
(search/serving_strategy.py). No mesh arguments -> the legacy
single-device paths, untouched; a 1-device mesh is bit-for-bit the
legacy engine.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core.types import DataType
from ..device import on_tpu
from ..models.transformer import TransformerConfig
from ..obs.capacity import ProgramRegistry, ServingFlops
from ..obs.steptrace import GLOBAL_STARTUP, phase
from ..obs.truth import PredictionLedger
from ..ops.attention import STREAM_SCORE_BYTES, latent_call_lowering, paged_call_lowering, prefill_call_lowering
from ..ops import ssm as ssm_ops
from ..ops.expert_product import expert_lowering
from ..runtime import faults
from .cache import (
    BlockAllocator, CacheConfig, KVCache, SlotStateConfig, StateConfig, WindowTable, pools_from_budget, slot_mapping,
)
from .decoder import (
    DecoderParams,
    block_step,
    decode_step,
    decoder_config,
    prefill,
    state_at,
    verify_step,
    write_rows,
)
from .prefix import KVHandoffPayload, PackedBlock, PrefixCache, PrefixEntry
from .sharding import ServingLayout

NEG_INF = -1e30


@dataclasses.dataclass
class PrefixPlan:
    """Admission-time reuse decision for one prompt (engine.prefix_plan):
    the cached entries to share, the boundary entry to COW-copy when the
    prompt is fully covered (its last position must still be recomputed
    for logits, and that write lands inside the last matched block), the
    token count reuse covers, and how many shared entries are already
    device-resident (the rest swap in from the host tier)."""

    entries: List[PrefixEntry]
    cow: Optional[PrefixEntry]
    reuse_tokens: int
    n_resident: int


EMPTY_PREFIX_PLAN = PrefixPlan([], None, 0, 0)
# the staged entries that are a decode program's RESULTS (engine._staged)
CARRIED = ("decode.positions", "decode.counts")
# and a block step's: every slot's block (tokens, 0 / 1 flags of the rows
# that are fixed), its base position and the denoising forwards it has had
BLOCK_CARRIED = ("block.tokens", "block.fixed", "block.base", "block.forwards")
REMASKING = ("low_confidence_static", "low_confidence_dynamic")


@dataclasses.dataclass(frozen=True)
class BlockDiffusion:
    """The generation rule of a block-diffusion model (a configuration
    whose ``block_mask`` is ``block_length``), given to the engine at
    construction: a served model has ONE block length (the cache's
    positions and the prefill's mask depend on it).

    Positions are laid out in blocks of ``block_length`` from 0. A block
    starts as the prompt's remaining tokens (fixed) followed by the mask
    token at every other row; a denoising forward runs the block's rows
    and each masked row's candidate is its best token other than the
    mask token, its confidence that token's softmax probability
    (float32, whole vocabulary). ``low_confidence_static`` fixes the
    ``block_length / denoising_steps`` masked rows of highest confidence
    (ties to the lower position); ``low_confidence_dynamic`` every
    masked row whose confidence passes ``threshold``, and the static
    rule's rows if fewer pass. A fixed row stays fixed; when no row is
    masked a commit forward runs the block over its final tokens, and
    the K/V of THAT forward is what later blocks read.
    ``denoising_steps``, ``remasking`` and ``threshold`` are defaults a
    request may override (``SamplingParams``)."""

    block_length: int = 4
    denoising_steps: int = 4
    remasking: str = "low_confidence_static"
    threshold: float = 0.9
    mask_token_id: int = 0

    def __post_init__(self):
        if self.block_length < 1 or self.mask_token_id < 0:
            raise ValueError(f"block_length {self.block_length}, mask_token_id {self.mask_token_id}")
        self.rows_per_forward(self.denoising_steps)
        self.threshold_of(self.remasking, self.threshold)

    def rows_per_forward(self, denoising_steps: Optional[int] = None) -> int:
        """Rows the static rule fixes a denoising forward."""
        steps = self.denoising_steps if denoising_steps is None else int(denoising_steps)
        if steps < 1 or self.block_length % steps:
            raise ValueError(f"{steps} denoising steps do not divide a block of {self.block_length}")
        return self.block_length // steps

    def threshold_of(self, remasking: Optional[str] = None, threshold: Optional[float] = None) -> float:
        """The confidence a row has to pass to be fixed beyond the
        static rule's rows: no confidence passes 2 (the static rule)."""
        rule = self.remasking if remasking is None else remasking
        if rule not in REMASKING:
            raise ValueError(f"remasking {rule!r}: one of {REMASKING}")
        return 2.0 if rule == "low_confidence_static" else float(self.threshold if threshold is None else threshold)


@dataclasses.dataclass(frozen=True)
class SamplingParams:
    """Per-request sampling configuration.

    ``temperature <= 0`` means greedy (argmax); ``top_k <= 0`` disables
    the top-k filter. ``seed`` makes the request's sampling stream
    deterministic — preemption-by-recompute replays the same stream.
    Seeds are folded as 32-bit values everywhere (the decode/verify
    jits derive keys in-jit from a uint32 seed): values outside
    [0, 2**32) truncate, consistently across prefill/decode/replay.
    """

    max_new_tokens: int = 16
    temperature: float = 0.0
    top_k: int = 0
    eos_id: Optional[int] = None
    seed: int = 0
    # a block-diffusion engine's defaults (:class:`BlockDiffusion`),
    # overridden for this request; None: the engine's
    denoising_steps: Optional[int] = None
    remasking: Optional[str] = None
    threshold: Optional[float] = None


def default_buckets(max_seq_len: int, start: int = 16) -> Tuple[int, ...]:
    """Doubling prompt-length buckets: start, 2*start, ... up to (and
    including) max_seq_len."""
    buckets: List[int] = []
    b = min(start, max_seq_len)
    while b < max_seq_len:
        buckets.append(b)
        b *= 2
    buckets.append(max_seq_len)
    return tuple(buckets)


def topk_scaled_logits(logits, temps, top_ks):
    """Temperature-scaled, top-k-masked logits — THE sampling transform
    for both the decode step and speculative verification (speculative/
    sampling.py imports this one; two copies drifting apart would break
    the zero-draft-verify ≡ decode bit-exactness contract).

    logits [..., V]; temps/top_ks shaped logits.shape[:-1] (callers
    broadcast). temp <= 0 rows are scaled by 1 (greedy callers argmax
    the RAW logits); top_k <= 0 disables the top-k filter.

    Only a batch in which some row sets ``top_k`` sorts the vocabulary,
    decided in the program (``lax.cond``: one compiled program, one
    branch run); the two branches return the same values, bit for bit.
    """
    v = logits.shape[-1]
    safe_t = jnp.where(temps <= 0.0, 1.0, temps)
    scaled = logits / safe_t[..., None]

    def unfiltered():
        # k = V keeps everything but NaN (no comparison with NaN holds)
        return jnp.where(jnp.isnan(scaled), NEG_INF, scaled)

    def by_sort():
        k = jnp.where(top_ks <= 0, v, jnp.clip(top_ks, 1, v)).astype(jnp.int32)
        sorted_desc = jnp.flip(jnp.sort(scaled, axis=-1), axis=-1)
        thresh = jnp.take_along_axis(sorted_desc, k[..., None] - 1, axis=-1)
        return jnp.where(scaled >= thresh, scaled, NEG_INF)

    return jax.lax.cond(jnp.any(top_ks > 0), by_sort, unfiltered)


def _sample(logits, temps, top_ks, keys):
    """Vectorized sampling: greedy where temp<=0, else temperature +
    optional top-k. logits [B, V]; temps/top_ks [B]; keys [B] PRNG.
    A batch whose rows are all greedy runs the argmax and nothing else."""
    v = logits.shape[-1]

    def greedy():
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)

    def mixed():
        masked = topk_scaled_logits(logits, temps, top_ks)
        gumbel = jax.vmap(lambda key: jax.random.gumbel(key, (v,)))(keys)
        sampled = jnp.argmax(masked + gumbel, axis=-1)
        return jnp.where(temps <= 0.0, jnp.argmax(logits, axis=-1), sampled).astype(jnp.int32)

    return jax.lax.cond(jnp.all(temps <= 0.0), greedy, mixed)


def sampling_branch(temps: np.ndarray, top_ks: np.ndarray) -> str:
    """Which branch of :func:`_sample` a step with these host arrays
    takes on the device (the predicates above, on the host)."""
    if np.all(temps <= 0.0):
        return "greedy"
    return "sort" if np.any(top_ks > 0) else "plain"


def derive_keys(seeds, counts):
    """Per-slot sampling keys derived IN-JIT from (seed, generated-token
    count): ``fold_in(key(seed), count)`` — bit-identical to the host
    derivation the scheduler used before ISSUE 13 (``Request.base_key``
    + ``fold_in`` by count), so seeded streams are unchanged while the
    host ``sample`` phase (per-request fold_in + stack, a real host
    dispatch per step) disappears from the critical path. Seeds are
    folded as 32-bit values; seeds >= 2**32 truncate."""
    return jax.vmap(lambda s, c: jax.random.fold_in(jax.random.key(s), c))(
        seeds, counts
    )


def derive_window_keys(seeds, counts, window: int):
    """[B, window] keys for a speculative window: key j of slot b is
    ``fold_in(key(seeds[b]), counts[b] + j)`` — the same per-emitted-
    count indexing the host-side ``Request.sample_keys`` used."""
    offs = jnp.arange(window, dtype=jnp.int32)
    return jax.vmap(
        lambda s, c: jax.vmap(
            lambda j: jax.random.fold_in(jax.random.key(s), c + j)
        )(offs)
    )(seeds, counts)


def unsupported_paths(kind: str, dcfg) -> Dict[str, str]:
    """``{path: reason}`` for the paths that cannot carry what a layer of
    ``kind`` keeps per sequence, each refused by name when it is taken
    (``GenerationEngine.unsupported``; ROADMAP "What the system cannot
    run yet")."""
    w = dcfg.window
    b = dcfg.block_mask
    return {
        "diffusion": {
            "speculation": (
                f"speculative verification (engine.verify) is refused for a block-diffusion configuration (blocks of "
                f"{b}): a step already scores a whole block of positions that see each other, and a drafted window "
                f"is a causal one"
            ),
            "constrained_decoding": (
                f"constrained decoding is refused for a block-diffusion configuration (blocks of {b}): a grammar "
                f"mask is a left-to-right automaton, and a block's rows are fixed out of order"
            ),
            "kv_handoff": (
                f"the disaggregation wire (pack_kv_blocks / import_kv_block(s)) is refused for a block-diffusion "
                f"configuration (blocks of {b}): a prefill yields no first token to hand over, and the decode side "
                f"would need the block in flight beside the committed K/V"
            ),
            "tensor_parallel": (
                f"tp_degree > 1 is refused for a block-diffusion configuration (blocks of {b}): the head-sharded "
                f"paged kernel takes a window's rows at their own positions, not a block's shared attend bound, and "
                f"the serving layout has no placement for the expert weights"
            ),
        },
        "ssm": {
            "speculation": (
                "speculative verification (engine.verify) is refused for a configuration with state-space "
                "layers: every window row's recurrent state would have to be kept, megabytes a row, to choose "
                "one at the accepted length"
            ),
            "kv_handoff": (
                "the disaggregation wire (pack_kv_blocks / import_kv_block(s)) is refused for a configuration "
                "with state-space layers: a payload carries K/V blocks and no recurrent state, so the decode "
                "side could not continue from it"
            ),
            "tensor_parallel": (
                "tp_degree > 1 is refused for a configuration with state-space layers: the serving layout "
                "shards attention heads and the FFN, and has no placement for the state-space mixer, its "
                "per-slot state or the expert weights"
            ),
            "prefix_reuse": (
                "prefix reuse is off: a state-space layer's state exists per slot and nowhere else (a "
                "snapshot a cached block would be a sequence's whole state a block), so no cached prefix "
                "could be resumed; every prompt is prefilled whole"
            ),
        },
        "samba": {
            "speculation": (
                "speculative verification (engine.verify) is refused for a decoder-hybrid-decoder configuration "
                "(Mamba-1, gmu and cross layers): every window row's recurrent state and its memory row would have "
                "to be kept, megabytes a row, to choose one at the accepted length, and a rejected draft would have "
                "to give back its window blocks"
            ),
            "kv_handoff": (
                "the disaggregation wire (pack_kv_blocks / import_kv_block(s)) is refused for a decoder-hybrid-decoder "
                "configuration: a payload carries one table's K/V blocks, and the decode side would need the Mamba-1 "
                "layers' per-slot state and the window layers' last blocks beside the one shared K/V"
            ),
            "tensor_parallel": (
                "tp_degree > 1 is refused for a decoder-hybrid-decoder configuration: the serving layout shards "
                "attention heads and the FFN, and has no placement for the Mamba-1 mixer, its per-slot state, the "
                "gated memory units or differential attention's paired heads; the head-sharded paged kernel takes "
                "no window"
            ),
            "prefix_reuse": (
                "prefix reuse is off: a Mamba-1 layer's state exists per slot and nowhere else, and the gmu layers' "
                "memory is never cached at all, so no cached prefix could be resumed; every prompt is prefilled "
                "whole (its cross-decoder on the last row alone)"
            ),
        },
        "conv": {
            "speculation": (
                "speculative verification (engine.verify) is refused for a configuration "
                "with convolution layers: the window's z rows would have to be kept and "
                "each slot's convolution state chosen at its accepted length"
            ),
            "kv_handoff": (
                "the disaggregation wire (pack_kv_blocks / import_kv_block(s)) is refused "
                "for a configuration with convolution layers: a payload carries K/V blocks "
                "and no convolution state, so the decode side could not continue from it"
            ),
            "tensor_parallel": (
                "tp_degree > 1 is refused for a configuration with convolution layers: the "
                "serving layout shards attention heads and the FFN, and has no placement "
                "for the convolution operator, its state or the expert weights"
            ),
        },
        "window": {
            "speculation": (
                f"speculative verification (engine.verify) is refused for a configuration with "
                f"sliding-window layers (window {w}): a rejected draft would have to give back the "
                f"window blocks its positions took and take back those they released"
            ),
            "kv_handoff": (
                f"the disaggregation wire (pack_kv_blocks / import_kv_block(s)) is refused for a "
                f"configuration with sliding-window layers (window {w}): a payload carries one table's "
                f"blocks, and the decode side would need the window layers' for the last {w} positions"
            ),
            "tensor_parallel": (
                f"tp_degree > 1 is refused for a configuration with sliding-window layers (window {w}): "
                f"the head-sharded paged kernel takes no window, and the window pool has no sharding"
            ),
        },
        "parallel": {
            "speculation": (
                "speculative verification (engine.verify) is refused for a configuration whose block is "
                "parallel (one norm, attention and experts side by side): no drafter of such a model is "
                "loaded, and its layers' window blocks could not be given back on a rejection"
            ),
            "kv_handoff": (
                "the disaggregation wire (pack_kv_blocks / import_kv_block(s)) is refused for a "
                "configuration whose block is parallel: it is served with window layers beside full "
                "ones, and a payload carries one table's blocks"
            ),
            "tensor_parallel": (
                "tp_degree > 1 is refused for a configuration whose block is parallel: the serving "
                "layout has no placement for data-parallel attention beside shares of experts and of "
                "the vocabulary, and the head-sharded paged kernel takes no window"
            ),
        },
        "shortcut": {
            "speculation": (
                "speculative verification (engine.verify) is refused for a configuration with a shortcut expert "
                "branch: it is served over latent layers, whose verify program takes K and V of one head width, "
                "and no drafter of such a model is loaded"
            ),
            "kv_handoff": (
                "the disaggregation wire (pack_kv_blocks / import_kv_block(s)) is refused for a configuration "
                "with a shortcut expert branch: it is served over latent layers, and a latent block is one array "
                "of rows that no importing engine's shape check knows"
            ),
            "tensor_parallel": (
                "tp_degree > 1 is refused for a configuration with a shortcut expert branch: the serving layout "
                "has no placement for data-parallel latent attention and dense feed-forwards beside a share of "
                "the experts, nor for the branch's sum held across a sub-layer"
            ),
        },
        "latent": {
            "speculation": (
                "speculative verification (engine.verify) is refused for a configuration with latent "
                "layers: the verify program takes K and V of one head width, and the model's own "
                "next-token module, the drafter such a model is served with, is not loaded"
            ),
            "kv_handoff": (
                "the disaggregation wire (pack_kv_blocks / import_kv_block(s)) is refused for a "
                "configuration with latent layers: the wire format is K and V blocks of heads, and a "
                "latent block is one array of rows that no importing engine's shape check knows"
            ),
            "tensor_parallel": (
                "tp_degree > 1 is refused for a configuration with latent layers: one latent row a "
                "position cannot be sharded by head, and the serving layout has no placement for "
                "data-parallel attention beside shares of experts"
            ),
        },
    }[kind]


class InFlightDecode:
    """One dispatched-but-unconsumed decode step (the overlap pipeline's
    frontier unit). Holds the device result refs, the async host copies
    started at dispatch-return (double-buffered readback), the pre-step
    cache refs for rollback on failure (None when the jit donates its
    cache buffers — a failed donated step is only recoverable by
    ``engine.reset()`` + journal replay), and the dispatch timestamps
    the step-anatomy profiler renders. Created by
    :meth:`GenerationEngine.decode_async`, consumed exactly once by
    :meth:`GenerationEngine.consume_decode`. Loop-thread only."""

    __slots__ = (
        "out", "ok", "prev_k", "prev_v", "prev_conv", "prev_window", "prev_counts", "ck", "cv", "t0", "t_disp",
        "t_started", "traced", "n_active", "ctx_sum", "consumed", "post", "children",
    )

    def __init__(self, out, ok, prev_k, prev_v, ck, cv, t0, t_disp, traced, n_active, ctx_sum,
                 prev_conv=None, prev_counts=None, prev_window=None):
        self.out = out
        self.ok = ok
        self.prev_k = prev_k
        self.prev_v = prev_v
        # the slots' convolution state before the step: rolled back with
        # K/V (None for a configuration without one)
        self.prev_conv = prev_conv
        # the window layers' K/V before the step ({"wk", "wv"}; None
        # without window layers), rolled back likewise
        self.prev_window = prev_window
        # the expert counters before the step (never donated, so always
        # held): a failed step's own are poisoned with its other results
        self.prev_counts = prev_counts
        # this step's cache outputs: rollback applies only while these
        # are still the engine's current refs (a failed chain is rolled
        # back once, to the OLDEST intact refs, never forward again)
        self.ck = ck
        self.cv = cv
        self.t0 = t0
        self.t_disp = t_disp
        # restamped by the scheduler when the PREVIOUS in-flight step
        # completes: with a one-deep pipeline this step only starts
        # executing then, so the execute span (and the watchdog's view
        # of its age) is measured from here, not from dispatch. None
        # (finish_decode sets it): from where the host parks
        self.t_started = t_disp
        self.traced = traced
        self.n_active = n_active
        self.ctx_sum = ctx_sum
        self.consumed = False
        # the dispatch's sibling span (``post``: the device-to-host
        # copies, the cache swap, this handle) and the dispatch span's
        # children (``args`` / ``upload`` / ``call``), for the
        # scheduler's anatomy; set by decode_async once both have closed
        self.post: Tuple[str, float, float] = ("post", t_disp, t_disp)
        self.children: List[Tuple[str, float, float]] = []


class InFlightBlock(InFlightDecode):
    """One dispatched-but-unconsumed block step: an
    :class:`InFlightDecode` (``out`` the blocks' tokens after the
    forward, what a rollback needs, the stamps) with the rule's other
    results (``chosen``, ``commit``), the state the program returned for
    the next forward (``next_dev``, what a successor dispatched before
    this step is consumed takes as its arguments) and the host's side of
    the state the forward RAN on: ``pre`` where the host staged it,
    else ``prev``, the predecessor whose ``nxt`` (its results applied to
    its own state, computed when it is consumed, which is always first)
    is that state. ``active`` [slots] int32: the slots the forward ran.
    Created by :meth:`GenerationEngine.block_async`, consumed exactly
    once by :meth:`GenerationEngine.consume_block`. Loop-thread only."""

    __slots__ = ("chosen", "commit", "next_dev", "pre", "prev", "nxt", "active")

    def __init__(self, results, next_dev, pre, prev, active, **step):
        out, self.chosen, self.commit, ok = results
        super().__init__(out, ok, n_active=0, ctx_sum=0, **step)  # (counted at the consume, over the slots still running)
        self.next_dev = next_dev
        self.pre = pre
        self.prev = prev
        self.nxt = None
        self.active = active


class GenerationEngine:
    """Owns the cache, the allocator, and the jitted step family. The
    continuous-batching scheduler drives it; ``generate`` is a
    convenience wrapper that spins up a private scheduler."""

    # pools, staging, tables, the kernels chosen: ff.startup.engine_build
    @GLOBAL_STARTUP.spanned("engine_build")
    def __init__(
        self,
        params: DecoderParams,
        cfg: TransformerConfig,
        cache_config: Optional[CacheConfig] = None,
        *,
        cache_budget_bytes: Optional[int] = None,
        max_batch_slots: int = 4,
        prompt_buckets: Optional[Sequence[int]] = None,
        max_seq_len: Optional[int] = None,
        block_size: int = 16,
        max_spec_tokens: int = 4,
        prefix_cache: bool = True,
        host_cache_bytes: Optional[int] = None,
        donate_cache: Optional[bool] = None,
        mesh=None,
        tp_degree: Optional[int] = None,
        mesh_devices: Optional[int] = None,
        expected_prefix_sharing: float = 0.0,
        diffusion: Optional[BlockDiffusion] = None,
    ):
        self.cfg = cfg
        # the block's choices as data (decoder.py): a plain
        # TransformerConfig is the GPT-2 setting of every one of them
        self.dcfg = decoder_config(cfg)
        self.max_seq_len = max_seq_len or cfg.seq_length
        self.max_batch_slots = max_batch_slots
        # paths that cannot carry a layer's per-sequence state yet, each
        # refused by name (ROADMAP "What the system cannot run yet"):
        # `unsupported[path]` is the reason, raised when the path is taken
        self.unsupported: Dict[str, str] = {}
        wants_tp = (
            (tp_degree or 1) > 1
            or (mesh is not None and int(dict(mesh.shape).get("model", 1)) > 1)
            or (tp_degree is None and mesh is None and (mesh_devices or 1) > 1)
        )
        if self.dcfg.window_layers and self.dcfg.conv_layers:
            raise NotImplementedError(
                "a configuration with both convolution and sliding-window layers is refused: a "
                "cached block would carry a convolution snapshot and a window half at once"
            )
        for kind in ("conv", "window", "latent", "ssm"):
            if kind in self.dcfg.layer_types:
                self.unsupported.update(unsupported_paths(kind, self.dcfg))
        if self.dcfg.block == "parallel":
            self.unsupported.update(unsupported_paths("parallel", self.dcfg))
        if self.dcfg.shortcut_experts:
            self.unsupported.update(unsupported_paths("shortcut", self.dcfg))
        if self.dcfg.mamba_layers or self.dcfg.gmu_layers or self.dcfg.cross_layers or self.dcfg.differential:
            self.unsupported.update(unsupported_paths("samba", self.dcfg))
        # the generation rule of a block-diffusion model (its steps are
        # block steps, and no decode step ever runs); None: one token a
        # sequence a step under the causal mask
        self.diffusion = diffusion
        if (diffusion.block_length if diffusion else 0) != self.dcfg.block_mask:
            raise ValueError(
                f"a configuration with block_mask {self.dcfg.block_mask} is generated by block diffusion over blocks "
                f"of that length, and no other is (diffusion={diffusion})"
            )
        if diffusion is not None:
            if diffusion.mask_token_id >= cfg.vocab_size:
                raise ValueError(f"mask_token_id {diffusion.mask_token_id} outside the vocabulary of {cfg.vocab_size}")
            self.unsupported.update(unsupported_paths("diffusion", self.dcfg))
        if wants_tp and "tensor_parallel" in self.unsupported:
            raise NotImplementedError(self.unsupported["tensor_parallel"])
        # ------------------------------------------------- serving mesh
        # Mesh-native engine (ISSUE 15): decoder weights and the KV
        # cache shard along the head axis over a "model" mesh axis
        # (generation/sharding.py). Three ways in:
        #   mesh=          an explicit Mesh carrying a "model" axis
        #   tp_degree=N    a pinned degree (serving_mesh over N devices)
        #   mesh_devices=N devices to serve on; the TP degree is CHOSEN
        #                  by the existing Unity-style search + cost
        #                  model (search/serving_strategy.py)
        # All None -> the legacy single-device engine, untouched paths.
        # A 1-device mesh is bit-for-bit the legacy engine — the
        # exactness anchor the multi-device gates compare against.
        self.layout: Optional[ServingLayout] = None
        self.serving_strategy = None
        if mesh is not None or tp_degree is not None or mesh_devices is not None:
            from ..search.serving_strategy import choose_serving_strategy

            if mesh is not None:
                from ..parallel.mesh import MODEL_AXIS

                tp = int(mesh.shape.get(MODEL_AXIS, 1))
                self.layout = ServingLayout.build(self.dcfg.cache_kv_heads, tp, mesh=mesh)
            else:
                n_dev = mesh_devices or tp_degree
            self.serving_strategy = choose_serving_strategy(
                cfg,
                mesh_devices=(
                    self.layout.mesh.size if self.layout is not None else n_dev
                ),
                max_batch_slots=max_batch_slots,
                prefill_len=self.max_seq_len,
                pinned_tp=(
                    self.layout.tp_degree if self.layout is not None
                    else tp_degree
                ),
            )
            if self.layout is None:
                self.layout = ServingLayout.build(
                    self.dcfg.cache_kv_heads, self.serving_strategy.tp_degree
                )
        self.tp_degree = self.layout.tp_degree if self.layout else 1
        self.mesh_devices = self.layout.mesh.size if self.layout else 1
        self.params = (
            self.layout.shard_params(params) if self.layout else params
        )
        self.buckets = tuple(sorted(prompt_buckets or default_buckets(self.max_seq_len)))
        # the window layers' pool (cache.py): sized for what a sequence
        # can hold of it, plus the one admission whose suffix prefill
        # reads a matched prefix's window beside its own blocks
        self.window_config: Optional[CacheConfig] = None
        self.window_columns = 0
        if self.dcfg.window_layers:
            wkv = dict(
                num_layers=len(self.dcfg.window_layers), num_heads=self.dcfg.cache_kv_heads,
                head_dim=self.dcfg.cache_head_dim, block_size=cache_config.block_size if cache_config else block_size,
                dtype=cfg.dtype, window=self.dcfg.window,
            )
            if cache_config is None and cache_budget_bytes is not None:
                fkv = dict(wkv, num_layers=len(self.dcfg.full_layers), window=0)
                cache_config, self.window_config = pools_from_budget(
                    cache_budget_bytes, self.max_seq_len, fkv, wkv
                )
            else:
                self.window_config = CacheConfig.for_slots(
                    max_seq_len=self.max_seq_len, max_batch_slots=max_batch_slots,
                    extra_blocks=-(-max(self.buckets[-1], self.max_seq_len) // wkv["block_size"]), **wkv,
                )
            # what a live sequence keeps of it at most: the columns of a decode step's window table
            self.window_columns = self.window_config.blocks_per_sequence(self.max_seq_len)
            need = 1 + max_batch_slots * self.window_columns
            if self.window_config.num_blocks < need:
                raise ValueError(
                    f"the window layers' pool holds {self.window_config.num_blocks} blocks; {max_batch_slots} "
                    f"slots need {need} (a live sequence keeps up to {self.window_columns}, and the release counts on it)"
                )
        # the third kind (cache.py SlotStateConfig): what the state-space
        # layers keep per sequence, per slot ONLY: the last rows of the
        # convolution's input and the recurrent state as ops/ssm.py stores it
        self.slot_state: Optional[SlotStateConfig] = None
        if self.dcfg.ssm_layers:
            d = self.dcfg
            self.slot_state = SlotStateConfig(
                num_layers=len(d.ssm_layers), slots=max_batch_slots,
                parts=(
                    ("ssm_conv", (d.ssm_conv_kernel - 1, d.ssm_conv_width), cfg.dtype),
                    # (a Mamba-1 layer's: [N, D], a decay for every pair and no heads to pack)
                    ("ssm", ssm_ops.selective_state_shape(d.ssm_inner, d.ssm_state_size) if d.mamba_layers
                     else ssm_ops.state_shape(d.ssm_heads, d.ssm_head_dim, d.ssm_groups, d.ssm_state_size), DataType.FLOAT),
                ),
            )
        if cache_config is None:
            # K/V is paged for the ATTENTION layers alone, at the K/V
            # heads' width, in the type the configuration serves in
            # (the FULL layers where the configuration has window layers
            # too: those have the pool above)
            kv = dict(
                num_layers=len(self.dcfg.full_layers),
                num_heads=self.dcfg.cache_kv_heads,
                head_dim=self.dcfg.cache_head_dim,
                block_size=block_size,
                dtype=cfg.dtype,
            )
            if self.dcfg.latent_layers:
                # one row a position, all heads': cache.py "Latent rows"
                kv.update(num_heads=1, head_dim=self.dcfg.latent_width, latent=True)
            if cache_budget_bytes is not None and self.slot_state is not None:
                # the slots' state comes out of the same budget, before any block
                held = self.slot_state.total_bytes
                if held >= cache_budget_bytes:
                    raise ValueError(
                        f"cache budget {cache_budget_bytes}B is under the {held}B that {max_batch_slots} slots' "
                        f"state-space state takes before any K/V block"
                    )
                cache_budget_bytes -= held
            if cache_budget_bytes is not None:
                # per-device HBM budget: the head-sharded cache holds
                # H/tp heads of every block per chip, so the same chip
                # budget buys tp x the blocks (ISSUE 15 satellite)
                cache_config = CacheConfig.from_budget(
                    cache_budget_bytes, kv_shards=self.tp_degree, **kv
                )
            else:
                # enough for every slot to reach max_seq_len (discounted
                # by expected prefix sharing), plus scratch
                cache_config = CacheConfig.for_slots(
                    max_seq_len=self.max_seq_len,
                    max_batch_slots=max_batch_slots,
                    expected_prefix_sharing=expected_prefix_sharing,
                    **kv,
                )
        if cache_config.kv_shards != self.tp_degree:
            # rows are packed shard by shard (CacheConfig.row_shape)
            cache_config = dataclasses.replace(cache_config, kv_shards=self.tp_degree)
        self.cache_config = cache_config
        # the second kind of state (cache.py): what the convolution
        # layers keep per sequence, per slot and per cached block
        self.state_config: Optional[StateConfig] = None
        if self.dcfg.conv_layers:
            self.state_config = StateConfig(
                num_layers=len(self.dcfg.conv_layers),
                rows=self.dcfg.conv_kernel - 1,
                width=cfg.hidden_size,
                slots=max_batch_slots,
                dtype=cfg.dtype,
            )
        self.cache = KVCache.create(
            cache_config,
            sharding=self.layout.cache_sharding if self.layout else None,
            state_config=self.state_config,
            window_config=self.window_config,
            slot_state=self.slot_state,
        )
        self._ssm_slots_live = 0  # the slots the last decode step ran live (`cache.ssm`)
        # a cross-decoder (decoder.py `cross_from`): its layers run on a prompt's last row alone; the rows of
        # the prefill programs (a bucket's) that they ran on and that they were spared (`prefill` of /v2/stats)
        self._cross_decoder = self.dcfg.cross_from < self.dcfg.num_layers
        # a configuration with a window pool AND per-slot state: its prefill returns the prompt's K/V rows and no
        # pool, and the hand-over (the one admission program that donates) writes them with the slot's parts, so
        # that no second copy of the pools stands beside the first while a prompt is prefilled
        self._rows_install = self.slot_state is not None and self.window_config is not None
        self.cross_rows_run = 0
        self.cross_rows_skipped = 0
        # the live sequences' tables of the window pool, by batch slot,
        # and what the release has given back (the `cache` section of
        # /v2/stats: cache_stats)
        self.window_allocator = BlockAllocator(self.window_config) if self.window_config else None
        self.window_tables: Dict[int, WindowTable] = {}
        self.window_released_total = 0
        # the ``ff.cache.window_release`` spans: how many, and their seconds
        self.window_releases_total = 0
        self.window_release_total_s = 0.0
        self._live_blocks = (0, 0)  # (full, window) blocks the last decode step's sequences held
        # a latent cache (the `cache.latent` section of /v2/stats): the
        # positions the last decode step's sequences held, and the
        # layers' attention calls by form
        self.latent_tokens_held = 0
        self.latent_calls: Dict[str, int] = {"absorbed": 0, "expanded": 0}
        self._n_latent = len(self.dcfg.latent_layers)  # 0: a step counts none of this
        self.window_held_peak = 0  # the most window blocks any one sequence held at a step
        # tokens every expert of every expert layer was handed, and the
        # calls that handed them, counted ON THE DEVICE: the step
        # programs take these arrays and return them grown, and nothing
        # reads them back but expert_stats() (a /v2/stats scrape). NOT
        # donated: a scrape reads the current arrays from another thread
        # while the loop thread dispatches the next step, and a donated
        # buffer would be gone under it; 2 KB a step is the price.
        # int32: a counter wraps after 2**31 tokens to one expert.
        self.expert_counts: Dict[str, jax.Array] = {}
        if self.dcfg.expert_layers:
            self.expert_counts = {
                # (a share of the experts: the held ones' columns and one more, the tokens none of them was
                # chosen for; identity experts: their picks and the tokens by real experts picked: decoder._count_row)
                "tokens": jnp.zeros((len(self.dcfg.expert_layers), self.dcfg.expert_count_columns), jnp.int32),
                "calls": jnp.zeros((2,), jnp.int32),  # decode, prefill
            }
        # step programs whose expert layers took the grouped form
        # (ops/expert_product.py): the form is static a program, so the
        # host counts it at dispatch, with no device counter to read back
        self.expert_grouped_calls = 0
        self._expert_forms: Dict[int, str] = {}
        # convolution-state traffic of the prefix cache (the conv_state
        # section of /v2/stats): hits that restored a slot's state from a
        # block's snapshot, snapshots written with registered blocks
        self.state_restores_total = 0
        self.state_snapshots_total = 0
        self.allocator = BlockAllocator(cache_config)
        self.max_blocks_per_seq = cache_config.blocks_for(self.max_seq_len)
        if diffusion is not None:
            # a cache block then ends where a diffusion block does, so a whole cache block's K/V depends on
            # nothing behind it and a prefix hit of whole cache blocks is always valid; one that is no
            # multiple would cut diffusion blocks in two, and is refused here
            off = [n for n in (cache_config.block_size, self.max_seq_len, *self.buckets) if n % diffusion.block_length]
            if off:
                raise ValueError(
                    f"block diffusion over blocks of {diffusion.block_length}: the cache's block size, max_seq_len "
                    f"and every prompt bucket have to be multiples of it ({off} are not)"
                )
            # cumulative, /v2/stats "diffusion" (diffusion_stats)
            self.diffusion_counts: Dict[str, int] = {
                "slot_forwards_total": 0, "commit_forwards_total": 0, "tokens_fixed_total": 0, "blocks_committed_total": 0,
            }
            # denoising forwards of a slot by the tokens they fixed (0 .. block_length)
            self.fixed_histogram = np.zeros((diffusion.block_length + 1,), np.int64)
        if self.buckets[-1] > self.max_seq_len:
            raise ValueError(
                f"bucket {self.buckets[-1]} exceeds max_seq_len {self.max_seq_len}"
            )
        if self.buckets[-1] < self.max_seq_len:
            # preemption-by-recompute re-prefills prompt + generated,
            # which can reach max_seq_len - 1: there must be a bucket
            # that holds it
            self.buckets = self.buckets + (self.max_seq_len,)
        if max_spec_tokens < 1:
            raise ValueError("max_spec_tokens must be >= 1")
        # speculative verification window: 1 committed token + up to
        # max_spec_tokens drafts, ONE fixed jit shape whatever per-
        # request adaptive k does
        self.max_spec_tokens = max_spec_tokens
        self.spec_window = max_spec_tokens + 1
        self.backend = jax.default_backend()
        # the mesh handed to the Pallas kernel dispatch (ISSUE 15): on
        # TPU backends a tp>1 engine routes decode/append attention
        # through the head-sharded shard_map kernel path; elsewhere the
        # plain-XLA reference composition is partitioned by GSPMD and
        # needs no manual mesh
        self._kernel_mesh = (
            self.layout.mesh
            if self.layout is not None and self.tp_degree > 1 and on_tpu()
            else None
        )
        # which body each attention kind's decode call lowers to, and at
        # what group: static per program (the `kernels` section of
        # /v2/stats, the server's start-up line)
        self.attention_kernels: Dict[str, Dict] = self.paged_lowerings()
        # a prefill's attention calls (the `prefill_attention` section of
        # /v2/stats): one a layer with K/V of heads a prefill, by the form
        # the bucket's shapes take (ops/attention.py prefill_call_lowering);
        # `refused`: by reason, the streamed calls whose kernel's gate sent
        # them to the XLA composition
        self.prefill_attention_calls: Dict[str, int] = {"tokens": 0, "calls": 0, "rows": 0, "streamed": 0, "materialised": 0}
        self.prefill_attention_refused: Dict[str, int] = {}
        self._prefill_lowerings: Dict[int, Dict] = {}
        # retrace counters: the Python body runs only when XLA traces, so
        # these count compiles, not calls (read by benchmark/drivers/serve*.py
        # for ``correct``, by chip_smoke.py and by the retrace tests)
        self.trace_counts: Dict[str, int] = {}
        # host-call counters: engine steps actually issued (the divisor of
        # benchmark/layer_metrics/decode_step_ms.py and batch_occupancy.py)
        self.step_counts: Dict[str, int] = {"prefill": 0, "decode": 0, "verify": 0}
        if diffusion is not None:
            self.step_counts["block_step"] = 0
        # per-kind step-phase seconds (the device_time_s split, ISSUE
        # 12): dispatch = host arg prep + XLA dispatch (jit call entry
        # to return), execute = dispatch-return to block_until_ready
        # completion (actual device compute under async dispatch),
        # readback = device->host result sync + numpy conversion. The
        # old device_time_s total survives as a derived property so the
        # flight/stats consumers keep their series; MFU divides by
        # execute-only seconds (obs/capacity.py convention change,
        # documented in README "Step anatomy").
        self.phase_time_s: Dict[str, Dict[str, float]] = {
            k: {"dispatch": 0.0, "execute": 0.0, "readback": 0.0}
            for k in ("prefill", "decode", "verify") + (("block_step",) if diffusion is not None else ())
        }
        # spans of the most recent engine step (obs/steptrace.py):
        # (phase, t0, t1) perf_counter stamps, overwritten per call —
        # read by the scheduler loop thread that made the call, never
        # concurrently
        self.last_step_spans: List[Tuple[str, float, float]] = []
        # the parts of that step's dispatch span, ``args`` (host
        # arithmetic), ``upload`` (host-to-device transfers) and ``call``
        # (the jit call): children, inside ``dispatch`` and so in no sum
        # of the host lane. ``_children`` collects the dispatch in hand.
        self.last_step_children: List[Tuple[str, float, float]] = []
        self._children: List[Tuple[str, float, float]] = []
        # host-to-device transfers through ``_dev`` (every one the
        # engine makes, whatever the step) and what ``_stage`` answered
        # (cumulative, /v2/stats "uploads")
        self.uploads = {"uploads_total": 0, "upload_bytes_total": 0, "staged_hits_total": 0, "staged_misses_total": 0,
                        "carried_hits_total": 0, "carried_misses_total": 0}
        # a scheduler that keeps a step anatomy switches this on for the
        # iterations it samples (one in ``CPU_CLOCK_EVERY``): the decode
        # dispatch span then reads the thread's CPU clock at its two
        # ends, and ``decode_dispatch_clock`` holds the span's cumulative
        # (wall, CPU) seconds of THOSE calls, as one tuple so that a
        # scrape reads a pair of the same steps
        self.cpu_stamps = False
        self.decode_dispatch_clock: Tuple[float, float] = (0.0, 0.0)
        # called with no argument after every dispatch, before the wait
        # for its result (_dispatched); the scheduler that serves from
        # this engine sets it
        self.on_dispatched: Optional[Callable[[], None]] = None
        # serving FLOPs accounting (obs/capacity.py): model-shaped FLOPs
        # per step kind — true prompt lengths and live context only, so
        # MFU = flops / execute seconds / chip peak is padding-honest.
        # Recovery replay / bisection probes accrue in BOTH terms (they
        # are real device work); goodput_ratio is the client-useful view.
        # The chip comes from the detected device kind (the calibration
        # preset table), so MFU and the truth ledger's roofline
        # predictions use real peaks instead of the generic default.
        from ..search.calibration import (
            chip_spec_for,
            detected_device_kind,
            mesh_device_kind,
        )

        # mesh geometry in the chip kind ("TPU v5e x4"): the aggregate
        # spec scales peaks by the shard count, so a multi-chip engine's
        # MFU divides by the MESH's peak FLOPs — against one chip's peak
        # a 4-way engine would report >100% MFU (ISSUE 15 satellite)
        kind = mesh_device_kind(detected_device_kind(), self.tp_degree)
        self.flops_model = ServingFlops.from_config(
            cfg, dtype=cache_config.dtype, chip=chip_spec_for(kind)
        )
        # drift alarms only where the roofline means something: on the
        # CPU backend the prediction models a chip that is not there
        # (dispatch overhead dominates, peaks are uncalibrated), so the
        # pairs still record — an operator can read the error — but a
        # permanently-wrong prediction must not spam the flight ring
        self._roofline_alarm = jax.default_backend() != "cpu"
        self.flops_by_kind: Dict[str, float] = {"prefill": 0.0, "decode": 0.0, "verify": 0.0}
        if diffusion is not None:
            self.flops_by_kind["block_step"] = 0.0
        # jit program registry: every traced program's static signature,
        # trace count, and compile wall time; retraces carry blame
        # strings (GET /v2/debug/programs)
        self.programs = ProgramRegistry()
        # cost-model truth ledger (obs/truth.py): every steady-state
        # step pairs its roofline-predicted time (same derate constants
        # as the search cost model) with measured wall seconds; EWMA
        # drift alarms land on the flight ring and
        # GET /v2/debug/predictions serves the pairs. Compile calls are
        # excluded — their wall time is compile cost, stamped into the
        # program registry instead.
        self.ledger = PredictionLedger()
        # per-slot finiteness of the last step's logits (the supervisor's
        # NaN blame vector: a cheap in-jit isfinite reduce, so a poisoned
        # request is pinned to its slot without extra device calls);
        # scalar-shaped [1] after prefill_one
        self.last_finite = np.ones((max_batch_slots,), bool)
        # crash-recovery restarts (generation/recovery.py supervisor)
        self.resets = 0
        # the fault plan's NaN-poison carrier: outside chaos runs inject
        # returns this very object, so the steady-state decode path pays
        # one identity check instead of a fresh alloc + device transfer
        self._zero_bias = np.zeros((max_batch_slots,), np.float32)
        self._zero_bias_dev = self._dev(np.zeros((max_batch_slots,), np.float32))
        # grammar-mask staging (ISSUE 18): per-shape cached zeros for
        # batches with no constrained slot — see _mask_arg
        self._zero_masks: Dict[str, jax.Array] = {}
        # KV-cache buffer donation on the hot fixed-shape programs: the
        # decode/verify jits alias their cache inputs to their cache
        # outputs, so XLA updates the (large) cache in place instead of
        # copying it every step. Aliasing alone moves nothing out of the
        # step: it is the in-place row scatter and the whole-cache kernel
        # signature (decoder.py::write_rows, paged_append_attention(q,
        # k_cache, v_cache, layer, ...)) over arrays stored in the shape
        # whose default layout the kernel reads (cache.py) that leave the
        # step no layer- or cache-sized value to copy (ISSUE 24: the
        # compiled 24-layer program's temporaries fell from 4.67 GiB to
        # 0.04). Auto: on for accelerator backends, off
        # on CPU — donation consumes the input buffers, which makes a
        # FAILED step unrecoverable by retry/bisection (the supervisor
        # then goes straight to reset + journal replay, which is
        # byte-exact); the CPU chaos suites exercise the retry/bisect
        # paths and keep them.
        self.donate = bool(
            donate_cache if donate_cache is not None
            else jax.default_backend() != "cpu"
        )
        # device-resident staging for decode/verify args: re-uploaded
        # only when the host-side contents change, not rebuilt via
        # jnp.asarray every step. Keyed by arg name; each entry is (host
        # snapshot, device array). The slot-constant ones (block tables,
        # sampling params, the active mask) are uploads; a decode step's
        # positions and counts are CARRIED: the entry is what the step
        # program itself returned (its inputs advanced by one in every
        # active slot) beside the host's same sum, so a step that
        # follows in the same composition uploads nothing (CARRIED
        # below; dropped wherever the program that made them may have
        # failed). Loop-thread only (like the cache refs).
        self._staged: Dict[str, Tuple[np.ndarray, jax.Array]] = {}
        # decode steps by the branch of `_sample` they ran (cumulative,
        # /v2/stats "sampling")
        self.sampling_steps: Dict[str, int] = {"greedy": 0, "plain": 0, "sort": 0}
        # sharded jits with EXPLICIT out-shardings (ISSUE 15): cache
        # outputs stay head-sharded across steps (no resharding between
        # chained fixed-shape programs), tokens/ok/emit counts come back
        # replicated so the host bookkeeping reads one copy. On the
        # legacy (no-mesh) path the jits are built exactly as before.
        # (every step program also takes and returns two dicts, `state`
        # and `counts`: the convolution state it carries and the expert
        # counters. Both are EMPTY for a configuration without such
        # layers, and an empty pytree adds no parameter and no result to
        # a program: GPT-2's are what they were. `state` is donated with
        # the cache, `counts` never: see expert_counts above.)
        dec_donate = (3, 4, 13) if self.donate else ()  # cache_k, cache_v, state
        ver_donate = (4, 5) if self.donate else ()
        if self.layout is None:
            sharded = {}
            dec_sh = ver_sh = {}
        else:
            repl = self.layout.replicated
            csh = self.layout.cache_sharding
            sharded = {"out_shardings": (repl, repl, csh, csh, repl, repl)}
            # a decode step also returns the positions and counts it advanced
            dec_sh = {"out_shardings": sharded["out_shardings"] + (repl, repl)}
            ver_sh = {"out_shardings": (repl, repl, repl, csh, csh)}
        self._prefill_jit = jax.jit(self._prefill_impl, **sharded)
        self._decode_jit = jax.jit(
            self._decode_impl, donate_argnums=dec_donate, **dec_sh
        )
        self._verify_jit = jax.jit(
            self._verify_impl, donate_argnums=ver_donate, **ver_sh
        )
        # a block-diffusion engine's step (tp_degree > 1 is refused for it: no shardings)
        self._block_jit = jax.jit(self._block_impl, donate_argnums=(5, 6) if self.donate else ())
        # cross-request prefix caching (generation/prefix.py): radix
        # index + refcounted COW blocks + host-RAM offload tier. The
        # block-level device programs below are admission-time only
        # (suffix prefill per bucket, one copy/read/write each) — the
        # steady-state decode/verify programs are untouched.
        # A hit's suffix prefill scores its rows against the whole table
        # in ONE float32 matrix [heads, bucket, max_seq_len] (it has no
        # streamed form). Where even the smallest bucket's would pass the
        # bound past which a whole prompt's prefill streams, no hit could
        # be taken without materialising what the streamed prefill exists
        # to avoid: decided once, here, the index is then not kept at all
        # (nothing inserted, evicted or read out to the host tier) and
        # the refusal is named; every other configuration's hits are
        # served as they always were
        suffix_scores = 4 * self.dcfg.attend_heads * self.buckets[0] * self.max_seq_len
        if self.slot_state is not None:
            prefix_cache = False  # (named in `unsupported` above: unsupported_paths("ssm"))
        elif prefix_cache and suffix_scores > STREAM_SCORE_BYTES:
            prefix_cache = False
            self.unsupported["prefix_reuse"] = (
                f"prefix reuse is off: a hit's suffix prefill[{self.buckets[0]}] (the smallest bucket) would hold "
                f"{suffix_scores / 2**30:.1f} GiB of float32 scores [{self.dcfg.num_heads}, {self.buckets[0]}, "
                f"{self.max_seq_len}], past the {STREAM_SCORE_BYTES >> 20} MiB from which a prefill streams, and the "
                f"suffix prefill has no streamed form; every prompt is prefilled whole"
            )
        self.prefix_cache = PrefixCache(
            self.allocator, cache_config,
            enabled=prefix_cache, host_budget_bytes=host_cache_bytes,
            state_bytes_per_block=(
                self.state_config.bytes_per_sequence if self.state_config
                else self.window_config.bytes_per_block if self.window_config else 0
            ),
        )
        self.prefix_cache.window_allocator = self.window_allocator
        if self.layout is None:
            blk_sh = rd_sh = {}
        else:
            # block-level programs over the sharded cache: COW copies and
            # swap-in writes keep the cache sharding; a swap-out read
            # gathers the full block to the host tier (replicated out)
            blk_sh = {"out_shardings": (csh, csh)}
            rd_sh = {"out_shardings": (repl, repl)}
        self._prefix_prefill_jit = jax.jit(self._prefix_prefill_impl, **sharded)
        # (the admission-time programs donate nothing, state included: a
        # failed prefill leaves every array as it was, for the retry)
        self._restore_state_jit = jax.jit(self._restore_state_impl)
        # a prefill's hand-over of a slot's named parts (cache.py SlotStateConfig): the one admission-time
        # program that donates, and only the slots' state: it runs after the prefill has succeeded, so a
        # failed prefill still leaves every array as it was
        self._install_state_jit = jax.jit(self._install_state_impl, donate_argnums=(0,) if self.donate else ())
        self._install_rows_jit = jax.jit(self._install_rows_impl, donate_argnums=(0, 1, 2) if self.donate else ())
        self._copy_block_jit = jax.jit(self._copy_block_impl, **blk_sh)
        self._read_block_jit = jax.jit(self._read_block_impl, **rd_sh)
        self._write_block_jit = jax.jit(self._write_block_impl, **blk_sh)
        self._read_window_jit = jax.jit(self._read_window_impl)
        self._write_window_jit = jax.jit(self._write_window_impl)
        # batched handoff-wire programs (one dispatch per payload, not
        # per block): padded to max_blocks_per_seq so ONE fixed-shape
        # program serves every prompt length
        self._read_blocks_jit = jax.jit(self._read_blocks_impl, **rd_sh)
        self._write_blocks_jit = jax.jit(self._write_blocks_impl, **blk_sh)
        self._register_strategy_predictions()

    def _dev(self, x) -> jax.Array:
        """Commit a host array onto the engine's devices. Mesh-native
        engines pin every non-sharded jit input replicated on the mesh
        (call-stable input shardings — a drifting placement would
        recompile the fixed-shape programs); the legacy engine keeps the
        plain uncommitted ``jnp.asarray``."""
        self.uploads["uploads_total"] += 1
        self.uploads["upload_bytes_total"] += getattr(x, "nbytes", 0)
        if self.layout is not None:
            return self.layout.put_replicated(x)
        return jnp.asarray(x)

    def _part(self, kind: str, part: str) -> phase:
        """Open one child of the ``ff.engine.<kind>.dispatch`` span in
        hand: ``args`` (host arithmetic: masks, casts, ``_lookup``'s
        compares), ``upload`` (every host-to-device transfer) or
        ``call`` (the jit call alone). The parent's self time is its
        seconds less these."""
        return phase(f"engine.{kind}.dispatch.{part}", into=self._children)

    def _dispatched(self) -> None:
        """A step program is with the device and the NEXT thing this
        thread does is park for it: tell whoever asked (the scheduler,
        which wakes the streams' threads here and not while a dispatch
        is still its to make). The blocking calls only: after
        ``decode_async`` the caller says itself when it parks
        (``consume_decode``), and the arguments' device arrays die in
        between, each destructor giving up the interpreter's lock to
        whoever is awake."""
        if self.on_dispatched is not None:
            self.on_dispatched()

    def _count_dispatch(self, disp: phase) -> None:
        """Add a decode dispatch span to ``decode_dispatch_clock``."""
        if disp.c0 is not None:
            wall, cpu = self.decode_dispatch_clock
            self.decode_dispatch_clock = (wall + disp.seconds, cpu + disp.cpu_seconds)

    def upload_stats(self) -> Dict[str, int]:
        """The ``uploads`` section of ``/v2/stats``."""
        return dict(self.uploads)

    def register_stats(self, stats) -> None:
        """Surface the engine on a ServingStats: its compute gauges
        (useful FLOPs over execute seconds, retrace blame) and the
        sections of ``/v2/stats`` it owns. What the layers that are not
        attention-and-MLP count is absent for a configuration without
        them. (``uploads`` is the tracing layer's to add: absent with
        the scheduler's observability off.)"""
        stats.add_gauge("mfu", self.mfu)
        stats.add_gauge("model_tflops_total", lambda: self.total_flops() / 1e12)
        stats.add_gauge("achieved_tflops", lambda: self.total_flops() / max(1e-9, self.total_device_time_s()) / 1e12)
        stats.add_gauge("retraces_blamed", self.programs.total_retraces)
        stats.add_section("sampling", self.sampling_stats)
        stats.add_section("kernels", self.kernel_stats)
        stats.add_section("prefill_attention", self.prefill_attention_stats)
        if self.expert_counts:
            stats.add_section("experts", self.expert_stats)
        if self.state_config is not None:
            stats.add_section("conv_state", self.conv_state_stats)
        if self.window_config is not None or self.cache_config.latent or self.slot_state is not None:
            stats.add_section("cache", self.cache_stats)
        if self.diffusion is not None:
            stats.add_section("diffusion", self.diffusion_stats)
        if self._cross_decoder:
            stats.add_section("prefill", self.prefill_stats)

    def _register_strategy_predictions(self) -> None:
        """Put the chosen serving layout's predicted step times into the
        truth ledger (keys ``serving_strategy:prefill`` / ``:decode``)
        so drift telemetry covers the layout DECISION, not just the
        per-step roofline: the engine's measured execute seconds pair
        against the search's estimate on GET /v2/debug/predictions.
        ``alarm=False`` — the strategy simulator is an analytic ranking
        device (fwd cost of a training-shaped graph), expected to miss
        absolute wall seconds; the pairs are for operators, the CHOICE
        is what they grade."""
        ch = self.serving_strategy
        if ch is None:
            return
        prov = (
            f"predict_strategy_time over TP candidates "
            f"{[c['tp_degree'] for c in ch.candidates]} on "
            f"{ch.device_kind}"
        )
        self.ledger.predict(
            "serving_strategy:prefill", ch.prefill_s,
            label=f"serving layout tp={ch.tp_degree} (prefill)",
            provenance=prov, alarm=False,
        )
        self.ledger.predict(
            "serving_strategy:decode", ch.decode_s,
            label=f"serving layout tp={ch.tp_degree} (decode)",
            provenance=prov, alarm=False,
        )

    def serving_strategy_block(self) -> Dict:
        """The ``serving_strategy`` metadata block (engine metadata +
        GET /v2/models/{name} + obsreport summary): mesh geometry, the
        chosen layout, and every scored TP candidate."""
        block: Dict = {
            "tp_degree": self.tp_degree,
            "mesh_devices": self.mesh_devices,
        }
        if self.layout is not None:
            block["layout"] = self.layout.describe()
        if self.serving_strategy is not None:
            block["search"] = self.serving_strategy.describe()
        return block

    # ------------------------------------------------------------ geometry
    def reset(self) -> None:
        """Tear down device-side generation state after a crash or a
        stalled step: rezero the KV cache and restore the allocator's
        free list. The compiled program family and trace counters
        survive (params are unchanged), so recovery costs no
        recompilation — the scheduler journal-replays every live stream
        into the fresh cache."""
        self.cache.reset()
        self.allocator.reset()
        self._drop_carried()
        if self.window_allocator is not None:
            self.window_allocator.reset()
            self.window_tables.clear()
        if self.expert_counts:
            # cumulative across a reset, unless the failed program's
            # results (a donating engine has no older ones) are all it has
            try:
                jax.block_until_ready(self.expert_counts)
            except Exception:
                self.expert_counts = jax.tree.map(jnp.zeros_like, self.expert_counts)
        # the prefix index is provenance-bound to the dead cache: drop
        # every entry (resident ids AND host copies) wholesale — replay
        # re-matches against the empty index, which is recompute,
        # which is byte-exact
        self.prefix_cache.reset()
        self.last_finite = np.ones((self.max_batch_slots,), bool)
        self.resets += 1

    def bucket_for(self, prompt_len: int) -> int:
        for b in self.buckets:
            if prompt_len <= b:
                return b
        raise ValueError(
            f"prompt length {prompt_len} exceeds the largest bucket {self.buckets[-1]}"
        )

    # ------------------------------------------------------- jitted bodies
    def _write_state(self, state, zs, slot, n_tokens, block_table, first_block):
        """What a prefill leaves of its convolution layers' padded ``z``
        rows (``zs`` [n_conv, 1, T + K - 1, E], decoder.py): the slot's
        state after the window's ``n_tokens`` real tokens into ``conv``,
        and into ``snap`` the state at the end of every block the window
        filled (the window starts at the table's block ``first_block``;
        a block it did not fill to its end writes to scratch block 0).
        A block joins the prefix index only with that snapshot."""
        bs = self.cache_config.block_size
        rows = self.state_config.rows
        n_blocks = (zs.shape[2] - rows) // bs
        with jax.named_scope("conv_state"):
            now = jax.vmap(lambda z: state_at(z, n_tokens[None], rows + 1)[0])(zs)  # [n_conv, K-1, E]
            conv = jax.lax.dynamic_update_slice_in_dim(
                state["conv"], now[:, None].astype(state["conv"].dtype), slot, axis=1
            )
            snap = state["snap"]
            if n_blocks:
                ends = (jnp.arange(n_blocks, dtype=jnp.int32) + 1) * bs  # tokens before a block's end
                at_end = zs[:, 0][:, ends[:, None] + jnp.arange(rows)[None, :]]  # [n_conv, n_blocks, K-1, E]
                idx = jnp.clip(first_block + jnp.arange(n_blocks), 0, block_table.shape[0] - 1)
                dst = jnp.where(ends <= n_tokens, block_table[idx], 0)
                snap = snap.at[:, dst].set(at_end.astype(snap.dtype))
        return {"conv": conv, "snap": snap}

    def _slot_parts(self, left, n_tokens):
        """What a prefill hands a slot of its state-space layers'
        results (``left``: decoder.py ``prefill``'s fourth result, one
        sequence): the convolution's input rows behind the sequence's
        ``n_tokens`` (:func:`state_at`) and the recurrent state after
        them (rows past the length have ``dt = 0``), in the stored
        layout: ``{name: [n_ssm, *shape]}``, for :meth:`_install_state`."""
        k = self.dcfg.ssm_conv_kernel
        with jax.named_scope("ssm.handover"):
            rows = jax.vmap(lambda z: state_at(z, n_tokens[None], k)[0])(left["xbc"])  # [n_ssm, K-1, width]
            if self.dcfg.mamba_layers:  # [n, D, N] -> the stored [n, N, D]
                return {"ssm_conv": rows, "ssm": jnp.swapaxes(left["state"][:, 0], -1, -2)}
            return {"ssm_conv": rows, "ssm": ssm_ops.pack_state(left["state"][:, 0], self.dcfg.ssm_groups)}

    def _install_state_impl(self, state, slot, parts):
        """A slot's parts into the slots' arrays (donated: in place)."""
        self.trace_counts["state_install"] = self.trace_counts.get("state_install", 0) + 1
        return self._parts_installed(state, slot, parts)

    @staticmethod
    def _parts_installed(state, slot, parts):
        return {
            name: jax.lax.dynamic_update_slice_in_dim(state[name], parts[name][:, None].astype(state[name].dtype), slot, axis=1)
            for name in state
        }

    def _write_prefill_rows(self, cache_k, cache_v, state, ks, vs, length, block_table, wtable):
        """A prompt's K/V rows (``ks`` / ``vs`` [n storing layers, S, R,
        LW], decoder.py ``prefill``) into the blocks of its tables: the
        full layers' into ``cache_k`` / ``cache_v``, the window layers'
        into the window pool's arrays (``state["wk" / "wv"]``), of which
        nothing behind what the table still holds is kept. Returns the
        two arrays and ``{"wk", "wv"}`` (empty without window layers)."""
        s = ks.shape[1]
        positions = jnp.arange(s, dtype=jnp.int32)
        block, offset = slot_mapping(block_table, positions, cache_k.shape[2])
        block = jnp.where(positions < length, block, 0)  # padding -> scratch
        offset = jnp.where(positions < length, offset, 0)
        if self.window_config is not None:
            # the window layers' rows go to the window pool's blocks: of
            # the positions behind what the table still holds nothing is
            # kept (scratch), the next query cannot reach them
            held = jnp.logical_and(positions < length, positions >= wtable["first"])
            wblock, woffset = slot_mapping(wtable["tables"], positions - wtable["first"], cache_k.shape[2])
            wblock, woffset = jnp.where(held, wblock, 0), jnp.where(held, woffset, 0)
            wk, wv = state["wk"], state["wv"]
        with jax.named_scope("cache_write"):
            # layer by layer, as a decode step writes: one scatter over
            # all layers would have the compiler transpose the whole
            # cache to bring the scattered axes to the front, and back
            for li in range(ks.shape[0]):
                kind, at = self.dcfg.stored_index[li]
                if kind == "window":
                    wk = write_rows(wk, at, wblock, woffset, ks[li])
                    wv = write_rows(wv, at, wblock, woffset, vs[li])
                    continue
                cache_k = write_rows(cache_k, at, block, offset, ks[li])
                cache_v = write_rows(cache_v, at, block, offset, vs[li])
        return cache_k, cache_v, ({"wk": wk, "wv": wv} if self.window_config is not None else {})

    def _install_rows_impl(self, cache_k, cache_v, state, slot, handed, length, block_table, wtable):
        """The hand-over of a configuration whose prefill returns its rows
        and no pool (``_rows_install``): the prompt's K/V rows into both
        pools and the slot's parts into the slots' arrays, everything
        donated: in place."""
        name = f"state_install[{handed['rows_k'].shape[1]}]"  # (a program a bucket, as the prefills are)
        self.trace_counts[name] = self.trace_counts.get(name, 0) + 1
        cache_k, cache_v, pools = self._write_prefill_rows(
            cache_k, cache_v, state, handed["rows_k"], handed["rows_v"], length, block_table, wtable
        )
        parts = {name: state[name] for name in self.slot_state.names}
        return cache_k, cache_v, dict(pools, **self._parts_installed(parts, slot, handed))

    def _install_state(self, slot: int, parts: Dict[str, jax.Array]) -> Dict[str, jax.Array]:
        """The hand-over behind a successful prefill: ``slot`` takes the
        sequence's state, whole (whatever the slot's last tenant left is
        overwritten). Returns the slots' arrays for ``cache.update``."""
        with phase("cache.state_install", slot=slot):
            return self._install_state_jit(
                {name: self.cache.state[name] for name in self.slot_state.names}, jnp.int32(slot), parts
            )

    def _count(self, counts, rows, kind: int):
        """The step's per-expert token counts (``rows``: one [N] row per
        expert layer, decoder.py) added into the carried counters."""
        if not counts:
            return counts
        with jax.named_scope("router"):
            return {
                "tokens": counts["tokens"] + jnp.stack(rows),
                "calls": counts["calls"].at[kind].add(1),
            }

    def _prefill_impl(self, params, tokens, length, cache_k, cache_v, block_table, temp, top_k, key, mask,
                      state=None, slot=None, counts=None, wtable=None):
        s = tokens.shape[1]
        self.trace_counts[f"prefill[{s}]"] = self.trace_counts.get(f"prefill[{s}]", 0) + 1
        self.programs.note_trace(f"prefill[{s}]", {
            "params": params, "tokens": tokens, "length": length,
            "cache_k": cache_k, "block_table": block_table,
            "temp": temp, "top_k": top_k, "key": key, "mask": mask,
        })
        state, counts, rows = state or {}, counts or {}, []
        last_only = self.dcfg.cross_from < self.dcfg.num_layers  # the cross-decoder runs on the last row alone
        logits, ks, vs, *zs = prefill(
            params, tokens, jnp.full((1,), length, jnp.int32), cfg=self.dcfg, counts=rows, backend=self.backend,
            head=self.diffusion is None, last_only=last_only,
        )
        if self.state_config is not None:
            state = self._write_state(state, zs[0], slot, length, block_table, 0)
        if self.slot_state is not None:
            state = self._slot_parts(zs[0], length)
        counts = self._count(counts, rows, 1)
        if self._rows_install:
            # the rows go out as they are and the hand-over writes them (`_install_rows`): this program returns
            # no pool, so none is copied beside the one it was given
            state = dict(state, rows_k=ks[:, 0], rows_v=vs[:, 0])
            cache_k = cache_v = None
        else:
            cache_k, cache_v, pools = self._write_prefill_rows(cache_k, cache_v, state, ks[:, 0], vs[:, 0], length, block_table, wtable)
            state = dict(state, **pools)
        if self.diffusion is not None:
            return self._no_token(logits[0, length - 1]) + (cache_k, cache_v, state, counts)
        with jax.named_scope("sample"):
            last = logits[0, 0 if last_only else length - 1]
            ok = jnp.all(jnp.isfinite(last))  # blame: poisoned prompt
            # grammar mask: additive [V] bias, 0 / NEG (finite — the ok
            # gate above still sees model NaN, never the mask)
            last = last + mask
            token = _sample(last[None], temp[None], top_k[None], key[None])[0]
        return token, ok, cache_k, cache_v, state, counts

    @staticmethod
    def _no_token(last):
        """What a block-diffusion engine's prefill returns where another
        samples a token: the prompt's whole blocks are cached and no
        logits are computed at all (the prompt's last ``P mod B`` tokens
        enter the first block as rows already fixed), so the blame is
        the finiteness of the last real row of the last layer's output."""
        return jnp.int32(0), jnp.all(jnp.isfinite(last.astype(jnp.float32)))

    def _decode_impl(
        self, params, tokens, positions, cache_k, cache_v, block_tables, active, temps, top_ks, bias, seeds, counts, mask,
        state=None, expert_counts=None, wtables=None,
    ):
        """One decode step. ``positions`` and ``counts`` are the slots'
        raw vectors and ``active`` their 0 / 1 mask (int32): the
        position an inactive slot writes at (0, in scratch) and every
        slot's context length are derived here, and the last two results
        are ``positions`` and ``counts`` advanced by one in every active
        slot: the next step's arguments, if its composition is this
        one's (:meth:`_decode_args`)."""
        self.trace_counts["decode"] = self.trace_counts.get("decode", 0) + 1
        self.programs.note_trace("decode", {
            "params": params, "tokens": tokens, "positions": positions,
            "cache_k": cache_k, "block_tables": block_tables,
            "active": active, "temps": temps, "top_ks": top_ks,
            "bias": bias, "seeds": seeds, "counts": counts, "mask": mask,
        })
        # (primitives, not jnp's jitted wrappers: the mask is 0 / 1, so a
        # product selects, and the program's trace gains no nested call)
        next_positions, next_counts = jax.lax.add(positions, active), jax.lax.add(counts, active)
        context_lens = jax.lax.mul(next_positions, active)
        positions = jax.lax.mul(positions, active)
        state, expert_counts, rows = state or {}, expert_counts or {}, []
        window = None
        if self.window_config is not None:
            window = {"k": state["wk"], "v": state["wv"], **wtables}
        logits, cache_k, cache_v, *conv = decode_step(
            params, tokens, positions, cache_k, cache_v, block_tables,
            context_lens, backend=self.backend, mesh=self._kernel_mesh,
            cfg=self.dcfg, conv=state.get("conv"), counts=rows, window=window,
            ssm={name: state[name] for name in self.slot_state.names} if self.slot_state is not None else None,
        )
        if self.slot_state is not None:
            state = conv[0]
        if self.state_config is not None:
            # a decode step carries the slots' state alone: the blocks'
            # snapshots are the prefill programs' to write
            state = {"conv": conv[0]}
        if self.window_config is not None:
            # (beside the slots' named parts, where the configuration has both)
            state = dict(state if self.slot_state is not None else {}, wk=conv[-1]["k"], wv=conv[-1]["v"])
        expert_counts = self._count(expert_counts, rows, 0)
        # bias is the fault plan's per-slot NaN poison (zeros outside
        # chaos runs); applying it before the finiteness reduce makes the
        # injected poison indistinguishable from model-produced NaN/inf.
        # mask is the grammar constraint: [B, V] additive rows of 0 / NEG
        # (finite, so it commutes with the poison semantics — the ok gate
        # trips on model/injected NaN, never on a banned token)
        with jax.named_scope("sample"):
            logits = logits + bias[:, None] + mask
            ok = jnp.all(jnp.isfinite(logits), axis=-1)
            # sampling keys derive in-jit from (seed, token count): no
            # host fold_in/stack on the critical path, same key bits as
            # before
            keys = derive_keys(seeds, counts)
            token = _sample(logits, temps, top_ks, keys)
        return token, ok, cache_k, cache_v, state, expert_counts, next_positions, next_counts

    def _verify_impl(
        self, params, tokens, start, n_draft, cache_k, cache_v, block_tables, temps, top_ks, bias, seeds, counts, mask
    ):
        """Speculative verification: score a [B, W] window (committed
        token + drafts) in one forward and accept/emit in-jit.
        ``n_draft[b]`` counts the slot's real drafts (0..W-1); -1 marks
        an inactive slot (everything masked to scratch, 0 emitted)."""
        from .speculative.sampling import speculative_accept

        self.trace_counts["verify"] = self.trace_counts.get("verify", 0) + 1
        self.programs.note_trace("verify", {
            "params": params, "tokens": tokens, "start": start,
            "n_draft": n_draft, "cache_k": cache_k,
            "block_tables": block_tables, "temps": temps, "top_ks": top_ks,
            "bias": bias, "seeds": seeds, "counts": counts, "mask": mask,
        })
        w = tokens.shape[1]
        keys = derive_window_keys(seeds, counts, w)  # in-jit, see decode
        offs = jnp.arange(w, dtype=jnp.int32)[None, :]
        # window token j sits at cache position start + j; slots past the
        # drafts (and whole inactive rows) are padding -> position -1
        positions = jnp.where(offs <= n_draft[:, None], start[:, None] + offs, -1)
        logits, cache_k, cache_v = verify_step(
            params, tokens, positions, cache_k, cache_v, block_tables,
            backend=self.backend, mesh=self._kernel_mesh, cfg=self.dcfg,
        )
        # per-position grammar mask [B, W, V] rides next to the NaN-poison
        # bias; draft and target score the SAME masked logits, so
        # rejection sampling stays distribution-preserving over the
        # constrained support and greedy stays token-for-token exact
        logits = logits + bias[:, None, None] + mask
        # blame vector: finiteness over each slot's REAL window positions
        # only — padded positions (and whole inactive rows) attend to
        # nothing and may hold garbage that must not indict the request
        valid = offs <= jnp.maximum(n_draft, 0)[:, None]
        ok = jnp.all(
            jnp.where(valid[:, :, None], jnp.isfinite(logits), True), axis=(1, 2)
        )
        out, n_emitted = speculative_accept(
            logits, tokens[:, 1:], jnp.maximum(n_draft, 0), temps, top_ks, keys
        )
        return out, jnp.where(n_draft >= 0, n_emitted, 0), ok, cache_k, cache_v

    def _block_impl(
        self, params, tokens, fixed, base, forwards, cache_k, cache_v, block_tables, active, temps, top_ks, seeds,
        n_fix, threshold, bias, expert_counts=None,
    ):
        """One block-diffusion step: a forward over every slot's block
        and, after the head, the generation rule (:class:`BlockDiffusion`).

        ``tokens`` [slots, B], ``fixed`` [slots, B] (0 / 1), ``base``
        [slots] the block's first position and ``forwards`` [slots] the
        denoising forwards the block has had are the slots' state, kept
        on the device from forward to forward: the last four results
        are that state after this forward, the next step's arguments if
        its composition is this one's (as a decode step's positions and
        counts are). ``n_fix`` [slots]: the rows the static rule fixes a
        forward; ``threshold`` [slots]: the confidence past which the
        dynamic rule fixes a row (2: the static rule). A slot none of
        whose rows was masked on entry ran its COMMIT forward: its base
        advances by B and its block resets to the next one's start (all
        masked). Returns the block's tokens after this forward [slots,
        B], the rows it fixed [slots, B] bool, the commits [slots] bool,
        the finiteness blame [slots], the cache, the expert counters and
        the next state."""
        self.trace_counts["block_step"] = self.trace_counts.get("block_step", 0) + 1
        self.programs.note_trace("block_step", {
            "params": params, "tokens": tokens, "fixed": fixed, "base": base, "forwards": forwards, "cache_k": cache_k,
            "block_tables": block_tables, "active": active, "temps": temps, "top_ks": top_ks, "seeds": seeds,
            "n_fix": n_fix, "threshold": threshold, "bias": bias,
        })
        d = self.diffusion
        slots, b = tokens.shape
        live, was_fixed = active > 0, fixed > 0
        commit = jnp.logical_and(live, jnp.all(was_fixed, axis=1))
        expert_counts, rows = expert_counts or {}, []
        logits, cache_k, cache_v = block_step(
            params, tokens, was_fixed, base, active, d.mask_token_id, cache_k, cache_v, block_tables,
            backend=self.backend, mesh=self._kernel_mesh, cfg=self.dcfg, counts=rows,
        )
        expert_counts = self._count(expert_counts, rows, 0)
        with jax.named_scope("sample"):
            logits = logits + bias[:, None, None]
            ok = jnp.all(jnp.logical_or(jnp.isfinite(logits), ~live[:, None, None]), axis=(1, 2))
            # a row's candidate: its best token other than the mask token (never a candidate),
            # or with a temperature a draw; its confidence: that token's probability over the
            # whole vocabulary, float32
            candidates = jnp.where(jnp.arange(logits.shape[-1]) == d.mask_token_id, NEG_INF, logits)
            own = base[:, None] + jnp.arange(b, dtype=jnp.int32)[None, :]
            keys = jax.vmap(jax.vmap(
                lambda s, p, f: jax.random.fold_in(jax.random.fold_in(jax.random.key(s), p), f), in_axes=(None, 0, None)
            ))(seeds, own, forwards)
            picked = _sample(
                candidates.reshape(slots * b, -1), jnp.repeat(temps, b), jnp.repeat(top_ks, b), keys.reshape(-1)
            ).reshape(slots, b)
            at_pick = jnp.take_along_axis(logits, picked[..., None], axis=-1)[..., 0]
            confidence = jnp.exp(at_pick - jax.scipy.special.logsumexp(logits, axis=-1))
            masked = jnp.logical_and(live[:, None], ~was_fixed)
            # the n_fix masked rows of highest confidence, ties to the lower position
            order = jnp.argsort(-jnp.where(masked, confidence, -1.0), axis=1, stable=True)
            top = jnp.argsort(order, axis=1, stable=True) < n_fix[:, None]
            passes = jnp.logical_and(masked, confidence > threshold[:, None])
            enough = jnp.sum(passes, axis=1) >= n_fix
            chosen = jnp.logical_and(masked, jnp.where(enough[:, None], passes, top))
            out = jnp.where(chosen, picked, tokens)
        # the state after this forward: a committed slot starts its next block
        keep = lambda new, old: jnp.where(live.reshape((-1,) + (1,) * (old.ndim - 1)), new, old)  # noqa: E731
        next_tokens = keep(jnp.where(commit[:, None], 0, out), tokens)
        next_fixed = keep(jnp.where(commit[:, None], 0, jnp.logical_or(was_fixed, chosen).astype(jnp.int32)), fixed)
        next_base = keep(base + b * commit.astype(jnp.int32), base)
        next_forwards = keep(jnp.where(commit, 0, forwards + 1), forwards)
        return out, chosen, commit, ok, cache_k, cache_v, expert_counts, next_tokens, next_fixed, next_base, next_forwards

    def _restore_state_impl(self, state, slot, block):
        """A prefix hit's restore: the slot's convolution state becomes
        the snapshot stored with the last matched block, from which the
        suffix prefill continues."""
        self.trace_counts["state_restore"] = self.trace_counts.get("state_restore", 0) + 1
        self.programs.note_trace("state_restore", {"state": state, "slot": slot, "block": block})
        at = jax.lax.dynamic_index_in_dim(state["snap"], block, axis=1)  # [n_conv, 1, K-1, E]
        conv = jax.lax.dynamic_update_slice_in_dim(state["conv"], at, slot, axis=1)
        return {"conv": conv, "snap": state["snap"]}

    def _prefix_prefill_impl(
        self, params, tokens, start, n_real, cache_k, cache_v, block_table, temp, top_k, key, mask,
        state=None, slot=None, counts=None, wtable=None,
    ):
        """Suffix-only prefill against a cached prefix: the [1, W]
        suffix window attends over the block table (shared prefix
        blocks + fresh suffix blocks) via the same chunked-append
        forward speculative verification uses, writes the suffix K/V,
        and samples the first generated token from the last REAL suffix
        position's logits. One program per suffix bucket W — admission
        cost, never steady state."""
        w = tokens.shape[1]
        name = f"prefix_prefill[{w}]"
        self.trace_counts[name] = self.trace_counts.get(name, 0) + 1
        self.programs.note_trace(name, {
            "params": params, "tokens": tokens, "start": start,
            "n_real": n_real, "cache_k": cache_k,
            "block_table": block_table, "temp": temp, "top_k": top_k,
            "key": key, "mask": mask,
        })
        offs = jnp.arange(w, dtype=jnp.int32)
        positions = jnp.where(offs < n_real, start + offs, -1)[None, :]
        state, counts, rows = state or {}, counts or {}, []
        conv_in = None
        if self.state_config is not None:
            # the window continues from the state the slot holds: the
            # restore (_restore_state_impl) put the matched prefix's there
            conv_in = jax.lax.dynamic_slice_in_dim(state["conv"], slot, 1, axis=1)
        window = None
        if self.window_config is not None:
            window = {"k": state["wk"], "v": state["wv"],
                      "tables": wtable["tables"][None], "first": wtable["first"][None]}
        attended = None
        if self.diffusion is not None:
            # under the block mask a row attends up to its block's end (the suffix is whole blocks:
            # a reused prefix ends on a cache block's boundary, which is a diffusion block's)
            b = self.diffusion.block_length
            attended = jnp.where(positions >= 0, (positions // b + 1) * b - 1, -1)
        logits, cache_k, cache_v, *zs = verify_step(
            params, tokens, positions, cache_k, cache_v, block_table[None],
            backend=self.backend, mesh=self._kernel_mesh, cfg=self.dcfg,
            conv_in=conv_in, counts=rows, window=window, attend_positions=attended, head=self.diffusion is None,
        )
        if self.window_config is not None:
            state = dict(state, wk=zs[-1]["k"], wv=zs[-1]["v"])
        if self.state_config is not None:
            # a reused prefix ends on a block boundary for such a
            # configuration (prefix_plan), so the window's blocks are whole
            state = self._write_state(
                state, zs[0], slot, n_real, block_table, start // self.cache_config.block_size
            )
        counts = self._count(counts, rows, 1)
        if self.diffusion is not None:
            return self._no_token(logits[0, n_real - 1]) + (cache_k, cache_v, state, counts)
        last = logits[0, n_real - 1]
        ok = jnp.all(jnp.isfinite(last))  # blame: poisoned prompt
        last = last + mask  # grammar mask: [V], finite (see _prefill_impl)
        token = _sample(last[None], temp[None], top_k[None], key[None])[0]
        return token, ok, cache_k, cache_v, state, counts

    def _copy_block_impl(self, cache_k, cache_v, src, dst):
        """COW: duplicate one block's K/V across all layers (the first
        divergent append into a shared block lands in the copy)."""
        self.trace_counts["kv_cow_copy"] = self.trace_counts.get("kv_cow_copy", 0) + 1
        self.programs.note_trace("kv_cow_copy", {
            "cache_k": cache_k, "src": src, "dst": dst,
        })
        k = jax.lax.dynamic_index_in_dim(cache_k, src, axis=1)
        v = jax.lax.dynamic_index_in_dim(cache_v, src, axis=1)
        return (
            jax.lax.dynamic_update_slice_in_dim(cache_k, k, dst, axis=1),
            jax.lax.dynamic_update_slice_in_dim(cache_v, v, dst, axis=1),
        )

    def _read_block_impl(self, cache_k, cache_v, src, snap=None):
        """Host-tier swap-out read: one block's K/V ([L, bs, H, D]
        each: off the device a block has its logical shape, whatever
        rows the cache stores it in), fetched with a traced index so
        every block id shares ONE program. With ``snap`` (a
        configuration with convolution layers) a third result: the
        state stored with the block, [n_conv, K - 1, E]."""
        self.trace_counts["kv_block_read"] = self.trace_counts.get("kv_block_read", 0) + 1
        self.programs.note_trace("kv_block_read", {"cache_k": cache_k, "src": src})
        out = (
            self._logical(jax.lax.dynamic_index_in_dim(cache_k, src, axis=1, keepdims=False)),
            self._logical(jax.lax.dynamic_index_in_dim(cache_v, src, axis=1, keepdims=False)),
        )
        if self.state_config is None:
            return out
        return out + (jax.lax.dynamic_index_in_dim(snap, src, axis=1, keepdims=False),)

    def _read_window_impl(self, wk, wv, src):
        """The window half of a cached block, read out with it for the
        host tier: [2, n_window, bs, H, D], K then V."""
        self.trace_counts["kv_window_read"] = self.trace_counts.get("kv_window_read", 0) + 1
        self.programs.note_trace("kv_window_read", {"wk": wk, "src": src})
        return jnp.stack([
            self._logical(jax.lax.dynamic_index_in_dim(wk, src, axis=1, keepdims=False)),
            self._logical(jax.lax.dynamic_index_in_dim(wv, src, axis=1, keepdims=False)),
        ])

    def _write_window_impl(self, wk, wv, dst, host_w):
        """A swap-in's window half, back into the window pool at ``dst``."""
        self.trace_counts["kv_window_write"] = self.trace_counts.get("kv_window_write", 0) + 1
        self.programs.note_trace("kv_window_write", {"wk": wk, "dst": dst, "host_w": host_w})
        return (
            jax.lax.dynamic_update_slice_in_dim(wk, self._stored(host_w[0][:, None], wk), dst, axis=1),
            jax.lax.dynamic_update_slice_in_dim(wv, self._stored(host_w[1][:, None], wv), dst, axis=1),
        )

    def _logical(self, blocks):
        """Stored [L, ..., bs, R, LW] -> logical [L, ..., bs, H, D]. (A
        latent cache's rows, and its V of no width, leave the device as
        they are stored.)"""
        cc = self.cache_config
        if cc.latent:
            return blocks
        return blocks.reshape(*blocks.shape[:-2], cc.num_heads, cc.head_dim)

    def _stored(self, blocks, like):
        """Logical [L, ..., bs, H, D] -> as ``like`` (a cache array)
        stores it: [L, ..., bs, R, LW], in its dtype."""
        if self.cache_config.latent:
            return blocks.astype(like.dtype)
        return blocks.reshape(*blocks.shape[:-2], *like.shape[3:]).astype(like.dtype)

    def _write_block_impl(self, cache_k, cache_v, dst, host_k, host_v, snap=None, host_s=None):
        """Host-tier swap-in write: place one block's K/V (and, with
        ``snap``, the state stored with it) back into the device cache
        at ``dst``."""
        self.trace_counts["kv_block_write"] = self.trace_counts.get("kv_block_write", 0) + 1
        self.programs.note_trace("kv_block_write", {
            "cache_k": cache_k, "dst": dst, "host_k": host_k,
        })
        out = (
            jax.lax.dynamic_update_slice_in_dim(
                cache_k, self._stored(host_k[:, None], cache_k), dst, axis=1
            ),
            jax.lax.dynamic_update_slice_in_dim(
                cache_v, self._stored(host_v[:, None], cache_v), dst, axis=1
            ),
        )
        if self.state_config is None:
            return out
        return out + (
            jax.lax.dynamic_update_slice_in_dim(snap, host_s[:, None].astype(snap.dtype), dst, axis=1),
        )

    def _read_blocks_impl(self, cache_k, cache_v, srcs):
        """Batched wire read: one payload's blocks ([L, n, bs, H, D]
        each) gathered in a single program. ``srcs`` is padded to
        ``max_blocks_per_seq`` by repeating the last id, so every
        prompt length shares ONE fixed-shape program."""
        self.trace_counts["kv_blocks_read"] = self.trace_counts.get("kv_blocks_read", 0) + 1
        self.programs.note_trace("kv_blocks_read", {"cache_k": cache_k, "srcs": srcs})
        return (
            self._logical(jnp.take(cache_k, srcs, axis=1)),
            self._logical(jnp.take(cache_v, srcs, axis=1)),
        )

    def _write_blocks_impl(self, cache_k, cache_v, dsts, host_ks, host_vs):
        """Batched wire write: commit one payload's blocks in a single
        program. A scan keeps the duplicate padding ids harmless — a
        repeated destination is simply rewritten with the same data."""
        self.trace_counts["kv_blocks_write"] = self.trace_counts.get("kv_blocks_write", 0) + 1
        self.programs.note_trace("kv_blocks_write", {
            "cache_k": cache_k, "dsts": dsts, "host_ks": host_ks,
        })

        def body(carry, x):
            ck, cv = carry
            dst, hk, hv = x
            ck = jax.lax.dynamic_update_slice_in_dim(
                ck, self._stored(hk[:, None], ck), dst, axis=1
            )
            cv = jax.lax.dynamic_update_slice_in_dim(
                cv, self._stored(hv[:, None], cv), dst, axis=1
            )
            return (ck, cv), None

        (ck, cv), _ = jax.lax.scan(
            body, (cache_k, cache_v), (dsts, host_ks, host_vs)
        )
        return ck, cv

    # ----------------------------------------------------------- host API
    def _record_step_phases(
        self, kind: str, disp: phase, block: phase, read: phase
    ) -> Tuple[float, float]:
        """Account one blocking step from its three host spans
        (``ff.engine.<kind>.dispatch | block | readback``, opened
        through obs/steptrace.phase) and publish them for the
        scheduler's step-anatomy profiler. The device-lane "execute"
        span is not host work and opens no annotation: in a blocking
        step the device computes while the host sits in "block", so it
        takes that span's stamps (the two diverge only under the
        overlap pipeline). ``phase_time_s`` stays contiguous — each
        phase runs to the start of the next — so its sum is the whole
        call, as it has always been.
        Returns (total_elapsed_s, execute_s) — the old conflated total
        and the device-only seconds the truth ledger pairs."""
        ph = self.phase_time_s[kind]
        ph["dispatch"] += block.t0 - disp.t0
        ph["execute"] += block.seconds
        ph["readback"] += read.t1 - block.t1
        self.last_step_spans = [
            disp.span, block.span, ("execute", block.t0, block.t1), read.span,
        ]
        self.last_step_children = self._children
        return read.t1 - disp.t0, block.seconds

    def prefill_one(
        self,
        prompt: Sequence[int],
        block_table: Sequence[int],
        sampling: SamplingParams,
        key: jax.Array,
        prefix_len: int = 0,
        mask=None,
        slot: int = 0,
    ) -> int:
        """Prefill one sequence into its allocated blocks and sample its
        first generated token. ``block_table`` is the sequence's block
        ids (padded internally to the engine's fixed table width).
        ``slot`` is the batch slot the sequence will decode in: where a
        configuration's convolution layers keep its state.
        ``prefix_len`` > 0 means positions [0, prefix_len) are already
        cached (shared prefix blocks at the front of the table): only
        the suffix is computed, attending to the cached prefix — the
        O(suffix) admission path prefix caching exists for.
        ``mask`` is an optional [vocab] grammar bias (0 / NEG) applied
        to the sampled position; None stages the shared zeros row."""
        faults.inject(faults.GENERATION_PREFILL, prompt)
        if prefix_len > 0:
            return self._prefill_suffix(prompt, block_table, sampling, key, prefix_len, mask, slot)
        self.step_counts["prefill"] += 1
        self.latent_calls["expanded"] += self._n_latent
        self._count_expert_form(self.bucket_for(len(prompt)))
        self._count_prefill_attention(self.bucket_for(len(prompt)), len(prompt))
        if self.window_config is not None and slot not in self.window_tables:
            # (a caller that assembled its own table: prepare_prefix does this for the scheduler)
            self._window_admit(slot, len(prompt), 0, [])
        self._children = []
        with phase("engine.prefill.dispatch") as disp:
            with self._part("prefill", "args"):
                n = len(prompt)
                bucket = self.bucket_for(n)
                traces_before = self.trace_counts.get(f"prefill[{bucket}]", 0)
                tokens = np.zeros((1, bucket), np.int32)
                tokens[0, :n] = prompt
                table = np.zeros((self.max_blocks_per_seq,), np.int32)
                table[: len(block_table)] = block_table
            with self._part("prefill", "upload"):
                args = (
                    self._dev(tokens),
                    jnp.int32(n),
                    self.cache.k,
                    self.cache.v,
                    self._dev(table),
                    jnp.float32(sampling.temperature),
                    jnp.int32(sampling.top_k),
                    self._dev(key),
                    self._mask_arg(mask, "prefill_mask", (self.cfg.vocab_size,)),
                    *self._state_args(slot, self.window_columns),
                )
            with self._part("prefill", "call"):
                token, ok, ck, cv, state, counts = self._prefill_jit(self.params, *args)
        self._dispatched()
        with phase("engine.prefill.block") as block:
            jax.block_until_ready((token, ok, ck, cv, state))  # device execution done
        with phase("engine.prefill.readback") as read:
            if self._rows_install:
                with phase("cache.state_install", slot=slot):
                    ck, cv, state = self._install_rows_jit(
                        self.cache.k, self.cache.v, self._step_state(), jnp.int32(slot), state, args[1], args[4], args[-1]
                    )
            elif self.slot_state is not None:
                state = self._install_state(slot, state)
            self.cache.update(ck, cv, **state)
            self.expert_counts = counts
            self.last_finite = np.asarray(ok).reshape(1)
            out = int(token)  # result sync lands inside the readback span
        if self._cross_decoder:
            self.cross_rows_run += 1
            self.cross_rows_skipped += bucket - 1
        elapsed, execute_s = self._record_step_phases("prefill", disp, block, read)
        # FLOPs accrue only on SUCCESS, next to the time they pair with:
        # a step that raises (and is retried by the supervisor) must not
        # count its FLOPs without its time, or MFU inflates under faults
        flops = self.flops_model.prefill_flops(n)
        self.flops_by_kind["prefill"] += flops
        if self.trace_counts.get(f"prefill[{bucket}]", 0) > traces_before:
            # this call traced (first compile or a retrace): its wall
            # time is the program's compile cost, registry-stamped
            self.programs.set_compile_time(f"prefill[{bucket}]", elapsed)
        else:
            # ledger prediction covers EXECUTED work — the program
            # computes the full padded bucket, so predicting from the
            # true prompt length would alarm on every short prompt in a
            # wide bucket. MFU above stays useful-work-only.
            self.ledger.observe(
                f"prefill[{bucket}]",
                self.flops_model.roofline_s(
                    self.flops_model.prefill_flops(bucket),
                    self.flops_model.prefill_bytes(bucket),
                ),
                execute_s,
                label=f"prefill[{bucket}] ({self.flops_model.chip.name})",
                provenance="serving roofline (ServingFlops x chip peak)",
                alarm=self._roofline_alarm,
            )
            if self.serving_strategy is not None:
                # pair the measured step against the layout-search
                # estimate too: drift telemetry covers the DECISION
                self.ledger.measure("serving_strategy:prefill", execute_s)
        return out

    def _prefill_suffix(
        self,
        prompt: Sequence[int],
        block_table: Sequence[int],
        sampling: SamplingParams,
        key: jax.Array,
        prefix_len: int,
        mask=None,
        slot: int = 0,
    ) -> int:
        """Suffix-only prefill: positions [prefix_len, len(prompt))
        computed against the cached prefix. Accounting mirrors
        prefill(): step/FLOPs/time under the "prefill" kind, compile
        calls registry-stamped, steady calls ledger-paired."""
        self.step_counts["prefill"] += 1
        self.latent_calls["absorbed"] += self._n_latent
        self._count_expert_form(self.bucket_for(len(prompt) - prefix_len))
        if self.state_config is not None:
            self._restore_state(slot, block_table[prefix_len // self.cache_config.block_size - 1])
        self._children = []
        with phase("engine.prefill.dispatch") as disp:
            with self._part("prefill", "args"):
                n = len(prompt)
                suffix = list(prompt[prefix_len:])
                w = self.bucket_for(len(suffix))
                name = f"prefix_prefill[{w}]"
                traces_before = self.trace_counts.get(name, 0)
                tokens = np.zeros((1, w), np.int32)
                tokens[0, : len(suffix)] = suffix
                table = np.zeros((self.max_blocks_per_seq,), np.int32)
                table[: len(block_table)] = block_table
            with self._part("prefill", "upload"):
                args = (
                    self._dev(tokens),
                    jnp.int32(prefix_len),
                    jnp.int32(len(suffix)),
                    self.cache.k,
                    self.cache.v,
                    self._dev(table),
                    jnp.float32(sampling.temperature),
                    jnp.int32(sampling.top_k),
                    self._dev(key),
                    self._mask_arg(mask, "prefill_mask", (self.cfg.vocab_size,)),
                    *self._state_args(slot, self._suffix_columns(w)),
                )
            with self._part("prefill", "call"):
                token, ok, ck, cv, state, counts = self._prefix_prefill_jit(self.params, *args)
        self._dispatched()
        with phase("engine.prefill.block") as block:
            jax.block_until_ready((token, ok, ck, cv, state))  # device execution done
        with phase("engine.prefill.readback") as read:
            self.cache.update(ck, cv, **state)
            self.expert_counts = counts
            self.last_finite = np.asarray(ok).reshape(1)
            out = int(token)  # result sync lands inside the readback span
        if self.window_config is not None:
            # the matched prefix's window was this prefill's to read: what
            # lies behind the next query's goes back now, not a step later
            self._window_advance(self.window_tables[slot], n, grow=False)
        elapsed, execute_s = self._record_step_phases("prefill", disp, block, read)
        # useful work = suffix tokens only, each attending its full live
        # context (causal): ctx = sum_{p=prefix_len}^{n-1} (p + 1)
        ctx = (n * (n + 1) - prefix_len * (prefix_len + 1)) // 2
        flops = self.flops_model.verify_flops(len(suffix), ctx)
        self.flops_by_kind["prefill"] += flops
        if self.trace_counts.get(name, 0) > traces_before:
            self.programs.set_compile_time(name, elapsed)
        else:
            # EXECUTED work: the program computes the full padded W
            # window (padding attends to nothing — see verify())
            self.ledger.observe(
                name,
                self.flops_model.roofline_s(
                    self.flops_model.verify_flops(w, ctx),
                    self.flops_model.verify_bytes(w, ctx),
                ),
                execute_s,
                label=f"{name} ({self.flops_model.chip.name})",
                provenance="serving roofline (ServingFlops x chip peak)",
                alarm=self._roofline_alarm,
            )
        return out

    def _state_args(self, slot: int, window_columns: int = 0) -> tuple:
        """The trailing (state, slot, counts) of the prefill programs
        and, where the configuration has window layers, the slot's table
        of the window pool at ``window_columns`` columns."""
        args = (
            # (a state-space configuration's prefill RETURNS the slot's parts, and beside a window pool the prompt's
            # K/V rows, and takes none: _install_state, _install_rows_impl)
            self.cache.state if self.slot_state is None else {},
            jnp.int32(slot) if self.state_config is not None else None,
            self.expert_counts,
        )
        if self.window_config is None:
            return args
        t = self.window_tables[slot]
        table = np.zeros((window_columns,), np.int32)
        table[: len(t.blocks)] = t.blocks[:window_columns]
        return args + ({"tables": self._dev(table), "first": jnp.int32(t.first * self.window_config.block_size)},)

    def _suffix_columns(self, bucket: int) -> int:
        """Columns of the window table a suffix prefill of ``bucket``
        tokens reads: the window behind its first query and its own."""
        return self.window_columns + self.window_config.blocks_for(bucket) if self.window_config else 0

    # ------------------------------------------------- the window pool
    def _window_allocate(self, n: int) -> Optional[List[int]]:
        """``n`` blocks of the window pool; the window halves of cached
        prefixes that no live sequence's table includes are what it
        takes back first (the pool's size guarantees the rest:
        CacheConfig.for_slots)."""
        got = self.window_allocator.allocate(n)
        if got is None:
            # a batch a scan: every slot asks for a block once a block's worth of steps
            self.prefix_cache.reclaim_window(max(n - self.window_allocator.num_free, self.max_batch_slots))
            got = self.window_allocator.allocate(n)
        return got

    def _window_release(self, t: WindowTable, upto: int, counted: bool = True) -> None:
        """Give back the table's blocks of block indices below ``upto``:
        a private block to the pool, a prefix entry's half to the entry
        (one reference fewer on it). ``counted``: into
        ``window_released_total``, which is what a sequence gives back
        while it lives, not what it leaves when it goes."""
        while t.blocks and t.first < upto:
            block = t.blocks.pop(0)
            entry = t.shared.pop(t.first, None)
            if entry is not None:
                entry.wrefs -= 1
            else:
                self.window_allocator.free([block])
            t.first += 1
            self.window_released_total += counted
        if not t.blocks:
            t.first = max(t.first, upto)

    def _window_advance(self, t: WindowTable, position: int, grow: bool = True) -> None:
        """Bring a sequence's window table to where a query at
        ``position`` needs it: every block wholly behind ``position -
        window + 1`` released, and (``grow``) the block ``position``
        lies in taken if the table ends before it."""
        wc = self.window_config
        self._window_release(t, max(0, position - wc.window + 1) // wc.block_size)
        while grow and t.end <= position // wc.block_size:
            got = self._window_allocate(1)
            if got is None:
                raise RuntimeError(
                    f"the window layers' pool is out of blocks ({wc.num_blocks}) with every live "
                    f"sequence inside its bound: sized for fewer slots than are decoding"
                )
            t.blocks.extend(got)

    def _window_admit(self, slot: int, n_tokens: int, prefix_len: int, entries: Sequence[PrefixEntry]) -> None:
        """The slot's window table for an admission of ``n_tokens``
        whose first ``prefix_len`` (whole blocks) are reused from
        ``entries``: the entries' window halves for the window behind
        the first computed position (a reference on each), then private
        blocks up to the block the first decode step writes in. A plain
        prefill keeps only what the next query can reach."""
        wc = self.window_config
        self.release_slot(slot)
        start = prefix_len if prefix_len else n_tokens
        t = WindowTable(first=max(0, start - wc.window + 1) // wc.block_size)
        for j in range(t.first, prefix_len // wc.block_size):
            entry = entries[j]
            t.blocks.append(entry.wblock)
            t.shared[j] = entry
            entry.wrefs += 1
        self.window_tables[slot] = t
        need = wc.blocks_for(n_tokens + 1) - t.end
        if need > 0:
            got = self._window_allocate(need)
            if got is None:
                self.release_slot(slot)
                raise RuntimeError(f"the window layers' pool cannot hold an admission of {need} blocks")
            t.blocks.extend(got)

    def release_slot(self, slot: int) -> None:
        """A sequence left ``slot`` (finished, preempted, failed): its
        window table's blocks go back. A no-op without window layers."""
        t = self.window_tables.pop(slot, None)
        if t is not None:
            self._window_release(t, t.end, counted=False)

    def _window_boundary(self, entries: Sequence[PrefixEntry]) -> int:
        """The longest run of ``entries`` a prefix can be resumed after:
        the largest ``m`` such that every entry of the window behind
        position ``m * block_size`` still has its window half."""
        wc = self.window_config
        best = held = 0  # held: entries up to the m-th, without a gap, that have their halves
        for m, entry in enumerate(entries, start=1):
            held = held + 1 if self.prefix_cache.has_window(entry) else 0
            if held >= m - max(0, m * wc.block_size - wc.window + 1) // wc.block_size:
                best = m
        return best

    def _share_window(self, slot: int, entries: Sequence[PrefixEntry]) -> None:
        """Newly registered ``entries`` take over the window blocks the
        slot holds at their block indices (the sequence keeps a
        reference, as it does on the full half)."""
        t = self.window_tables.get(slot)
        if t is None:
            return
        for entry in entries:
            j = entry.depth
            if t.first <= j < t.end and j not in t.shared and not entry.wblock:
                entry.wblock = t.blocks[j - t.first]
                entry.wrefs += 1
                t.shared[j] = entry

    def cache_stats(self) -> Dict:
        """The ``cache`` section of ``/v2/stats``, by kind of layer: each
        pool's blocks, what decoding has released of the window pool,
        the bytes the sequences of the last decode step held, and the
        bytes ONE table for all attention layers would hold for them."""
        cc, wc = self.cache_config, self.window_config
        if cc.latent:
            return {"latent": self.latent_stats()}
        ssm = {}
        if self.slot_state is not None:
            ss, live = self.slot_state, self._ssm_slots_live
            ssm = {
                "ssm": {"layers": ss.num_layers, "slots": ss.slots, "bytes_per_slot": ss.bytes_per_sequence,
                        "state_bytes_per_slot": ss.part_bytes("ssm"), "conv_bytes_per_slot": ss.part_bytes("ssm_conv"),
                        "slots_live": live, "bytes_held": ss.total_bytes, "live_bytes": live * ss.bytes_per_sequence},
            }
        if self.dcfg.cross_layers:
            # ONE layer's K/V that the cross layers read again: what a token costs, and what it would cost
            # if every reader stored K/V of its own
            readers = len(self.dcfg.cross_layers)
            ssm["shared_kv"] = {
                "producer_layers": [self.dcfg.kv_source], "reader_layers": list(self.dcfg.cross_layers),
                "bytes_per_token": cc.bytes_per_token, "bytes_saved_per_token": readers * cc.bytes_per_token // max(1, cc.num_layers),
            }
        if wc is None:
            return {
                **ssm,
                "full": {"layers": cc.num_layers, "blocks_total": self.allocator.num_total,
                         "blocks_used": self.allocator.num_total - self.allocator.num_free,
                         "bytes_per_block": cc.bytes_per_block},
            }
        full, window = self._live_blocks
        return {
            **ssm,
            "full": {"layers": cc.num_layers, "blocks_total": self.allocator.num_total,
                     "blocks_used": self.allocator.num_total - self.allocator.num_free,
                     "bytes_per_block": cc.bytes_per_block},
            "window": {"layers": wc.num_layers, "window": wc.window,
                       "blocks_total": self.window_allocator.num_total,
                       "blocks_used": self.window_allocator.num_total - self.window_allocator.num_free,
                       "bytes_per_block": wc.bytes_per_block,
                       "blocks_per_sequence": self.window_columns,
                       "held_by_a_sequence_peak": self.window_held_peak},
            "window_released_total": self.window_released_total,
            "window_releases_total": self.window_releases_total,
            "window_release_total_s": self.window_release_total_s,
            "live_bytes": full * cc.bytes_per_block + window * wc.bytes_per_block,
            "one_table_bytes": full * (cc.bytes_per_block + wc.bytes_per_block),
        }

    def prefill_stats(self) -> Dict:
        """The ``prefill`` section of ``/v2/stats`` (a configuration with a
        cross-decoder): of the rows the prefill programs ran, a bucket's
        each, those its layers ran on (one a prompt) and those they were
        spared."""
        return {
            "cross_layers": self.dcfg.num_layers - self.dcfg.cross_from,
            "cross_rows_run_total": self.cross_rows_run, "cross_rows_skipped_total": self.cross_rows_skipped,
        }

    def latent_stats(self) -> Dict:
        """``cache.latent`` of ``/v2/stats``: what a cached position
        costs (all latent layers, as stored), the positions the last
        decode step's sequences held and their bytes, what per-head K/V
        of the same sequences would hold, and the latent layers'
        attention calls by the form they ran."""
        cc, d = self.cache_config, self.dcfg
        per_head = cc.num_layers * d.num_heads * (d.qk_nope_head_dim + d.qk_rope_head_dim + d.v_head_dim)
        held = self.latent_tokens_held
        return {
            "layers": cc.num_layers, "entry_width": d.latent_width, "stored_width": cc.row_shape[0],
            "bytes_per_token": cc.bytes_per_token, "tokens_held": held, "live_bytes": held * cc.bytes_per_token,
            "per_head_bytes": held * per_head * cc.dtype.size_bytes,
            "absorbed_calls_total": self.latent_calls["absorbed"],
            "expanded_calls_total": self.latent_calls["expanded"],
        }

    def blocks_in_use(self) -> Tuple[int, int]:
        """(used, total) blocks of the pool that is fuller: what
        ``cache_blocks_used`` / ``cache_blocks_total`` report. With one
        pool, that pool."""
        pools = [self.allocator] + ([self.window_allocator] if self.window_allocator else [])
        fuller = max(pools, key=lambda a: 1.0 - a.num_free / max(1, a.num_total))
        return fuller.num_total - fuller.num_free, fuller.num_total

    def _restore_state(self, slot: int, block: int) -> None:
        """A prefix hit on a configuration with convolution layers: the
        slot takes the state stored with the last matched block (span
        ``ff.cache.state_restore``; ``conv_state.restores_total``)."""
        with phase("cache.state_restore", slot=slot):
            self.cache.state = self._restore_state_jit(
                self.cache.state, jnp.int32(slot), jnp.int32(block)
            )
        self.state_restores_total += 1

    # ------------------------------------------------------ prefix caching
    def prefix_plan(self, prompt: Sequence[int]) -> PrefixPlan:
        """Match ``prompt`` against the radix index and decide what to
        reuse. Offloaded entries in the matched run are only used when
        the host->device transfer beats recomputing the same positions
        on the chip roofline (the PR 7 cost-model idiom); otherwise the
        run truncates at the first offloaded entry. A failed lookup
        (``generation.prefix_lookup`` chaos) degrades to a miss — full
        recompute, byte-exact."""
        pc = self.prefix_cache
        if not pc.enabled or len(prompt) < 2:
            return EMPTY_PREFIX_PLAN
        try:
            faults.inject(faults.GENERATION_PREFIX_LOOKUP, list(prompt))
            run = pc.match(prompt)
        except Exception:
            pc.recompute_fallbacks += 1
            return EMPTY_PREFIX_PLAN
        if not run:
            return EMPTY_PREFIX_PLAN
        bs = self.cache_config.block_size
        reuse = min(len(run) * bs, len(prompt) - 1)
        n_shared = reuse // bs
        cow = run[n_shared] if (reuse % bs and len(run) > n_shared) else None
        if self.state_config is not None or self.diffusion is not None:
            # a convolution state is stored at a block's END and nowhere
            # else: reuse stops on the last whole block (a fully covered
            # prompt recomputes its last block instead of copying it).
            # Block diffusion: a hit that does not end on a multiple of
            # the block length is cut back to one, and a whole cache
            # block always ends on one (the constructor holds the cache's
            # block size to a multiple)
            reuse, cow = n_shared * bs, None
            if not reuse:
                return EMPTY_PREFIX_PLAN
        if self.window_config is not None:
            # a prefix is resumed at a block boundary behind which the
            # window layers' blocks are still held: the longest such, or none
            n_shared = self._window_boundary(run[:n_shared])
            reuse, cow = n_shared * bs, None
            if not reuse:
                return EMPTY_PREFIX_PLAN
        entries = run[:n_shared]
        off_idx = [i for i, e in enumerate(entries) if not e.resident]
        cow_off = cow is not None and not cow.resident
        if off_idx or cow_off:
            n_off = len(off_idx) + (1 if cow_off else 0)
            first = off_idx[0] if off_idx else n_shared
            # the recompute alternative: truncate at the first offloaded
            # entry and prefill positions [first*bs, reuse) instead
            start = first * bs
            n_tok = reuse - start
            ctx = (reuse * (reuse + 1) - start * (start + 1)) // 2
            recompute_s = self.flops_model.roofline_s(
                self.flops_model.verify_flops(n_tok, ctx),
                self.flops_model.verify_bytes(n_tok, ctx),
            )
            if pc.swap_in_cost_s(n_off) >= recompute_s:
                pc.recompute_fallbacks += 1
                entries = entries[:first]
                reuse = first * bs
                cow = None
        n_resident = sum(1 for e in entries if e.resident)
        return PrefixPlan(entries, cow, reuse, n_resident)

    def prepare_prefix(
        self,
        prompt: Sequence[int],
        plan: PrefixPlan,
        new_blocks: List[int],
        slot: int = 0,
    ) -> Optional[Tuple[List[int], set, List[PrefixEntry], int]]:
        """Assemble one admission's block table from a plan: shared
        entries first (swapping offloaded ones back in), then the
        private blocks (COW boundary copy, suffix, growth room).
        Returns (table, shared_idx, held entries, prefix_len), or None
        when a mid-assembly swap-in fallback could not replace the lost
        shared blocks — everything is handed back and the caller
        retries admission later.

        A failed or corrupted swap-in truncates reuse at that entry and
        falls back to recomputing the rest — the exactness invariant
        makes the fallback invisible in the token stream."""
        pc = self.prefix_cache
        bs = self.cache_config.block_size
        entries = list(plan.entries)
        cow = plan.cow
        reuse = plan.reuse_tokens
        pc.acquire(entries)
        if cow is not None:
            # hold the boundary entry too: the reclaim fallback below
            # must not evict the COW source out from under the copy
            pc.acquire([cow])
        pool = list(new_blocks)
        shared: List[int] = []
        kept: List[PrefixEntry] = []
        failed_at: Optional[int] = None
        for i, e in enumerate(entries):
            if e.resident:
                shared.append(e.block)
                kept.append(e)
                continue
            if not pool:
                failed_at = i  # stale plan: no swap target left
                break
            dst = pool.pop(0)
            if self._swap_in(e, dst):
                shared.append(e.block)
                kept.append(e)
            else:
                pool.insert(0, dst)
                failed_at = i
                break
        need_total = self.cache_config.blocks_for(len(prompt) + 1)
        if failed_at is not None:
            pc.release(entries[failed_at:])
            entries = list(kept)
            reuse = len(kept) * bs
            if cow is not None:
                pc.release([cow])
                cow = None
        if self.window_config is not None:
            # a swap-in may have come back without its window half, or
            # pushed another entry's out: hold the rule on what is kept
            m = self._window_boundary(kept)
            pc.release(kept[m:])
            del kept[m:], shared[m:]
            entries, reuse = list(kept), m * bs
        # re-balance the private pool against the full table budget.
        # The plan's resident count can go stale between planning and
        # assembly: a reclaim (this admission's own, or the allocator
        # retry's) may evict a planned-resident entry, whose swap-in
        # then consumes a pool block budgeted for the suffix — a short
        # table would silently map suffix positions to the scratch
        # block and corrupt the stream. Top the pool back up (or hand
        # everything back and let the caller retry).
        short = need_total - len(shared) - len(pool)
        if short > 0:
            extra = self.allocator.allocate(short)
            if extra is None and self.reclaim_cached(short):
                extra = self.allocator.allocate(short)
            if extra is None:
                if cow is not None:
                    pc.release([cow])
                pc.release(kept)
                self.allocator.free(pool)
                return None
            pool.extend(extra)
        if cow is not None:
            # the boundary block: the copy target doubles as the plain
            # private block when the COW source is unusable (corrupt
            # offloaded content) — the table shape is identical either
            # way, only prefix_len changes
            if pool and self._cow_copy(cow, pool[0]):
                pc.cow_copies_total += 1
            else:
                reuse = len(kept) * bs
            pc.release([cow])
        table = shared + pool
        if len(table) > need_total:
            surplus = table[need_total:]
            del table[need_total:]
            self.allocator.free(surplus)
        if self.window_config is not None:
            self._window_admit(slot, len(prompt), reuse, kept)
        return table, set(range(len(shared))), kept, reuse

    def _swap_in(self, entry: PrefixEntry, dst: int) -> bool:
        """Bring one offloaded entry's K/V back to device block ``dst``.
        CRC-verified; the (predicted, measured) transfer time joins the
        PredictionLedger so drift telemetry covers the swap heuristic."""
        pc = self.prefix_cache
        predicted = pc.swap_in_cost_s(1)
        traces_before = self.trace_counts.get("kv_block_write", 0)
        with phase("cache.restore") as restore:
            try:
                faults.inject(faults.GENERATION_KV_OFFLOAD, ("in", 1))
                buf = pc.take_host_copy(entry)
                if buf is None:  # corrupted or already dropped
                    raise ValueError("host-tier block failed CRC verification")
                wblock = self._write_host_copy(dst, buf)
            except Exception:
                pc.swap_in_failures += 1
                pc.recompute_fallbacks += 1
                return False
            pc.note_swapped_in(entry, dst, wblock)
        pc.observe("cache_restore", restore.seconds)
        if self.trace_counts.get("kv_block_write", 0) == traces_before:
            self.ledger.observe(
                "kv_swap_in", predicted, restore.seconds,
                label="kv_swap_in (host tier)",
                provenance="host-tier transfer model (link bytes/s)",
                alarm=self._roofline_alarm,
            )
        return True

    def _cow_copy(self, src: PrefixEntry, dst: int) -> bool:
        """Materialize a private copy of ``src``'s block at ``dst`` —
        from device (resident) or the host tier (offloaded). The source
        entry is untouched: its content stays shared."""
        if src.resident:
            ck, cv = self._copy_block_jit(
                self.cache.k, self.cache.v,
                jnp.int32(src.block), jnp.int32(dst),
            )
            self.cache.update(ck, cv)
            return True
        pc = self.prefix_cache
        try:
            faults.inject(faults.GENERATION_KV_OFFLOAD, ("in", 1))
            buf = pc.take_host_copy(src)
            if buf is None:
                raise ValueError("host-tier block failed CRC verification")
            self._write_host_copy(dst, buf)
        except Exception:
            pc.swap_in_failures += 1
            pc.recompute_fallbacks += 1
            return False
        pc.swaps_in_total += 1
        return True

    def _write_host_copy(self, dst: int, buf) -> int:
        """One host-tier copy (K, V and, where blocks carry one, the
        convolution state stored with the block) into device block
        ``dst``. A window half that came with it goes into a block of
        the window pool, whose id is returned (0: none, or no room)."""
        hk, hv, *hs = buf
        if self.window_config is not None:
            ck, cv = self._write_block_jit(self.cache.k, self.cache.v, jnp.int32(dst), self._dev(hk), self._dev(hv))
            self.cache.update(ck, cv)
            got = self._window_allocate(1) if hs else None
            if not got:
                return 0
            wk, wv = self._write_window_jit(
                self.cache.state["wk"], self.cache.state["wv"], jnp.int32(got[0]), self._dev(hs[0])
            )
            self.cache.state.update(wk=wk, wv=wv)
            return got[0]
        extra = (self.cache.state["snap"], self._dev(hs[0])) if self.state_config is not None else ()
        ck, cv, *snap = self._write_block_jit(
            self.cache.k, self.cache.v, jnp.int32(dst), self._dev(hk), self._dev(hv), *extra
        )
        self.cache.update(ck, cv, **({"snap": snap[0]} if snap else {}))
        return 0

    def register_prefix(
        self,
        prompt: Sequence[int],
        table: List[int],
        shared_idx: set,
        entries: List[PrefixEntry],
        prefix_len: int = 0,
        slot: int = 0,
    ) -> None:
        """Post-prefill registration: the prompt's freshly written full
        blocks join the radix index (ownership moves to the index; the
        sequence keeps a ref). Called only after the finiteness check —
        poisoned K/V must never become shared content. Reuse telemetry
        counts HERE, not at table assembly, so a failed or poisoned
        prefill (whose retry would double-count) never inflates
        hit/reuse ratios with reuse that produced no token."""
        pc = self.prefix_cache
        if not pc.enabled:
            return
        pc.lookups += 1
        if prefix_len > 0:
            pc.hits += 1
            pc.tokens_reused_total += prefix_len
            pc.blocks_reused_total += len(entries)
        held = len(entries)
        n_new = self.prefix_cache.register_chain(
            prompt, table, shared_idx, entries, len(prompt)
        )
        if self.window_config is not None:
            self._share_window(slot, entries[held:])
        if self.state_config is not None:
            # every block a prefill fills to its end got its snapshot
            # from the same program (_write_state)
            self.state_snapshots_total += n_new

    def stash_prefix(self, state) -> None:
        """Preemption stash: register the victim's full blocks below
        ``cached_len`` (prompt AND generated content) so its recompute
        re-admission — and any request sharing the prefix — matches
        them instead of recomputing; under continued pressure they
        offload to the host tier and swap back in."""
        if not self.prefix_cache.enabled:
            return
        req = state.req
        tokens = list(req.original_prompt) + list(req.generated)
        upto = min(state.cached_len, len(tokens))
        if self.state_config is not None:
            # blocks filled while DECODING carry no convolution state (a
            # decode step writes no snapshot: cache.py) and so cannot be
            # resumed from: only what the admission's prefill wrote is
            # registered, and that is registered already
            upto = min(upto, len(req.prompt))
        held = len(state.shared_entries)
        self.prefix_cache.register_chain(
            tokens, state.blocks, state.shared_idx, state.shared_entries, upto
        )
        if self.window_config is not None:
            # the window halves of the blocks the victim still holds: its
            # re-admission resumes at a boundary inside them
            self._share_window(state.slot, state.shared_entries[held:])

    def release_admission(
        self, table: List[int], shared_idx: set, entries: List[PrefixEntry], slot: Optional[int] = None
    ) -> None:
        """Undo one admission's block bookkeeping (failed or poisoned
        prefill): private blocks back to the allocator, shared refs
        dropped (the content stays cached for the next request)."""
        self.allocator.free(
            [b for i, b in enumerate(table) if i not in shared_idx]
        )
        self.prefix_cache.release(entries)
        if slot is not None:
            self.release_slot(slot)

    def reclaim_cached(self, n_blocks: int) -> int:
        """Free device blocks held by unreferenced cached prefixes (LRU;
        content offloads to the host tier when budget allows). The
        allocator's last resort before preemption."""
        if not self.prefix_cache.enabled:
            return 0

        def read(block_id: int, window_block: int = 0):
            faults.inject(faults.GENERATION_KV_OFFLOAD, ("out", 1))
            snap = self.cache.state.get("snap")
            extra = () if snap is None else (snap,)
            out = tuple(np.asarray(a) for a in self._read_block_jit(
                self.cache.k, self.cache.v, jnp.int32(block_id), *extra
            ))
            if window_block:
                out += (np.asarray(self._read_window_jit(
                    self.cache.state["wk"], self.cache.state["wv"], jnp.int32(window_block)
                )),)
            return out

        # one span per call: victim selection, the device reads and the
        # CRCs are all what evicting to the host tier costs an admission
        with phase("cache.offload", blocks=n_blocks) as offload:
            freed = self.prefix_cache.reclaim(max(1, n_blocks), read)
        if freed:
            self.prefix_cache.observe("cache_offload", offload.seconds)
        return freed

    def pack_kv_blocks(
        self, table: List[int], n_positions: int
    ) -> KVHandoffPayload:
        """Pack the blocks covering positions ``[0, n_positions)`` into
        the prefill->decode wire format: full-head host reads through
        the same jitted block reader the host tier uses (the reader's
        replicated out_shardings gather every head even when this
        engine's cache is sharded, so the payload is TP-agnostic), each
        block CRC-stamped at packing time."""
        self._refuse("kv_handoff")
        bs = self.cache_config.block_size
        n_blocks = self.cache_config.blocks_for(n_positions)
        ids = list(table[:n_blocks])
        srcs = ids + [ids[-1]] * (self.max_blocks_per_seq - len(ids))
        ks, vs = self._read_blocks_jit(
            self.cache.k, self.cache.v,
            self._dev(np.asarray(srcs, dtype=np.int32)),
        )
        ks, vs = np.asarray(ks), np.asarray(vs)
        blocks = [
            PackedBlock(np.ascontiguousarray(ks[:, i]),
                        np.ascontiguousarray(vs[:, i]))
            for i in range(len(ids))
        ]
        return KVHandoffPayload(n_positions, bs, blocks)

    def import_kv_block(
        self, dst: int, host_k: np.ndarray, host_v: np.ndarray
    ) -> None:
        """Commit one wire block into this engine's cache at ``dst``
        through the jitted block writer — the write's out_shardings
        reshard the full-head payload onto this engine's own head
        partitioning, so differing pool TP degrees need no explicit
        reshard step."""
        self._refuse("kv_handoff")
        ck, cv = self._write_block_jit(
            self.cache.k, self.cache.v, jnp.int32(dst),
            self._dev(host_k), self._dev(host_v),
        )
        self.cache.update(ck, cv)

    def import_kv_blocks(self, dsts: Sequence[int], blocks) -> None:
        """Commit one payload's wire blocks in a single batched program
        (same resharding semantics as :meth:`import_kv_block`): padded
        to ``max_blocks_per_seq`` by repeating the last block, so a
        decode-pool replica pays one dispatch per adopted stream, not
        one per block, between its decode steps."""
        self._refuse("kv_handoff")
        ids = list(dsts)
        pad = self.max_blocks_per_seq - len(ids)
        idx = ids + [ids[-1]] * pad
        hk = np.stack([b.host_k for b in blocks] + [blocks[-1].host_k] * pad)
        hv = np.stack([b.host_v for b in blocks] + [blocks[-1].host_v] * pad)
        ck, cv = self._write_blocks_jit(
            self.cache.k, self.cache.v,
            self._dev(np.asarray(idx, dtype=np.int32)),
            self._dev(hk), self._dev(hv),
        )
        self.cache.update(ck, cv)

    def _refuse(self, path: str) -> None:
        """Raise the reason this configuration cannot take ``path``
        (``self.unsupported``, filled at construction), if it cannot."""
        if path in self.unsupported:
            raise NotImplementedError(self.unsupported[path])

    def _lookup(self, name: str, host: np.ndarray):
        """Device-resident staging, the half that is host arithmetic:
        the device array ``name`` was uploaded as while its contents are
        still ``host``'s (a hit), else ``(name, host)`` for
        :meth:`_upload` to send (a miss). Slot-constant decode/verify
        args (block tables, sampling params, seeds) change only on
        batch-composition events, so steady state stops paying a fresh
        ``jnp.asarray`` per arg per step."""
        cached = self._staged.get(name)
        if (
            cached is not None
            and cached[0].shape == host.shape
            and cached[0].dtype == host.dtype
            and np.array_equal(cached[0], host)
        ):
            self.uploads["staged_hits_total"] += 1
            return cached[1]
        self.uploads["staged_misses_total"] += 1
        return name, host

    def _upload(self, x) -> jax.Array:
        """The half that transfers: a fresh host array, or a miss of
        :meth:`_lookup` (whose host snapshot is copied: callers may
        mutate their arrays in place afterwards), goes to the device; a
        hit, or an array that is on the device already, passes."""
        if isinstance(x, tuple):
            name, host = x
            dev = self._dev(host)
            self._staged[name] = (host.copy(), dev)
            return dev
        return self._dev(x) if isinstance(x, np.ndarray) else x

    def _stage(self, name: str, host: np.ndarray) -> jax.Array:
        """Both halves at once, for a caller outside a dispatch span."""
        return self._upload(self._lookup(name, host))

    def _drop_carried(self) -> None:
        """Forget the staged entries a decode program returned: that
        program may have failed, and what a failed program returned is
        poisoned (an uploaded entry is not, and stays)."""
        for name in CARRIED + BLOCK_CARRIED:
            self._staged.pop(name, None)

    def _decode_args(self, tokens, positions, block_tables, active, temps, top_ks, seeds, counts, bias, mask=None,
                     window=None):
        """Assemble the decode jit's argument tuple after the
        parameters, in the two children of the dispatch span that the
        work falls into: ``args`` (the masks, the casts, the staging
        compares, the sampling-branch count) and ``upload`` (every
        transfer: a staging miss, a poisoned bias, a mask, and
        ``tokens`` where it is a host array; the pipelined path carries
        it device-resident, and it passes). ``positions`` and ``counts``
        go in raw and are staged like the rest, but their entries are
        what the step before RETURNED (:meth:`_carry`): where the
        host's vectors are that step's advanced by one in every active
        slot, as a step of the same composition's are, they pass, and a
        steady step uploads nothing at all. Anything else (the first
        step, an admission, a finish, a preemption, a probe that clears
        a slot, a step voided or retried) is a miss and one upload each,
        counted in ``carried_hits_total`` / ``carried_misses_total``.
        ``window``: what :meth:`advance_windows` returned for this step,
        where the caller has made that call already; a caller that has
        not pays it here, before ``args`` opens, in the parent's self
        time. Returns the arguments, the context lengths (the
        accounting's) and the host's side of what the program will
        return for the next step."""
        if self.window_config is None:
            window = ()
        else:
            window = (self.advance_windows(positions, active) if window is None else window,)
        with self._part("decode", "args"):
            act = active.astype(np.int32)
            # (copies: the caller bumps its arrays in place for the next step)
            positions, counts = positions.astype(np.int32), counts.astype(np.int32)
            context_lens = np.where(active, positions + 1, 0)
            # scratch-mask inactive slots' tables too: an inactive slot with
            # a REAL table (a bisection probe deactivating a live slot)
            # would otherwise write its position-0 K/V into that slot's
            # first real block and silently corrupt the surviving stream
            tables = np.where(active[:, None], block_tables, 0).astype(np.int32)
            self.sampling_steps[sampling_branch(temps, top_ks)] += 1
            carried = [self._lookup(name, host) for name, host in zip(CARRIED, (positions, counts))]
            missed = any(isinstance(x, tuple) for x in carried)
            self.uploads["carried_misses_total" if missed else "carried_hits_total"] += 1
            staged = [
                self._lookup("decode.tables", tables),
                self._lookup("decode.active", act),
                self._lookup("decode.temps", temps.astype(np.float32)),
                self._lookup("decode.top_ks", top_ks.astype(np.int32)),
                self._lookup("decode.seeds", seeds.astype(np.uint32)),
            ]
            advanced = (positions + act, counts + act)
        with self._part("decode", "upload"):
            tokens, positions, counts = (self._upload(x) for x in (tokens, *carried))
            tables, act, temps, top_ks, seeds = (self._upload(x) for x in staged)
            bias = self._bias_arg(bias)
            mask = self._mask_arg(mask, "decode_mask", (self.max_batch_slots, self.cfg.vocab_size))
        return (
            tokens,
            positions,
            self.cache.k,
            self.cache.v,
            tables,
            act,
            temps,
            top_ks,
            bias,
            seeds,
            counts,
            mask,
            # the slots' convolution state alone (not the blocks'
            # snapshots, which no decode step touches), the window
            # layers' K/V, and the counters
            self._step_state(),
            self.expert_counts,
            *window,
        ), context_lens, advanced

    def _carry(self, advanced, positions, counts) -> None:
        """After a decode program's call: the positions and counts it
        returned become the staged entries the next step's are compared
        with, beside the host's same sums (``advanced``). (The call
        itself stays in ``decode_async``: every Python
        frame between it and the jit is one more frame in the location
        of every operation the program's first trace and lowering emit,
        and two of them cost a 24-layer program 4 s of set-up.)"""
        for name, host, dev in zip(CARRIED, advanced, (positions, counts)):
            self._staged[name] = (host, dev)

    def _step_state(self) -> Dict[str, jax.Array]:
        """What a decode step carries (and donates) beside K/V."""
        return {k: a for k, a in self.cache.state.items() if k != "snap"}

    def advance_windows(self, positions: np.ndarray, active: np.ndarray) -> Dict[str, jax.Array]:
        """Before a decode step is dispatched: every live sequence's
        window table brought to its position (blocks behind the window
        released, the block a position starts taken; span
        ``ff.cache.window_release``), and the tables as the step takes
        them: ``[slots, blocks_per_sequence]`` and each column 0's
        position. Host work only: nothing waits for the device, so the
        overlap pipeline keeps its step in flight. Once a step: the
        scheduler calls this inside its own ``sched.schedule`` span (the
        release is scheduling work, and is accounted there) and hands
        the answer to :meth:`decode_async`; a sequential :meth:`decode`
        calls it while it assembles its arguments."""
        wc = self.window_config
        positions = np.where(active, positions, 0)
        tables = np.zeros((self.max_batch_slots, self.window_columns), np.int32)
        first = np.zeros((self.max_batch_slots,), np.int32)
        full = held = 0
        with phase("cache.window_release") as release:
            for slot in np.nonzero(active)[0]:
                t = self.window_tables.get(int(slot))
                if t is None:  # a caller with tables of its own, decoding from position 0
                    t = self.window_tables[int(slot)] = WindowTable()
                self._window_advance(t, int(positions[slot]))
                tables[slot, : len(t.blocks)] = t.blocks
                first[slot] = t.first * wc.block_size
                full += int(positions[slot]) // wc.block_size + 1
                held += len(t.blocks)
                self.window_held_peak = max(self.window_held_peak, len(t.blocks))
        self._live_blocks = (full, held)
        self.window_releases_total += 1
        self.window_release_total_s += release.seconds
        return {"tables": self._stage("decode.wtables", tables), "first": self._stage("decode.wfirst", first)}

    def decode(
        self,
        tokens: np.ndarray,
        positions: np.ndarray,
        block_tables: np.ndarray,
        active: np.ndarray,
        temps: np.ndarray,
        top_ks: np.ndarray,
        seeds: np.ndarray,
        counts: np.ndarray,
        mask: Optional[np.ndarray] = None,
    ) -> np.ndarray:
        """One decode step across all ``max_batch_slots`` slots. Arrays
        are slot-indexed; inactive slots (active[i] False) write to
        scratch and return garbage tokens the scheduler ignores. After
        the call ``last_finite[i]`` says whether slot i's logits were
        finite — the supervisor's per-slot NaN blame vector.
        ``seeds``/``counts`` replace the old host-built key array: the
        per-slot sampling key derives in-jit (see :func:`derive_keys`).
        The blocking call of the one decode body: :meth:`decode_async`
        and :meth:`finish_decode`."""
        return self.finish_decode(
            self.decode_async(tokens, positions, block_tables, active, temps, top_ks, seeds, counts, mask=mask)
        )

    def finish_decode(self, step: InFlightDecode) -> np.ndarray:
        """The back half of a BLOCKING decode step, straight after its
        :meth:`decode_async`: the dispatched hook, then
        :meth:`consume_decode`. Nothing was in flight before the step,
        so its ``execute`` span is the park's (``t_started`` None:
        ``consume_decode`` takes the ``block`` span's start), and the
        whole anatomy is published here, the dispatch's spans too. (A
        method of its own so that the scheduler's sequential step, which
        is the decode call that traces, can call ``decode_async`` from
        its own frame: ONE frame more between it and the jit read 3.0-3.6
        s of set-up on the chip for a 24-layer program, PERF.md §6,
        PR 44.)"""
        self._dispatched()
        step.t_started = None
        result = self.consume_decode(step)
        self.last_step_spans[:0] = [("dispatch", step.t0, step.t_disp), step.post]
        self.last_step_children = step.children
        return result

    def _account_decode(self, n_active, ctx_sum, traced, elapsed, execute_s):
        """Post-success decode accounting, shared by the blocking and
        pipelined paths: FLOPs accrue next to the time they pair with;
        a compile call registry-stamps its wall time instead of feeding
        the truth ledger. In a span of its own
        (``ff.engine.decode.account``, a host-lane sibling after the
        readback: 30-40 us a step that no span named)."""
        with phase("engine.decode.account", into=self.last_step_spans):
            flops = self.flops_model.decode_flops(n_active, ctx_sum)
            self.flops_by_kind["decode"] += flops
            if self._n_latent:
                self.latent_tokens_held = ctx_sum
                self.latent_calls["absorbed"] += self._n_latent
            if self.slot_state is not None:
                self._ssm_slots_live = n_active
            if traced:
                self.programs.set_compile_time("decode", elapsed)
            else:
                # EXECUTED work: the fixed-shape program runs every batch
                # slot's projections/FFN (inactive rows masked to scratch,
                # but computed); only attention context is truly live-only
                b = self.max_batch_slots
                self.ledger.observe(
                    "decode",
                    self.flops_model.roofline_s(
                        self.flops_model.decode_flops(b, ctx_sum),
                        self.flops_model.decode_bytes(b, ctx_sum),
                    ),
                    execute_s,
                    label=f"decode ({self.flops_model.chip.name})",
                    provenance="serving roofline (ServingFlops x chip peak)",
                    alarm=self._roofline_alarm,
                )
                if self.serving_strategy is not None:
                    # pair the measured step against the layout-search
                    # estimate too: drift telemetry covers the DECISION
                    self.ledger.measure("serving_strategy:decode", execute_s)

    def decode_async(
        self,
        tokens: Optional[np.ndarray],
        positions: np.ndarray,
        block_tables: np.ndarray,
        active: np.ndarray,
        temps: np.ndarray,
        top_ks: np.ndarray,
        seeds: np.ndarray,
        counts: np.ndarray,
        tokens_dev: Optional[jax.Array] = None,
        mask: Optional[np.ndarray] = None,
        window: Optional[Dict[str, jax.Array]] = None,
    ) -> InFlightDecode:
        """Dispatch one decode step WITHOUT blocking on it: the front
        half of every decode step (the overlap pipeline's, and the
        blocking :meth:`decode`'s). Returns an :class:`InFlightDecode` whose
        result :meth:`consume_decode` collects one scheduler iteration
        later — the async host copy of the sampled tokens starts here,
        at dispatch-return, so the eventual readback is a wait on an
        already-moving transfer (double-buffered readback), not a fresh
        synchronous device round trip.

        ``tokens_dev`` carries the PREVIOUS step's sampled-token device
        array straight back in (device-resident staging: steady-state
        decode uploads no token array at all and XLA chains the steps
        on-device); ``tokens`` is the host token array for the
        pipeline's first step (or None in carry mode — the fault site
        still fires with the same (tokens, bias) value shape). Inactive
        slots in carry mode embed whatever garbage token the dead slot
        sampled; their writes land in scratch and their outputs are
        dropped, exactly like the host-masked path. ``window``: the
        window tables :meth:`advance_windows` made for these positions,
        from a caller that has advanced them already."""
        if tokens_dev is None:
            masked = np.where(active, tokens, 0).astype(np.int32)
        else:
            masked = None
        try:  # whatever raises from the fault site to the program's call leaves no carried entry behind
            masked, bias = faults.inject(
                faults.GENERATION_DECODE_STEP, (masked, self._zero_bias)
            )
            if self.tp_degree > 1:
                # sharded step: the cross-shard psum boundary can fail or
                # wedge like any device work — chaos plans target it here
                faults.inject(faults.GENERATION_COLLECTIVE, ("decode", self.tp_degree))
            self.step_counts["decode"] += 1
            self._count_expert_form(self.max_batch_slots)
            children = self._children = []
            with phase("engine.decode.dispatch", cpu=self.cpu_stamps) as disp:
                traces_before = self.trace_counts.get("decode", 0)
                args, context_lens, advanced = self._decode_args(
                    masked if tokens_dev is None else tokens_dev, positions, block_tables, active, temps, top_ks,
                    seeds, counts, bias, mask, window,
                )
                prev_k, prev_v, prev_conv = (None, None, None) if self.donate else (
                    self.cache.k, self.cache.v, self.cache.state.get("conv")
                )
                # (the window pool's arrays and the slots' named parts)
                named = (("wk", "wv") if self.window_config is not None else ()) + (self.slot_state.names if self.slot_state is not None else ())
                prev_window = {k: self.cache.state[k] for k in named} if named and not self.donate else None
                with self._part("decode", "call"):
                    out, ok, ck, cv, state, counts, *carried = self._decode_jit(self.params, *args)
                self._carry(advanced, *carried)
        except BaseException:
            self._drop_carried()
            raise
        with phase("engine.decode.post") as post:
            # start the device->host copies NOW; consume_decode's numpy
            # conversion then finds the bytes already resident
            out.copy_to_host_async()
            ok.copy_to_host_async()
            self.cache.update(ck, cv, **state)
            prev_counts, self.expert_counts = self.expert_counts, counts
            self.phase_time_s["decode"]["dispatch"] += disp.seconds
            self._count_dispatch(disp)
            step = InFlightDecode(
                out, ok, prev_k, prev_v, ck, cv, disp.t0, disp.t1,
                traced=self.trace_counts.get("decode", 0) > traces_before,
                n_active=int(active.sum()), ctx_sum=int(context_lens.sum()),
                prev_conv=prev_conv, prev_counts=prev_counts, prev_window=prev_window,
            )
        step.post, step.children = post.span, children
        return step

    def rollback_decode(self, step: InFlightDecode) -> None:
        """Put the cache back to what it was before ``step`` (a failed
        or voided step of a non-donating engine): K, V and the slots'
        convolution state together, or the state would be one token
        ahead of the K/V it is replayed against."""
        state = {} if step.prev_conv is None else {"conv": step.prev_conv}
        self.cache.update(step.prev_k, step.prev_v, **state, **(step.prev_window or {}))
        self.expert_counts = step.prev_counts
        self._drop_carried()  # the voided step's results, or a successor's chained on them

    def consume_decode(self, step: InFlightDecode) -> np.ndarray:
        """Block on an in-flight decode step and finish its accounting:
        the back half of every decode step. On failure the pre-step cache
        refs are restored (non-donating engines only) so the scheduler
        can re-run the step sequentially under the supervisor's normal
        retry/bisect machinery; a donating engine's failed step is
        handled by reset + journal replay instead."""
        if step.consumed:
            raise RuntimeError("InFlightDecode consumed twice")
        step.consumed = True
        with phase("engine.decode.block") as block:
            try:
                jax.block_until_ready((step.out, step.ok))
            except Exception:
                self._drop_carried()  # a donating engine's too, which rolls nothing back
                if step.prev_k is not None:
                    # roll the cache back to the pre-step refs: the
                    # failed program's outputs (and any successor
                    # chained on them) are poisoned, while the inputs
                    # are still intact. A successor's own discard must
                    # NOT restore forward over this (it checks its
                    # outputs are still current).
                    self.rollback_decode(step)
                raise
        with phase("engine.decode.readback") as read:
            self.last_finite = np.asarray(step.ok)
            result = np.asarray(step.out)  # async copy already landed
        elapsed, execute_s = self._record_lanes("decode", step, block, read)
        self._account_decode(step.n_active, step.ctx_sum, step.traced, elapsed=elapsed, execute_s=execute_s)
        return result

    def _record_lanes(self, kind: str, step: InFlightDecode, block: phase, read: phase) -> Tuple[float, float]:
        """Account a consumed step (a decode or a block step) from its
        handle's stamps and the consume's two host spans, and publish
        them for the scheduler's anatomy: what :meth:`_record_step_phases`
        is to a call that dispatches and waits in one. Returns
        (total_elapsed_s, execute_s)."""
        t_exec = block.t1
        ph = self.phase_time_s[kind]
        if step.t_started is None:
            # a blocking step's: the device's time is this park's, and what
            # lay between the dispatch and it (post, the dispatched hook)
            # is the dispatch's, so that the kind's phases stay contiguous
            # and their sum the whole call
            step.t_started = block.t0
            ph["dispatch"] += block.t0 - step.t_disp
        ph["execute"] += t_exec - step.t_started
        ph["readback"] += read.t1 - t_exec
        # two-lane spans: "execute" starts at t_started (when the device
        # actually began this step — restamped by the scheduler at the
        # previous step's completion), "block" is only the host's park
        # inside THIS call. The lanes genuinely diverge under overlap.
        self.last_step_spans = [
            block.span, ("execute", step.t_started, t_exec), read.span,
        ]
        self.last_step_children = []  # the dispatch and its parts went to the scheduler with the handle
        return read.t1 - step.t0, t_exec - step.t_started

    def _bias_arg(self, bias) -> jax.Array:
        """Device-side logit bias: the cached zeros unless a fault plan
        actually poisoned this call."""
        if bias is self._zero_bias:
            return self._zero_bias_dev
        return self._dev(np.asarray(bias, np.float32))

    def _mask_arg(self, mask, name: str, shape: Tuple[int, ...]) -> jax.Array:
        """Device-side grammar mask: with no constrained slot in the
        batch (mask None — the overwhelmingly common case) every call
        reuses one cached zeros array per shape, so unconstrained
        serving uploads nothing and the jit signature stays fixed.
        Built lazily: the [B, W, V] verify zeros never allocate unless
        speculation actually runs."""
        if mask is None:
            cached = self._zero_masks.get(name)
            if cached is None:
                cached = self._dev(np.zeros(shape, np.float32))
                self._zero_masks[name] = cached
            return cached
        return self._dev(np.asarray(mask, np.float32))

    def verify(
        self,
        window_tokens: np.ndarray,
        start: np.ndarray,
        n_draft: np.ndarray,
        block_tables: np.ndarray,
        temps: np.ndarray,
        top_ks: np.ndarray,
        seeds: np.ndarray,
        counts: np.ndarray,
        mask: Optional[np.ndarray] = None,
    ) -> Tuple[np.ndarray, np.ndarray]:
        """One speculative verification step across all slots.

        ``window_tokens`` [B, spec_window]: per slot, the last committed
        token followed by its drafts (then padding); ``start`` [B]: the
        committed token's cache position (the slot's ``cached_len``);
        ``n_draft`` [B]: real drafts per slot, -1 for inactive slots;
        ``seeds``/``counts`` [B]: per-slot sampling seed and generated-
        token count — the [B, spec_window] per-emitted-count key matrix
        derives in-jit (:func:`derive_window_keys`), deleting the host
        key-assembly phase. Returns (out_tokens [B, spec_window],
        n_emitted [B]) — the scheduler keeps
        ``out_tokens[i, :n_emitted[i]]`` (further truncated by EOS /
        budget). ONE fixed-shape jit: per-request adaptive k only
        changes ``n_draft`` values, never the shape.
        """
        self._refuse("speculation")
        window = window_tokens.astype(np.int32)
        window, bias = faults.inject(faults.GENERATION_VERIFY, (window, self._zero_bias))
        if self.tp_degree > 1:
            faults.inject(faults.GENERATION_COLLECTIVE, ("verify", self.tp_degree))
        self.step_counts["verify"] += 1
        self._count_expert_form(self.max_batch_slots * self.spec_window)
        # useful verify work: per live slot, n_draft+1 window tokens;
        # window token j at position start+j attends to start+j+1 live
        # context positions -> (nd+1)(start+1) + nd(nd+1)/2. Computed
        # BEFORE the clock starts: device_time_s is wall seconds inside
        # the step's host API call only, same as prefill/decode
        nd = np.maximum(n_draft, 0).astype(np.int64)
        live = n_draft >= 0
        w_tok = np.where(live, nd + 1, 0)
        ctx = np.where(live, w_tok * (start.astype(np.int64) + 1) + nd * (nd + 1) // 2, 0)
        self._children = []
        with phase("engine.verify.dispatch") as disp:
            traces_before = self.trace_counts.get("verify", 0)
            with self._part("verify", "args"):
                fresh = [window, start.astype(np.int32), n_draft.astype(np.int32), counts.astype(np.int32)]
                staged = [
                    self._lookup("verify.tables", block_tables.astype(np.int32)),
                    self._lookup("verify.temps", temps.astype(np.float32)),
                    self._lookup("verify.top_ks", top_ks.astype(np.int32)),
                    self._lookup("verify.seeds", seeds.astype(np.uint32)),
                ]
            with self._part("verify", "upload"):
                window, start_dev, n_draft_dev, counts_dev = (self._upload(x) for x in fresh)
                tables, temps_dev, top_ks_dev, seeds_dev = (self._upload(x) for x in staged)
                args = (
                    window, start_dev, n_draft_dev, self.cache.k, self.cache.v, tables, temps_dev, top_ks_dev,
                    self._bias_arg(bias), seeds_dev, counts_dev,
                    self._mask_arg(
                        mask, "verify_mask",
                        (self.max_batch_slots, self.spec_window, self.cfg.vocab_size),
                    ),
                )
            with self._part("verify", "call"):
                out, n_emitted, ok, ck, cv = self._verify_jit(self.params, *args)
        self._dispatched()
        with phase("engine.verify.block") as block:
            jax.block_until_ready((out, n_emitted, ok, ck, cv))  # execution done
        with phase("engine.verify.readback") as read:
            self.cache.update(ck, cv)
            self.last_finite = np.asarray(ok)
            result = (np.asarray(out), np.asarray(n_emitted))
        elapsed, execute_s = self._record_step_phases("verify", disp, block, read)
        # success-only, paired with the time below (see prefill())
        n_tok, ctx_sum = int(w_tok.sum()), int(ctx.sum())
        flops = self.flops_model.verify_flops(n_tok, ctx_sum)
        self.flops_by_kind["verify"] += flops
        if self.trace_counts.get("verify", 0) > traces_before:
            self.programs.set_compile_time("verify", elapsed)
        else:
            # EXECUTED work: all B x W window positions compute (see
            # decode) — padding only skips attention context
            bw = self.max_batch_slots * self.spec_window
            self.ledger.observe(
                "verify",
                self.flops_model.roofline_s(
                    self.flops_model.verify_flops(bw, ctx_sum),
                    self.flops_model.verify_bytes(bw, ctx_sum),
                ),
                execute_s,
                label=f"verify ({self.flops_model.chip.name})",
                provenance="serving roofline (ServingFlops x chip peak)",
                alarm=self._roofline_alarm,
            )
        return result

    def block_step(
        self,
        tokens: np.ndarray,
        fixed: np.ndarray,
        base: np.ndarray,
        forwards: np.ndarray,
        block_tables: np.ndarray,
        active: np.ndarray,
        temps: np.ndarray,
        top_ks: np.ndarray,
        seeds: np.ndarray,
        n_fix: np.ndarray,
        threshold: np.ndarray,
    ) -> Dict[str, np.ndarray]:
        """One block-diffusion step across all slots (:meth:`_block_impl`):
        the blocking call of the one block-step body, :meth:`block_async`
        and :meth:`finish_block` (as :meth:`decode` is of the decode
        step's).

        ``tokens`` / ``fixed`` [slots, B], ``base`` / ``forwards``
        [slots]: the slots' blocks as the host holds them. They are
        staged as a decode step's positions and counts are: the entries
        are what the step before RETURNED, beside the host's same
        arrays, so a step whose composition is that step's uploads
        nothing. Returns ``{"tokens", "chosen", "commit"}``, this
        forward's result: the block's tokens after it, the rows it
        fixed, the slots that ran their commit."""
        return self.finish_block(self.block_async(
            tokens, fixed, base, forwards, block_tables, active, temps, top_ks, seeds, n_fix, threshold,
        ))

    def finish_block(self, step: InFlightBlock) -> Dict[str, np.ndarray]:
        """The back half of a BLOCKING block step, straight after its
        :meth:`block_async`: :meth:`finish_decode` for a block step (a
        method of its own for the same reason: the scheduler's
        sequential step calls ``block_async`` from its own frame)."""
        self._dispatched()
        step.t_started = None
        result = self.consume_block(step)
        self.last_step_spans[:0] = [("dispatch", step.t0, step.t_disp)]
        self.last_step_children = step.children
        return result

    def block_async(
        self,
        tokens: Optional[np.ndarray],
        fixed: Optional[np.ndarray],
        base: Optional[np.ndarray],
        forwards: Optional[np.ndarray],
        block_tables: np.ndarray,
        active: np.ndarray,
        temps: np.ndarray,
        top_ks: np.ndarray,
        seeds: np.ndarray,
        n_fix: np.ndarray,
        threshold: np.ndarray,
        prev: Optional[InFlightBlock] = None,
    ) -> InFlightBlock:
        """Dispatch one block step WITHOUT blocking on it: the front
        half of every block step (the overlap pipeline's, and the
        blocking :meth:`block_step`'s). Returns an :class:`InFlightBlock`
        whose result :meth:`consume_block` collects one scheduler
        iteration later; the four device-to-host copies of the result
        start here.

        ``prev``: the unconsumed step this one follows. Its program's
        last four results (``next_dev``) ARE this step's blocks, flags,
        bases and forward counts, and they go in as the device arrays
        they are, whatever ``active`` is now (a slot left out keeps its
        rows: ``_block_impl``'s ``keep``): no host array is compared and
        none of the state is uploaded, so the four state arguments are
        not read (None). The host cannot know them: what ``prev`` fixed
        is on the device alone until it is consumed. Tables, ``active``
        and the sampling vectors are staged as ever."""
        d = self.diffusion
        if d is None:
            raise NotImplementedError("block_step is a block-diffusion engine's step (diffusion=None)")
        if prev is None:
            tokens = np.where(active[:, None], tokens, 0).astype(np.int32)
        try:  # whatever raises from the fault site to the program's call leaves no carried entry behind
            tokens, bias = faults.inject(faults.GENERATION_DECODE_STEP, (tokens, self._zero_bias))
            self.step_counts["block_step"] += 1
            self._count_expert_form(self.max_batch_slots * d.block_length)
            children = self._children = []
            with phase("engine.block_step.dispatch") as disp:
                traces_before = self.trace_counts.get("block_step", 0)
                with self._part("block_step", "args"):
                    act = active.astype(np.int32)
                    if prev is None:
                        pre = (tokens, np.where(active[:, None], fixed, 0).astype(np.int32),
                               np.where(active, base, 0).astype(np.int32), np.where(active, forwards, 0).astype(np.int32))
                        carried = [self._lookup(name, host) for name, host in zip(BLOCK_CARRIED, pre)]
                        missed = any(isinstance(x, tuple) for x in carried)
                    else:
                        pre, carried, missed = None, prev.next_dev, False
                    self.uploads["carried_misses_total" if missed else "carried_hits_total"] += 1
                    self.sampling_steps[sampling_branch(temps, top_ks)] += 1
                    staged = [
                        self._lookup("block.tables", np.where(active[:, None], block_tables, 0).astype(np.int32)),
                        self._lookup("block.active", act),
                        self._lookup("block.temps", temps.astype(np.float32)),
                        self._lookup("block.top_ks", top_ks.astype(np.int32)),
                        self._lookup("block.seeds", seeds.astype(np.uint32)),
                        self._lookup("block.n_fix", n_fix.astype(np.int32)),
                        self._lookup("block.threshold", threshold.astype(np.float32)),
                    ]
                with self._part("block_step", "upload"):
                    dev_state = [self._upload(x) for x in carried]
                    tables, *rest = (self._upload(x) for x in staged)
                    args = (*dev_state, self.cache.k, self.cache.v, tables, *rest, self._bias_arg(bias), self.expert_counts)
                prev_k, prev_v = (None, None) if self.donate else (self.cache.k, self.cache.v)
                with self._part("block_step", "call"):
                    *results, ck, cv, counts, next_tokens, next_fixed, next_base, next_forwards = self._block_jit(
                        self.params, *args
                    )
                for result in results:
                    # the copies start now: the readback finds the bytes on the host (four small
                    # transfers one after another read 1.6 ms a step on the chip: PERF.md §6, PR 45)
                    result.copy_to_host_async()
                self.cache.update(ck, cv)
                prev_counts, self.expert_counts = self.expert_counts, counts
        except BaseException:
            self._drop_carried()
            raise
        self.phase_time_s["block_step"]["dispatch"] += disp.seconds
        step = InFlightBlock(
            results, (next_tokens, next_fixed, next_base, next_forwards), pre, prev, act,
            prev_k=prev_k, prev_v=prev_v, ck=ck, cv=cv, t0=disp.t0, t_disp=disp.t1,
            traced=self.trace_counts.get("block_step", 0) > traces_before, prev_counts=prev_counts,
        )
        step.children = children
        return step

    def consume_block(self, step: InFlightBlock, running: Optional[np.ndarray] = None) -> Dict[str, np.ndarray]:
        """Block on an in-flight block step and finish its accounting:
        the back half of every block step. A failed step's cache is
        rolled back as a decode step's is (:meth:`consume_decode`).

        ``running`` [slots] bool: the slots of the step whose requests
        are still running now (None: all it ran). A step dispatched
        behind an unconsumed one ran every slot that one ran, and a
        request may have ENDED on what that one fixed (its
        end-of-sequence token): its rows in this step are work a
        sequential loop never does, so the rule's counters
        (``diffusion_counts``, the histogram, the useful FLOPs) leave
        them out and stay what the sequential loop's are. The state
        carried to the next step is the whole program's, whoever
        counts."""
        if step.consumed:
            raise RuntimeError("InFlightBlock consumed twice")
        step.consumed = True
        d = self.diffusion
        with phase("engine.block_step.block") as block:
            try:
                jax.block_until_ready((step.out, step.chosen, step.commit, step.ok))
            except Exception:
                self._drop_carried()  # a donating engine's too, which rolls nothing back
                if step.prev_k is not None:
                    self.rollback_decode(step)  # (see consume_decode)
                raise
        with phase("engine.block_step.readback") as read:
            self.last_finite = np.asarray(step.ok)
            out, chosen, commit = np.asarray(step.out), np.asarray(step.chosen), np.asarray(step.commit)
        elapsed, execute_s = self._record_lanes("block_step", step, block, read)
        with phase("engine.block_step.account", into=self.last_step_spans):
            # the host's side of the state the device now holds (the program's last four results), from
            # the state the forward ran on: what the host staged, or what the step before it left
            pre, act = step.pre if step.prev is None else step.prev.nxt, step.active
            step.prev = None  # (a chain of consumed steps is not kept alive through it)
            step.nxt = (
                np.where(commit[:, None], 0, out).astype(np.int32),
                np.where(commit[:, None], 0, pre[1] | chosen).astype(np.int32),
                (pre[2] + d.block_length * commit).astype(np.int32), np.where(commit, 0, pre[3] + act).astype(np.int32),
            )
            for name, host, dev in zip(BLOCK_CARRIED, step.nxt, step.next_dev):
                self._staged[name] = (host, dev)
            counted = act > 0 if running is None else np.logical_and(act > 0, running)
            n_active, n_commit = int(counted.sum()), int(commit[counted].sum())
            c = self.diffusion_counts
            c["slot_forwards_total"] += n_active
            c["commit_forwards_total"] += n_commit
            c["blocks_committed_total"] += n_commit
            c["tokens_fixed_total"] += int(chosen[counted].sum())
            self.fixed_histogram += np.bincount(
                chosen.sum(axis=1)[counted & ~commit], minlength=d.block_length + 1
            )
            # success-only, paired with the time below (see prefill()): every live row attends its block's end
            ctx_sum = int(((pre[2] + d.block_length) * counted).sum())
            self.flops_by_kind["block_step"] += self.flops_model.block_flops(n_active, ctx_sum, d.block_length)
            if step.traced:
                self.programs.set_compile_time("block_step", elapsed)
            else:
                b = self.max_batch_slots  # EXECUTED work: every slot's rows compute
                self.ledger.observe(
                    "block_step",
                    self.flops_model.roofline_s(
                        self.flops_model.block_flops(b, ctx_sum, d.block_length),
                        self.flops_model.block_bytes(b, ctx_sum, d.block_length),
                    ),
                    execute_s, label=f"block_step ({self.flops_model.chip.name})",
                    provenance="serving roofline (ServingFlops x chip peak)", alarm=self._roofline_alarm,
                )
        return {"tokens": out, "chosen": chosen, "commit": commit}

    def diffusion_stats(self) -> Dict:
        """The ``diffusion`` section of ``/v2/stats`` (a block-diffusion
        engine's): forwards by slot (a step is one forward of every live
        slot), those that were commits, the tokens fixed, the blocks
        committed, the denoising forwards by the tokens they fixed
        (``fixed_per_forward_histogram[k]``, k = 0 .. block_length), and
        the rule's defaults."""
        d = self.diffusion
        return {
            **self.diffusion_counts,
            "fixed_per_forward_histogram": [int(n) for n in self.fixed_histogram],
            "block_length": d.block_length, "denoising_steps": d.denoising_steps, "remasking": d.remasking,
            "threshold": d.threshold, "mask_token_id": d.mask_token_id,
        }

    def generate(
        self,
        prompts: Sequence[Sequence[int]],
        sampling: Optional[SamplingParams] = None,
        speculation=None,
        **scheduler_kwargs,
    ) -> List[List[int]]:
        """Convenience: run ``prompts`` through a private continuous-
        batching scheduler to completion; returns generated tokens per
        prompt (prompt excluded). ``speculation``: a SpeculationConfig
        to decode speculatively (exact — greedy output is identical)."""
        from .scheduler import ContinuousBatchingScheduler

        sampling = sampling or SamplingParams()
        sched = ContinuousBatchingScheduler(self, **scheduler_kwargs)
        handles = [sched.submit(list(p), sampling, speculation=speculation) for p in prompts]
        while any(not h.done() for h in handles):
            if not sched.step():
                break
        return [h.result(timeout=0) for h in handles]

    def expert_stats(self) -> Dict:
        """The ``experts`` section of ``/v2/stats``: cumulative tokens
        handed to every expert (per expert layer, and summed over the
        layers), and the calls that handed them. This is the ONE place
        the device's counters are read back (a few KB, and a wait for
        the step in flight): a scrape pays for it, a step never does."""
        tokens = np.asarray(self.expert_counts["tokens"]).astype(np.int64)
        calls = np.asarray(self.expert_counts["calls"])
        extra: Dict = {}
        if self.dcfg.zero_experts:
            # identity experts: behind the experts' columns, the picks that went to one and the tokens
            # by how many REAL experts they picked (0 .. k), per layer and summed
            k = self.dcfg.experts_per_token
            zero, real, tokens = tokens[:, -k - 2], tokens[:, -k - 1:], tokens[:, : -k - 2]
            picks = int(real.sum()) * k
            extra = {
                "zero_experts": int(self.dcfg.zero_experts), "zero_picks_total": int(zero.sum()),
                "zero_picks_total_by_layer": [int(z) for z in zero],
                "zero_pick_share": float(zero.sum()) / picks if picks else 0.0,
                "real_experts_per_token_total": [int(n) for n in real.sum(axis=0)],
            }
        if self.dcfg.experts_held:
            # a share of the experts: `tokens_total` lists the held ones
            # (in the order `held` names them), and the last column the
            # tokens none of the held was chosen for, counted once a layer
            extra.update(held=[int(i) for i in self.dcfg.experts_held], unrouted_here_total=int(tokens[:, -1].sum()))
            tokens = tokens[:, :-1]
        return {
            **extra,
            "layers": [int(l) for l in self.dcfg.expert_layers],
            "experts": int(self.dcfg.num_experts),
            "experts_per_token": int(self.dcfg.experts_per_token),
            "tokens_total": [int(t) for t in tokens.sum(axis=0)],
            "tokens_total_by_layer": [[int(t) for t in row] for row in tokens],
            "decode_calls_total": int(calls[0]),
            "prefill_calls_total": int(calls[1]),
            "grouped_calls_total": self.expert_grouped_calls,
            "forms": self.expert_lowerings(),
        }

    def expert_form(self, rows: int) -> str:
        """``"dense"`` or ``"grouped"``: the lowering the expert layers
        of a step program over ``rows`` rows take, asked of the rule
        ``decoder.expert_ffn`` asks (ops/expert_product.py)."""
        if rows not in self._expert_forms:
            held = self.params["layers"][self.dcfg.expert_layers[0]]["ew1"].shape[0]
            self._expert_forms[rows] = expert_lowering(rows, held, self.dcfg.experts_per_token, self.dcfg.router_outputs)
        return self._expert_forms[rows]

    def _count_expert_form(self, rows: int) -> None:
        if self.expert_counts and self.expert_form(rows) == "grouped":
            self.expert_grouped_calls += 1

    def expert_lowerings(self) -> Dict[str, str]:
        """The form per step program of a model with expert layers, as
        :meth:`paged_lowerings` reports a kernel's body: the decode step
        (its rows are the slots) and every ``prefill[N]`` bucket (a
        suffix prefill of ``N`` rows takes the same form)."""
        programs = {"decode": self.max_batch_slots, **{f"prefill[{b}]": b for b in self.buckets}}
        if self.diffusion is not None:  # (its step program, in the decode step's place)
            programs = {"block_step": self.max_batch_slots * self.diffusion.block_length, **programs}
            del programs["decode"]
        return {name: self.expert_form(rows) for name, rows in programs.items()}

    def paged_lowerings(self) -> Dict[str, Dict]:
        """``{"body", "group"}`` and, where a kernel runs, its walk
        (``columns_per_step``, ``grid_steps``, ``walk_steps_at_most``) per
        attention kind the model has (``full``, ``window``; ``latent``
        alone where the layers are latent) for the decode step's call
        (every slot over the kind's whole table), asked of the gate and
        the rules the step programs' dispatch asks (ops/attention.py
        ``paged_call_lowering``)."""
        shape = {"batch": self.max_batch_slots, "max_blocks": self.max_blocks_per_seq}
        if self.cache_config.latent:
            return {"latent": latent_call_lowering(self.dcfg.num_heads, self.cache.k, backend=self.backend, **shape)}
        kinds = {"full": (self.cache.k, shape)}
        if self.window_config is not None:
            kinds["window"] = (self.cache.state["wk"], {**shape, "max_blocks": self.window_columns})
        if self.diffusion is not None:
            # the one paged call such an engine's steps make: a block's rows, W = block_length
            # window queries a sequence (x the group), under the name of its step program
            kinds = {"block_step": (self.cache.k, {**shape, "window": self.diffusion.block_length})}
        return {
            kind: paged_call_lowering(
                self.dcfg.attend_heads, self.dcfg.cache_head_dim, arrays,
                backend=self.backend, mesh=self._kernel_mesh, **of,
            )
            for kind, (arrays, of) in kinds.items() if arrays.shape[0]
        }

    def kernel_stats(self) -> Dict:
        """The ``kernels`` section of ``/v2/stats``: per attention kind of
        the loaded model (``full``, ``window``), the body its paged decode
        call lowered to — ``mxu`` (a grouped call), ``vpu`` (plain
        multi-head) or ``reference`` (the XLA composition: the CPU
        backend, or a shape the kernel's gate refused) — the group the
        shapes show, and the walk over the block table that call runs:
        the table columns a step folds, the grid steps a call and the
        steps the walk takes at most (absent for the composition). (What a prefill's attention calls lower to is in
        the ``prefill_attention`` section, per program.)"""
        return {kind: dict(low) for kind, low in self.attention_kernels.items()}

    def prefill_lowering(self, bucket: int) -> Dict:
        """What the attention calls of ``prefill[bucket]`` lower to, asked
        of the rule the program's dispatch asks (ops/attention.py
        ``prefill_call_lowering``)."""
        if bucket not in self._prefill_lowerings:
            d = self.dcfg
            # (a latent layer's expanded form: every head has K of its own, at the score's width, and V at its own)
            heads, width, value = (
                (d.num_heads, d.qk_nope_head_dim + d.qk_rope_head_dim, d.v_head_dim) if self._n_latent
                else (d.cache_kv_heads, d.cache_head_dim, d.cache_head_dim)
            )
            self._prefill_lowerings[bucket] = prefill_call_lowering(
                (1, bucket, d.num_heads if self._n_latent else d.attend_heads, width), (1, bucket, heads, width), d.dtype.size_bytes,
                backend=self.backend, v_shape=(1, bucket, heads, value), block=d.block_mask,
            )
        return self._prefill_lowerings[bucket]

    def _count_prefill_attention(self, bucket: int, tokens: int) -> None:
        n = len(self.dcfg.attention_layers)
        if not n:
            return
        low, calls = self.prefill_lowering(bucket), self.prefill_attention_calls
        calls["tokens"] += tokens
        calls["calls"] += n
        calls["rows"] += n * bucket
        calls[low["form"]] += n
        if low["refused"]:
            reason = f"prefill_stream_attention: {low['refused']}"
            self.prefill_attention_refused[reason] = self.prefill_attention_refused.get(reason, 0) + n

    def prefill_attention_stats(self) -> Dict:
        """The ``prefill_attention`` section of ``/v2/stats``: the
        attention calls whole-prompt prefills made (one a layer with K/V
        of heads or a latent row), the query rows they scored, how many took the streamed
        form and how many materialised their scores, the prompt tokens
        those prefills took, what was refused, by reason, and why prefix
        reuse is off where it is (``unsupported["prefix_reuse"]``)."""
        c = self.prefill_attention_calls
        return {
            "tokens_total": c["tokens"], "calls_total": c["calls"], "rows_total": c["rows"], "streamed_calls_total": c["streamed"],
            "materialised_calls_total": c["materialised"], "score_bytes_bound": STREAM_SCORE_BYTES,
            "refused_total": dict(self.prefill_attention_refused),
            # decided at construction: why no prefix hit is taken (None where hits are)
            "prefix_reuse_refused": self.unsupported.get("prefix_reuse"),
            # per ``prefill[N]`` program the form and the kernel of its calls (a latent
            # layer's: of its expanded form, scored at qk_nope + qk_rope)
            "programs": {f"prefill[{b}]": dict(self.prefill_lowering(b)) for b in self.buckets}
            if self.dcfg.attention_layers else {},
        }

    def sampling_stats(self) -> Dict:
        """The ``sampling`` section of ``/v2/stats``: decode steps by the
        branch of the sampling transform they ran; the three add up to
        ``step_counts["decode"]``."""
        return {f"{branch}_steps_total": n for branch, n in self.sampling_steps.items()}

    def conv_state_stats(self) -> Dict:
        """The ``conv_state`` section of ``/v2/stats``."""
        sc = self.state_config
        return {
            "layers": sc.num_layers,
            "bytes_per_sequence": sc.bytes_per_sequence,
            "bytes": sc.total_bytes(self.cache_config.num_blocks),
            "restores_total": self.state_restores_total,
            "snapshots_total": self.state_snapshots_total,
        }

    def recompiles(self) -> Dict[str, int]:
        """Retraces beyond the first compile, per program."""
        return {k: v - 1 for k, v in self.trace_counts.items() if v > 1}

    def total_flops(self) -> float:
        """Cumulative useful model FLOPs across all step kinds."""
        return sum(self.flops_by_kind.values())

    @property
    def device_time_s(self) -> Dict[str, float]:
        """The pre-split total per kind, derived: dispatch + execute +
        readback — the same wall seconds the old conflated timer
        measured, kept for the flight/stats series' continuity."""
        return {k: sum(v.values()) for k, v in self.phase_time_s.items()}

    def total_device_time_s(self) -> float:
        return sum(self.device_time_s.values())

    def total_execute_time_s(self) -> float:
        """Cumulative device-EXECUTE seconds (dispatch-return to
        block_until_ready) — the MFU denominator after the ISSUE 12
        split; host arg prep and dispatch overhead no longer count as
        device time."""
        return sum(v["execute"] for v in self.phase_time_s.values())

    def mfu(self) -> float:
        """Serving model-FLOPs utilization: useful FLOPs over device
        EXECUTE seconds against the chip's peak for the cache dtype
        (definition changed by ISSUE 12 — previously the denominator
        included host arg prep, XLA dispatch, and readback; see README
        "Step anatomy" for the CPU-backend caveat). 0 before any step
        ran."""
        t = self.total_execute_time_s()
        if t <= 0:
            return 0.0
        return self.total_flops() / t / self.flops_model.peak_flops
