"""Decoder-only language model for the generation engine: a pure-JAX
params pytree, ONE block definition driven by data, and four forward
modes over it that provably agree.

**What a layer is comes from the configuration** (:class:`DecoderConfig`,
a :class:`~flexflow_tpu.models.transformer.TransformerConfig` with the
block's choices added; a plain ``TransformerConfig`` is the GPT-2
setting of every one of them):

* norm: ``layernorm`` (weight and bias), ``layernorm_nobias`` (the mean
  subtracted, a weight and no bias) or ``rmsnorm`` (weight only);
* block: ``sequential`` — ``h = x + Op(norm1(x))``, ``y = h +
  FFN(norm2(h))`` — or ``parallel``: ONE norm a layer, which the
  operator and the feed-forward both read, and both add into the
  residual: ``n = norm1(x)``, ``y = x + Op(n) + FFN(n)`` (such a layer
  has no second norm); or ``single``: ONE norm and ONE branch a layer,
  ``y = x + Mixer(norm1(x))``, the mixer the layer's operator or, for the
  layer type ``ffn``, its feed-forward alone (no layer has both);
* positions: ``learned`` (an absolute table added at the embedding) or
  ``rotary`` (on q and k inside attention, nothing at the embedding:
  rotate-half, or with ``rope_interleave`` over the pairs ``(2i, 2i+1)``;
  an attention kind whose ``rope_parameters`` say ``"positions": "none"``
  is not rotated at all: such a layer has no positional signal but the
  causal mask);
* operator per layer (``layer_types``): ``attention`` — causal softmax
  attention, optionally grouped (``num_kv_heads`` K/V heads, query head
  ``i`` reading K/V head ``i // group``) and with a per-head RMSNorm on
  q and k (``qk_norm``) — ``window``, the same attention over the
  ``window`` positions up to the query's own and no further back (its
  K/V lives in arrays and tables of its own, which hold what the window
  can still reach: generation/cache.py), with rotary parameters of its
  own kind (``rope_parameters``: a theta, and for YaRN the scaled
  frequencies and the factor on cos and sin) — ``latent``, attention
  whose K and V come from ONE low-rank row a token shared by all heads
  (below) — or ``conv``, a gated short convolution:
  (or ``mamba``, ``gmu``, ``cross``: a decoder-hybrid-decoder's kinds,
  below; or ``ssm``, a Mamba-2 state-space mixer: :func:`_ssm` has its
  equations; its per-sequence state is the convolution's last ``K - 1``
  input rows and a float32 recurrent state ``[H, P, N]``, kept per batch
  slot and nowhere else: ops/ssm.py, generation/cache.py) — the gated
  short convolution is
  ``[B, C, X] = split3(W_in u)``, ``z_t = B_t * X_t``, ``c_t = sum_j
  w[:, j] * z_{t-K+1+j}`` (depthwise, causal, kernel ``K``, zeros before
  the sequence), ``out = W_out (C_t * c_t)``. Its state after position
  ``t`` is the last ``K - 1`` rows of ``z``;
* feed-forward per layer: ``gelu`` (two matrices with biases), ``swiglu``
  (``W2 (silu(W1 v) * W3 v)``) for the first ``num_dense_layers``, and
  routed experts after them (:func:`expert_ffn`: a ``sigmoid`` router
  with a selection bias used for the choice only, or a ``softmax`` one
  without; top-k, renormalised gates, SwiGLU experts, no capacity and
  no dropped token), beside them ``num_shared_experts`` experts every
  token goes through (one SwiGLU of their summed width, added to the
  routed sum; ``shared_experts: "average"`` scales it by ``1 /
  num_shared_experts``: the mean of their outputs);
  ``expert_activation: "relu2"``: the experts and the shared expert are
  UNGATED, ``W2 relu(W1 v)^2`` (two matrices); ``moe_latent_size`` > 0:
  the routed experts read ``W_down v`` and ``W_up`` takes their gated sum
  back to the hidden size (the router and the shared expert read ``v``);
  ``router_selection_bias=False`` is a sigmoid router whose choice is
  ``top_k(s)`` and whose gates are ``s_i / sum_{j in I} s_j``.
  ``experts_held`` names the routed experts whose weights
  THIS engine holds (one chip's share of an expert-sharded deployment:
  the router scores all of them, the sum runs over the held ones, and
  nothing stands in for the absent chips' part). A softmax router may
  have a selection bias too (``router_softmax_bias``), gates that are
  NOT renormalised (``router_renormalise`` False: ``g_i = s_i x
  routed_scaling_factor``), and behind its ``num_experts`` outputs
  ``zero_experts`` more that are identity experts: a pick of one adds
  ``g_i x v``, the experts' own input, and multiplies nothing (they hold
  no weights, so under a share every chip computes their term whole);
* a routed branch on a SHORTCUT (``shortcut_experts`` = the period P, in
  layers): every layer has its dense feed-forward, and every P-th, from
  the first, also routes that feed-forward's normed input ``u`` through
  the experts; the branch's sum ``R`` is carried past the next ``P - 1``
  layers and lands in the residual behind the LAST feed-forward of the
  period: ``x_l = h_l + D_l(u_l)``, ..., ``x_{l+P-1} = h_{l+P-1} +
  D_{l+P-1}(u_{l+P-1}) + R(u_l)``. With P = 2 and latent layers this is a
  published layer of two attentions and two dense feed-forwards whose
  experts hide their exchange behind the second pair. The stack counts
  SUB-layers: the cache, ``kv_index`` and the engine see ``num_layers``
  ordinary layers;
* dtype: the weights' own. Every matmul accumulates in float32 and
  hands its result on in the activations' type; norms, softmax, the
  rotary angles and the router are computed in float32.

A ``sequential`` layer is ``h = x + Op(norm(x))``, ``y = h +
FFN(norm(h))``; a ``parallel`` one ``y = x + Op(n) + FFN(n)``, ``n =
norm(x)``.

**A prefill's attention** goes through ops/attention.py
``prefill_attention``: the composition that materialises ``[B, H, S, S]``
float32 scores while those stay under 1 GiB a call, and past that the
streamed form (K/V blocks folded into a running softmax, never a score
matrix of the sequence's square), which on a TPU is a Pallas call of its
own name (``prefill_stream_attention``: a group of whole tiles of query
heads a K/V head, or every head with K/V of its own as a latent layer's
expanded form has them, the score's width beside the value's).

**A latent layer** (multi-head latent attention). With ``h`` the normed
input: ``c_q = RMSNorm(h W_DQ)``, ``q = c_q W_UQ`` -> per head ``[q_nope
(qk_nope_head_dim), q_rope (qk_rope_head_dim)]``; ``[c_kv (kv_lora_rank),
k_r (qk_rope_head_dim)] = h W_DKV``, ``c = RMSNorm(c_kv)``; ``q_rope``
and the ONE ``k_r`` all heads share rotated over interleaved pairs
``(2i, 2i+1)``; ``[k_nope_i, v_i] = c W_UKV`` per head
(``latent_q_scale`` multiplies ``q`` behind ``W_UQ`` and
``latent_kv_scale`` the normed ``c``, before ``W_UKV`` and before the
cache: the row holds the scaled ``c``, and ``k_r`` is not scaled). The cache holds
a position's ``[c, k_r]`` (after the norm and the rotation), one row of
``kv_lora_rank + qk_rope_head_dim`` values stored at the next multiple of
128 lanes (generation/cache.py), and the layer has TWO forms that are
equal in exact arithmetic:

* *expanded* (``prefill``): K and V are expanded per head out of the
  rows, ``s_i(t, j) = (q_nope_i(t) . k_nope_i(j) + q_rope_i(t) . k_r(j))
  / sqrt(qk_nope + qk_rope)``, through ``prefill_attention`` as any
  prefill's attention goes (score width and value width differ; past the
  score bound it streams, and no ``[H, S, S]`` value exists);
* *absorbed* (``decode_step``, ``verify_step`` and with it the suffix
  prefill behind a prefix hit): ``W_UK`` goes into the query, ``q~_i =
  q_nope_i W_UK_i^T``, the scores are ``(q~_i . c(j) + q_rope_i . k_r(j))``
  straight over the cached rows, the values are the rows' first
  ``kv_lora_rank`` columns, and ``W_UV`` goes onto the result, ``o_i =
  (sum_j p_i(t, j) c(j)) W_UV_i``. ``W_UK`` / ``W_UV`` are the two halves
  of the stored ``W_UKV``, sliced where they are used.

**A decoder-hybrid-decoder** (SambaY: ``mamba``, ``window``, ONE
``attention`` layer, then ``gmu`` and ``cross`` layers; every layer
``sequential`` with its own feed-forward). With ``h`` the layer's normed
input:

* ``mamba``, a Mamba-1 mixer (``D = mamba_expand x E``, ``N =
  ssm_state_size``, ``R = dt_rank``): ``[x, z] = W_in h``; ``x <-
  silu(conv1d_causal(x) + b)`` (depthwise, kernel K, zeros before the
  sequence); ``[delta, B, C] = W_x x`` (D -> R + 2N); ``dt = softplus(W_dt
  delta + b_dt)`` [D]; ``A = -exp(A_log)`` [D, N]; in float32
  ``S_t[d, n] = exp(dt_t[d] A[d, n]) S_{t-1}[d, n] + dt_t[d] B_t[n] x_t[d]``,
  ``y_t[d] = sum_n C_t[n] S_t[d, n] + D[d] x_t[d]``; ``out = W_out (y_t *
  silu(z_t))``. A decay for every (channel, state) pair and no heads: the
  three forms are ops/ssm.py ``selective_*``. Its state per sequence is the
  convolution's last ``K - 1`` rows of ``x`` and ``S`` stored ``[N, D]``
  float32, per batch slot and nowhere else. The layer ``memory_source``
  also hands on ``m_t = y_t`` (before the gate): the MEMORY, carried to the
  ``gmu`` layers inside the one forward and never cached.
* ``gmu``, a Gated Memory Unit: ``out = W_2 (m_t * silu(W_1 h))``, ``m_t``
  the memory at the same row. It keeps nothing.
* ``cross``: attention that has ``W_q`` and ``W_o`` alone and reads the K/V
  that layer ``kv_source`` stores (a ``cross`` layer ATTENDS and has no K/V
  of its own: ``attention_layers`` against ``kv_layers``; ``kv_index`` names
  the producer's place in the arrays). It writes nothing.
* ``differential`` (every attending layer): query pair ``i`` is ``q1_i =
  q[2i]``, ``q2_i = q[2i + 1]``; K/V pair ``j`` is ``k1_j = k[2j]``, ``k2_j =
  k[2j + 1]``, ``V_j = [v[2j] ‖ v[2j + 1]]``; query pair ``i`` reads K/V pair
  ``i // (H / Hkv)``. ``a1_i = softmax(q1_i k1_j^T / sqrt(D) + mask) V_j``,
  ``a2_i`` of ``q2_i``, ``k2_j``; ``lambda = exp(lq1 . lk1) - exp(lq2 . lk2)
  + lambda_init``, ``lambda_init = 0.8 - 0.6 exp(-0.3 l)``; ``o_i = (1 -
  lambda_init) RMSNorm_2D(a1_i - lambda a2_i; g)``, the ``H / 2`` x ``2D``
  read as ``H`` x ``D`` into ``W_o`` (biases on the four projections with
  ``attention_bias``). It runs through the attention calls AS THEY STAND, in
  a padded-query form: a layer's K/V is stored as ``Hkv / 2`` heads of ``2D``
  (``[k1_j ‖ k2_j]``, ``[v1_j ‖ v2_j]``: the projection's own row-major order)
  and pair ``j`` gets the query rows ``[q1 ‖ 0]`` and ``[0 ‖ q2]`` of each of
  its query pairs, times ``sqrt(2)`` so that a call's own ``1 / sqrt(2D)``
  comes to ``1 / sqrt(D)``; prefill, the window call and the full call then
  compute ``a1`` and ``a2`` with K/V read once, and lambda and the pair norm
  are an elementwise epilogue (:func:`_diff_qkv`, :func:`_diff_out`). The
  stored pairs are padded to a count of rows a block of the cache can be
  copied by (``cache_kv_heads``: 10 pairs are stored as 16 rows, 6 zero).
* a prefill (``last_only``) runs the layers from ``cross_from`` on, and the
  head, on each sequence's LAST row alone: they store nothing a later
  position reads.

**Block diffusion** (``block_mask = B`` > 0). The mask is not causal:
position ``i`` attends position ``j`` iff ``j // B <= i // B`` — every
earlier block, and the whole of its own, later rows included — and the
logits at position ``i`` are the distribution of the token AT ``i`` (a
masked position predicts itself; nothing is shifted). ``forward_full``
and ``prefill`` run under that mask; the step is :func:`block_step`, a
:func:`verify_step` whose ``B`` rows a sequence are rotated at their
own positions and all attend up to the block's last one.

Four forwards over one params pytree, all through :func:`_layers`:

* :func:`forward_full` — full-context causal forward, [B, S] -> logits
  [B, S, V]. The parity oracle.
* :func:`prefill` — forward_full that also returns every ATTENTION
  layer's K/V ([n_attn, B, S, Hkv, D]) for the engine to scatter into
  the block cache and every CONVOLUTION layer's padded ``z`` rows
  ([n_conv, B, S + K - 1, E]: the engine takes each sequence's state at
  its own length, and each block's at the block's end, out of them),
  with per-sequence length masking so padded prompt buckets match the
  unpadded forward.
* :func:`decode_step` — one token per sequence against the cache
  (writes the token's K/V, then decode-mode attention; reads, shifts
  and writes the convolution state in place), [B] -> logits [B, V].
* :func:`verify_step` — a W-token append window per sequence against
  the cache, [B, W] -> logits [B, W, V]: the speculative-verification
  forward (attention-only configurations) and the suffix prefill behind
  a prefix hit (any configuration: the window continues from the
  convolution state its slots hold). With ``attend_positions`` the
  rows' attend bound is given apart from their own positions:
  :func:`block_step`, a block-diffusion model's step, is that call.

``forward_full(tokens)[b, i] == decode logits after caching tokens[:i]``
within fp32 tolerance — asserted by tests/test_generation.py and
tests/test_lfm2.py; ``verify_step`` agrees with ``decode_step``
token-for-token — asserted by tests/test_speculative.py.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from ..core.types import DataType
from ..models.transformer import TransformerConfig
from ..ops.attention import (
    append_attention_core, decode_attention_core, latent_attention_core, masked_attention, prefill_attention,
)
from ..ops import ssm as ssm_ops
from ..ops.expert_product import expert_lowering, grouped_expert_sum
from ..ops.kernels.decode_attention import latent_row_width
from .cache import slot_mapping

# a decoder is a plain pytree: jit-friendly, checkpoint-friendly
DecoderParams = Dict[str, Any]


@dataclasses.dataclass
class DecoderConfig(TransformerConfig):
    """The block's choices, as data (module docstring). The defaults are
    GPT-2's, so ``DecoderConfig(**asdict(TransformerConfig(...)))`` is
    the decoder the engine has always served."""

    norm: str = "layernorm"  # | "layernorm_nobias" | "rmsnorm"
    norm_eps: float = 1e-5
    # | "parallel": one norm a layer, operator and feed-forward both read it
    # | "single": one norm and ONE branch a layer, the operator or (layer type "ffn") the feed-forward
    block: str = "sequential"
    positions: str = "learned"  # | "rotary"
    rope_theta: float = 10000.0
    qk_norm: bool = False
    num_kv_heads: int = 0  # 0: as many as query heads
    head_dim: int = 0  # 0: hidden_size // num_heads
    # per layer "attention" | "window" | "latent" | "conv" | "ssm", or in a "single" block "ffn" (a layer that
    # is its feed-forward alone); (): all attention
    layer_types: Tuple[str, ...] = ()
    window: int = 0  # positions a "window" layer's query attends, its own included
    # rotary parameters by attention kind ("attention" / "window"); a kind
    # without an entry has plain `rope_theta`. Keys: "theta" and, for YaRN,
    # "factor", "original_max_position_embeddings", "beta_fast",
    # "beta_slow", "attention_factor" (:func:`_rope`); or "positions":
    # "none", a kind whose q and k are not rotated
    rope_parameters: Dict[str, Dict[str, Any]] = dataclasses.field(default_factory=dict)
    conv_kernel: int = 3
    ffn: str = "gelu"  # the dense feed-forward: "gelu" | "swiglu"
    num_dense_layers: int = -1  # layers from here on are routed experts; -1: none is
    num_experts: int = 0
    experts_per_token: int = 0
    moe_ff_size: int = 0
    routed_scaling_factor: float = 1.0
    router: str = "sigmoid"  # | "softmax" (no selection bias)
    router_selection_bias: bool = True  # a sigmoid router's; False: top_k(s), gates s_i / sum s_j
    tied_head: bool = False  # logits = x E^T, no output matrix of its own
    # a "latent" layer (module docstring): the query's bottleneck, the
    # cached row's low-rank part, the score's unrotated and rotated
    # widths and the value's width a head
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    rope_interleave: bool = False  # rotate pairs (2i, 2i+1), not (i, i + D/2)
    num_shared_experts: int = 0  # experts every token goes through, beside the routed ones
    shared_experts: str = "sum"  # | "average": their outputs' mean
    # the routed experts whose weights this engine holds, in the order
    # ew1 / ew3 / ew2 stack them; (): all `num_experts` of them
    experts_held: Tuple[int, ...] = ()
    # a routed branch beside the dense feed-forwards (module docstring):
    # every `shortcut_experts`-th sub-layer, from the first, also routes
    # its feed-forward's normed input through the experts, and that sum
    # lands in the residual behind the feed-forward of the LAST sub-layer
    # of its period. 0: no such branch
    shortcut_experts: int = 0
    # router outputs behind the `num_experts` real ones that are identity
    # experts: a pick of one adds `gate x (the experts' input)`
    zero_experts: int = 0
    router_renormalise: bool = True  # gates divided by the picked sum; False: `s_i x routed_scaling_factor`
    router_softmax_bias: bool = False  # a softmax router's selection bias (the choice only, never the gate)
    # a latent layer's LoRA scales: on q behind W_UQ, and on the normed c
    # (the cached row holds the scaled c)
    latent_q_scale: float = 1.0
    latent_kv_scale: float = 1.0
    # block diffusion (module docstring): position i attends position j
    # iff j // block_mask <= i // block_mask. 0: the causal mask
    block_mask: int = 0
    # an "ssm" layer (module docstring): heads H of width P (d_inner = H x P), G groups that share B
    # and C, the state's width N, the depthwise convolution's kernel and the prefill's chunk
    ssm_heads: int = 0
    ssm_head_dim: int = 0
    ssm_groups: int = 1
    ssm_state_size: int = 0
    ssm_conv_kernel: int = 4
    ssm_chunk: int = 128
    # what init_decoder_params draws an ssm layer's steps from: log-uniform in [min, max], floored (the
    # published Mamba-2 initialisation's time_step_min / time_step_max / time_step_floor)
    ssm_dt_range: Tuple[float, float, float] = (1e-3, 1e-1, 1e-4)
    # the experts' (and the shared expert's) form: "swiglu", or "relu2": ungated, W2 relu(W1 v)^2
    expert_activation: str = "swiglu"
    # > 0: the routed experts live in a latent of this width: u = W_down h goes through them
    # (their matrices are [latent, moe_ff_size] and back) and W_up takes their gated sum to the
    # hidden size; the router and the shared expert read h itself
    moe_latent_size: int = 0
    shared_ff_size: int = 0  # the shared expert's width; 0: num_shared_experts x moe_ff_size
    # a "mamba" layer (Mamba-1, module docstring): the inner width is `mamba_expand` x hidden_size, the step's
    # low rank `mamba_dt_rank` (0: ceil(hidden_size / 16)); the state's width, the convolution's kernel, the
    # prefill's chunk and the steps' range are the ssm fields above (`ssm_state_size`, `ssm_conv_kernel`, ...)
    mamba_expand: int = 2
    mamba_dt_rank: int = 0
    # differential attention in every layer that attends (module docstring): two softmaxes over paired heads,
    # subtracted, a norm over the pair; K/V is stored as `kv_heads / 2` heads of `2 x head_dim`
    differential: bool = False
    attention_bias: bool = False  # biases on W_q, W_k, W_v and W_o
    # a "cross" layer attends the K/V that layer `kv_source` (an "attention" layer before it) stores, and has
    # none of its own; a "gmu" layer gates the scan output of layer `memory_source` (a "mamba" layer before it)
    kv_source: int = -1
    memory_source: int = -1

    def __post_init__(self):
        if self.layer_types and len(self.layer_types) != self.num_layers:
            raise ValueError(f"{len(self.layer_types)} layer_types for {self.num_layers} layers")
        for kind in self.layer_types:
            if kind not in ("attention", "window", "latent", "conv", "ssm", "ffn", "mamba", "gmu", "cross"):
                raise ValueError(
                    f"layer type {kind!r}: 'attention', 'window', 'latent', 'conv', 'ssm', 'ffn', 'mamba', 'gmu' or 'cross'"
                )
        if "window" in self.layer_types and self.window < 1:
            raise ValueError("a 'window' layer needs window >= 1")
        if self.router not in ("sigmoid", "softmax"):
            raise ValueError(f"router {self.router!r}: 'sigmoid' or 'softmax'")
        if self.norm not in ("layernorm", "layernorm_nobias", "rmsnorm"):
            raise ValueError(f"norm {self.norm!r}: 'layernorm', 'layernorm_nobias' or 'rmsnorm'")
        if self.block not in ("sequential", "parallel", "single"):
            raise ValueError(f"block {self.block!r}: 'sequential', 'parallel' or 'single'")
        if ("ffn" in self.layer_types) != (self.block == "single") or self.block == "single" and self.shortcut_experts:
            raise ValueError("a layer that is its feed-forward alone ('ffn') is a 'single' block's, and such a block has one")
        if "ssm" in self.layer_types:
            h, p, g, n = self.ssm_heads, self.ssm_head_dim, self.ssm_groups, self.ssm_state_size
            if min(h, p, g, n) < 1 or h % g or self.ssm_conv_kernel < 2 or self.ssm_chunk < 1:
                raise ValueError(f"an 'ssm' layer needs its heads, head width, groups (dividing the heads) and state width, got {(h, p, g, n)}")
            if self.conv_layers or self.window_layers or self.latent_layers or self.mamba_layers:
                raise ValueError(
                    "Mamba-2 ('ssm') layers beside convolution, window, latent or Mamba-1 layers: per-slot state beside a "
                    "window pool is written down, and tested, for Mamba-1 ('mamba') layers"
                )
        if self.mamba_layers:
            if self.ssm_state_size < 1 or self.mamba_expand < 1 or self.ssm_conv_kernel < 2 or self.ssm_chunk < 1:
                raise ValueError(
                    f"a 'mamba' layer needs its state width, expansion, kernel and chunk, got "
                    f"{(self.ssm_state_size, self.mamba_expand, self.ssm_conv_kernel, self.ssm_chunk)}"
                )
            if self.conv_layers or self.latent_layers or self.block != "sequential":
                raise ValueError("mamba layers are written down for a sequential block, beside attention, window, gmu and cross layers")
        for kind, source, producer in (("cross", self.kv_source, "attention"), ("gmu", self.memory_source, "mamba")):
            readers = tuple(l for l in range(self.num_layers) if self.operator(l) == kind)
            if readers and not (0 <= source < readers[0] and self.operator(source) == producer):
                raise ValueError(
                    f"a {kind!r} layer reads what layer {source} keeps: that has to be a {producer!r} layer before the first of them "
                    f"(layer {readers[0]})"
                )
        if self.differential:
            rotated = self.positions == "rotary" and any(
                self.rope_parameters.get(kind, {}).get("positions") != "none" for kind in ("attention", "window")
            )
            if self.num_heads % 2 or self.kv_heads % 2 or self.num_heads % self.kv_heads or self.qk_norm or rotated or self.latent_layers or self.block_mask or self.block != "sequential":
                raise ValueError(
                    "differential attention pairs neighbouring heads (an even count of query and of K/V heads) and is written "
                    "down without rotation, q/k norm, latent layers or a block mask, in a sequential block"
                )
        if self.expert_activation not in ("swiglu", "relu2"):
            raise ValueError(f"expert_activation {self.expert_activation!r}: 'swiglu' or 'relu2'")
        if self.block == "parallel" and (self.stateful or self.latent_layers):
            raise ValueError("a parallel block is written down for attention and window layers")
        if self.shared_experts not in ("sum", "average"):
            raise ValueError(f"shared_experts {self.shared_experts!r}: 'sum' or 'average'")
        if self.rope_interleave and any("factor" in r for r in self.rope_parameters.values()):
            raise ValueError("YaRN is written down for the rotate-half form, not for interleaved pairs")
        if "latent" in self.layer_types:
            if set(self.layer_types) != {"latent"}:
                raise ValueError("latent layers beside another kind: one cache holds rows of one width")
            widths = (self.q_lora_rank, self.kv_lora_rank, self.qk_nope_head_dim, self.qk_rope_head_dim, self.v_head_dim)
            if min(widths) < 1 or self.qk_rope_head_dim % 2:
                raise ValueError(f"a 'latent' layer needs its five widths (and an even rotary one), got {widths}")
        self.experts_held = tuple(int(i) for i in self.experts_held)
        if self.experts_held and not all(0 <= i < self.num_experts for i in self.experts_held):
            raise ValueError(f"experts_held {self.experts_held} outside the {self.num_experts} routed experts")
        if self.shortcut_experts:
            if self.block != "sequential" or self.num_dense_layers >= 0 or self.num_layers % self.shortcut_experts:
                raise ValueError(
                    "a shortcut expert branch runs beside dense feed-forwards of a sequential block, over whole periods "
                    f"(block {self.block!r}, num_dense_layers {self.num_dense_layers}, {self.num_layers} layers, "
                    f"period {self.shortcut_experts})"
                )
        if self.zero_experts < 0 or self.router_softmax_bias and self.router != "softmax":
            raise ValueError("zero_experts counts router outputs; router_softmax_bias is a softmax router's")
        if self.block_mask < 0 or self.block_mask and set(self.layer_types) - {"attention"}:
            raise ValueError(
                f"block_mask {self.block_mask}: a block of positions that see each other is written down for "
                f"attention layers that keep every position (layer types {sorted(set(self.layer_types))})"
            )

    @property
    def kv_heads(self) -> int:
        return self.num_kv_heads or self.num_heads

    @property
    def dim_per_head(self) -> int:
        return self.head_dim or self.hidden_size // self.num_heads

    def operator(self, layer: int) -> str:
        return self.layer_types[layer] if self.layer_types else "attention"

    def ffn_kind(self, layer: int) -> str:
        if self.block == "single" and self.operator(layer) != "ffn":
            return "none"  # the layer is its operator alone
        return "experts" if 0 <= self.num_dense_layers <= layer else self.ffn

    @property
    def attention_layers(self) -> Tuple[int, ...]:
        """Layers that ATTEND, in layer order: those with K/V of their
        own, of either kind, and the ``cross`` layers, which read another
        layer's (:attr:`kv_layers` are the ones that store)."""
        return tuple(l for l in range(self.num_layers) if self.operator(l) in ("attention", "window", "latent", "cross"))

    @property
    def kv_layers(self) -> Tuple[int, ...]:
        """Layers that STORE K/V (a ``cross`` layer attends and stores none)."""
        return tuple(l for l in self.attention_layers if self.operator(l) != "cross")

    @property
    def cross_layers(self) -> Tuple[int, ...]:
        return tuple(l for l in range(self.num_layers) if self.operator(l) == "cross")

    @property
    def gmu_layers(self) -> Tuple[int, ...]:
        return tuple(l for l in range(self.num_layers) if self.operator(l) == "gmu")

    @property
    def cross_from(self) -> int:
        """The first layer of the cross-decoder: from here on no layer
        stores anything a later position reads (``gmu`` and ``cross``
        layers and their feed-forwards), so a prefill runs these on each
        sequence's last row alone. ``num_layers`` where there is none."""
        readers = self.gmu_layers + self.cross_layers
        first = min(readers) if readers else self.num_layers
        return first if all(self.operator(l) in ("gmu", "cross") for l in range(first, self.num_layers)) else self.num_layers

    @property
    def cache_kv_heads(self) -> int:
        """K/V heads as the cache and the attention calls see them: with
        differential attention a PAIR of neighbouring heads is one, and
        the pairs are stored at the next count of rows a block of the
        cache can be copied by: 1, 2, 4 or a multiple of 8 (the device
        lays ``[..., R, 128]`` out in tiles of 8 rows and the paged kernel
        copies whole tiles, so 10 pairs are stored as 16 rows, the 6
        behind them zero). One call over 16 rows reads at the memory's
        rate; five calls over 2 rows each, which would store nothing
        idle, take 2.2 times as long (PERF.md section 6, PR 57)."""
        if not self.differential:
            return self.kv_heads
        pairs = self.kv_heads // 2
        return next(r for r in (1, 2, 4) if r >= pairs) if pairs <= 4 else -(-pairs // 8) * 8

    @property
    def attend_heads(self) -> int:
        """Query heads as the attention calls see them: the real ones
        and, where the stored pairs are padded, the group of each padded
        pair (zero queries, whose results are dropped)."""
        return self.cache_kv_heads * (self.num_heads // (self.kv_heads // 2)) if self.differential else self.num_heads

    @property
    def cache_head_dim(self) -> int:
        return 2 * self.dim_per_head if self.differential else self.dim_per_head

    @property
    def window_layers(self) -> Tuple[int, ...]:
        return tuple(l for l in range(self.num_layers) if self.operator(l) == "window")

    @property
    def full_layers(self) -> Tuple[int, ...]:
        """Layers whose cache keeps every position: the main pool's."""
        return tuple(l for l in range(self.num_layers) if self.operator(l) in ("attention", "latent"))

    @property
    def latent_layers(self) -> Tuple[int, ...]:
        return tuple(l for l in range(self.num_layers) if self.operator(l) == "latent")

    @property
    def latent_width(self) -> int:
        """Values a latent layer caches a position: ``[c, k_r]``."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def held_experts(self) -> int:
        """Routed experts whose weights a layer holds here."""
        return len(self.experts_held) or self.num_experts

    @property
    def kv_index(self) -> Tuple[Tuple[str, int], ...]:
        """For the ``ai``-th attention layer: its kind and its index in
        that kind's K/V arrays."""
        seen = {"attention": 0, "window": 0, "latent": 0}
        out, at = [], {}
        for l in self.attention_layers:
            kind = self.operator(l)
            if kind == "cross":  # reads the arrays of the layer that produced its K/V, and writes nothing
                out.append((kind, at[self.kv_source]))
                continue
            out.append((kind, seen[kind]))
            at[l] = seen[kind]
            seen[kind] += 1
        return tuple(out)

    @property
    def stored_index(self) -> Tuple[Tuple[str, int], ...]:
        """:attr:`kv_index` of the layers that store K/V: what a
        prefill's returned K/V is indexed by."""
        return tuple(entry for entry in self.kv_index if entry[0] != "cross")

    @property
    def conv_layers(self) -> Tuple[int, ...]:
        return tuple(l for l in range(self.num_layers) if self.operator(l) == "conv")

    @property
    def ssm_layers(self) -> Tuple[int, ...]:
        """The state-space layers, Mamba-2 (``ssm``) or Mamba-1
        (``mamba``): a configuration has one of the two."""
        return tuple(l for l in range(self.num_layers) if self.operator(l) in ("ssm", "mamba"))

    @property
    def mamba_layers(self) -> Tuple[int, ...]:
        return tuple(l for l in range(self.num_layers) if self.operator(l) == "mamba")

    @property
    def ssm_inner(self) -> int:
        """A state-space layer's inner width: ``H x P``, or a Mamba-1
        layer's ``mamba_expand x hidden_size``."""
        return self.mamba_expand * self.hidden_size if self.mamba_layers else self.ssm_heads * self.ssm_head_dim

    @property
    def ssm_conv_width(self) -> int:
        """What a state-space layer's convolution runs over: ``[x, B,
        C]``, or a Mamba-1 layer's ``x`` alone."""
        return self.ssm_inner + (0 if self.mamba_layers else 2 * self.ssm_groups * self.ssm_state_size)

    @property
    def dt_rank(self) -> int:
        """The low rank a Mamba-1 layer's step comes through."""
        return self.mamba_dt_rank or -(-self.hidden_size // 16)

    def shortcut(self, layer: int) -> bool:
        """``layer`` carries a shortcut expert branch (beside its dense
        feed-forward)."""
        return bool(self.shortcut_experts) and layer % self.shortcut_experts == 0

    @property
    def expert_layers(self) -> Tuple[int, ...]:
        """Layers that route: their feed-forward is the experts, or they
        carry a shortcut branch."""
        return tuple(l for l in range(self.num_layers) if self.ffn_kind(l) == "experts" or self.shortcut(l))

    @property
    def router_outputs(self) -> int:
        return self.num_experts + self.zero_experts

    @property
    def expert_count_columns(self) -> int:
        """Width of an expert layer's counter row (:func:`_count_row`)."""
        return self.held_experts + bool(self.experts_held) + (2 + self.experts_per_token if self.zero_experts else 0)

    @property
    def stateful(self) -> bool:
        """Some layer keeps a state that is not paged K/V."""
        return bool(self.conv_layers or self.ssm_layers)


def decoder_config(cfg: TransformerConfig) -> DecoderConfig:
    """``cfg`` as a :class:`DecoderConfig` (a plain TransformerConfig is
    the GPT-2 setting)."""
    if isinstance(cfg, DecoderConfig):
        return cfg
    return DecoderConfig(**dataclasses.asdict(cfg))


def _config_of(params: DecoderParams) -> DecoderConfig:
    """The GPT-2 setting a bare pytree implies (the forwards' ``cfg``
    argument is optional for it: nothing in that setting is read from
    the configuration but what the weights' shapes already say)."""
    wq = params["layers"][0]["wq"]
    return DecoderConfig(
        num_layers=len(params["layers"]), hidden_size=wq.shape[0], num_heads=wq.shape[1],
        ff_size=params["layers"][0]["ff1"].shape[1], vocab_size=params["tok_embed"].shape[0],
        causal=True,
    )


def _glorot(rng, shape, dtype=jnp.float32):
    fan_in, fan_out = shape[0], shape[-1]
    if len(shape) == 3:  # [E, H, D] / [H, D, E] projections
        fan_in = shape[0] if shape[0] > shape[2] else shape[0] * shape[1]
        fan_out = shape[1] * shape[2] if shape[0] > shape[2] else shape[2]
    lim = (6.0 / (fan_in + fan_out)) ** 0.5
    return jax.random.uniform(rng, shape, jnp.float32, -lim, lim).astype(dtype)


def init_decoder_params(
    rng: jax.Array, cfg: TransformerConfig, max_positions: Optional[int] = None
) -> DecoderParams:
    """Initialize the decoder pytree for ``cfg`` (``vocab_size`` > 0),
    in ``cfg.dtype`` (router weights and bias stay float32)."""
    if cfg.vocab_size <= 0:
        raise ValueError("generation decoder needs cfg.vocab_size > 0")
    cfg = decoder_config(cfg)
    dt = cfg.dtype.jnp
    e, h, hk, d = cfg.hidden_size, cfg.num_heads, cfg.kv_heads, cfg.dim_per_head
    f, v = cfg.ff_size, cfg.vocab_size
    p = max_positions or cfg.seq_length
    # (a configuration without latent layers or shared experts draws the keys it always drew)
    per_layer = 14 if cfg.latent_layers or cfg.num_shared_experts or cfg.shortcut_experts or cfg.block == "single" else 10
    if cfg.differential or cfg.mamba_layers:
        per_layer = 20
    keys = iter(jax.random.split(rng, 4 + per_layer * cfg.num_layers))
    ones, zeros = jnp.ones((e,), dt), jnp.zeros((e,), dt)
    params: DecoderParams = {"tok_embed": _glorot(next(keys), (v, e), dt)}
    pos_key, head_key = next(keys), next(keys)
    if cfg.positions == "learned":
        params["pos_embed"] = (0.02 * jax.random.normal(pos_key, (p, e), jnp.float32)).astype(dt)
    params["final_ln_g"] = ones
    if cfg.norm == "layernorm":
        params["final_ln_b"] = zeros
    if not cfg.tied_head:
        params["lm_head"] = _glorot(head_key, (e, v), dt)
    params["layers"] = []
    for li in range(cfg.num_layers):
        layer: Dict[str, Any] = {"ln1_g": ones}
        if cfg.norm == "layernorm":
            layer["ln1_b"] = zeros
        if cfg.operator(li) == "latent":
            rq, rkv, dn, dr, dv = cfg.q_lora_rank, cfg.kv_lora_rank, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
            layer.update(
                w_dq=_glorot(next(keys), (e, rq), dt), q_lora_g=jnp.ones((rq,), dt),
                w_uq=_glorot(next(keys), (rq, h, dn + dr), dt),
                w_dkv=_glorot(next(keys), (e, rkv + dr), dt), kv_lora_g=jnp.ones((rkv,), dt),
                w_ukv=_glorot(next(keys), (rkv, h, dn + dv), dt), wo=_glorot(next(keys), (h, dv, e), dt),
            )
        elif cfg.operator(li) == "ssm":
            layer.update(_init_ssm(cfg, keys, dt))
        elif cfg.operator(li) == "mamba":
            layer.update(_init_mamba(cfg, keys, dt))
        elif cfg.operator(li) == "gmu":
            layer.update(gmu_in=_glorot(next(keys), (e, cfg.ssm_inner), dt), gmu_out=_glorot(next(keys), (cfg.ssm_inner, e), dt))
        elif cfg.operator(li) == "ffn":
            pass  # the layer is its feed-forward alone
        elif cfg.operator(li) != "conv":
            layer.update(wq=_glorot(next(keys), (e, h, d), dt))
            if cfg.operator(li) != "cross":  # a cross layer reads another layer's K/V: W_q and W_o alone
                layer.update(wk=_glorot(next(keys), (e, hk, d), dt), wv=_glorot(next(keys), (e, hk, d), dt))
            layer.update(wo=_glorot(next(keys), (h, d, e), dt))
            if cfg.qk_norm:
                layer.update(q_norm_g=jnp.ones((d,), dt), k_norm_g=jnp.ones((d,), dt))
            if cfg.attention_bias:
                small = lambda shape: (0.02 * jax.random.normal(next(keys), shape, jnp.float32)).astype(dt)  # noqa: E731
                layer.update(bq=small((h, d)), bo=small((e,)))
                if "wk" in layer:
                    layer.update(bk=small((hk, d)), bv=small((hk, d)))
            if cfg.differential:
                # the four vectors of lambda (float32, as the router's weights are) and the pair norm's weight
                layer.update({f"lambda_{n}": 0.1 * jax.random.normal(next(keys), (d,), jnp.float32) for n in ("q1", "k1", "q2", "k2")})
                layer.update(subln_g=jnp.ones((2 * d,), dt))
        else:
            layer.update(
                conv_in=_glorot(next(keys), (e, 3 * e), dt),
                conv_w=_glorot(next(keys), (e, cfg.conv_kernel), dt),
                conv_out=_glorot(next(keys), (e, e), dt),
            )
        if cfg.block == "sequential":  # a parallel block has the one norm
            layer["ln2_g"] = ones
            if cfg.norm == "layernorm":
                layer["ln2_b"] = zeros
        kind = cfg.ffn_kind(li)
        if kind == "gelu":
            layer.update(
                ff1=_glorot(next(keys), (e, f), dt), ff1_b=jnp.zeros((f,), dt),
                ff2=_glorot(next(keys), (f, e), dt), ff2_b=zeros,
            )
        elif kind == "swiglu":
            layer.update(
                w1=_glorot(next(keys), (e, f), dt), w3=_glorot(next(keys), (e, f), dt),
                w2=_glorot(next(keys), (f, e), dt),
            )
        if kind == "experts" or cfg.shortcut(li):
            n, fe = cfg.router_outputs, cfg.moe_ff_size
            layer.update(router=_glorot(next(keys), (e, n)))
            if cfg.router == "sigmoid" and cfg.router_selection_bias:
                layer.update(router_bias=0.02 * jax.random.normal(next(keys), (n,), jnp.float32))
            elif cfg.router_softmax_bias:  # a tenth of a uniform pick's probability: it moves near-ties
                layer.update(router_bias=0.1 / n * jax.random.normal(next(keys), (n,), jnp.float32))
            n = cfg.held_experts  # the router scores every expert; the weights are the held ones'
            gated = cfg.expert_activation == "swiglu"  # an ungated expert has no third matrix
            ew = cfg.moe_latent_size or e  # the width the routed experts read and write
            if cfg.moe_latent_size:
                layer.update(lat_down=_glorot(next(keys), (e, ew), dt), lat_up=_glorot(next(keys), (ew, e), dt))
            layer.update(ew1=_glorot(next(keys), (n, ew, fe), dt))
            if gated:
                layer.update(ew3=_glorot(next(keys), (n, ew, fe), dt))
            layer.update(ew2=_glorot(next(keys), (n, fe, ew), dt))
            if cfg.num_shared_experts:
                fs = cfg.shared_ff_size or cfg.num_shared_experts * fe
                layer.update(sw1=_glorot(next(keys), (e, fs), dt))
                if gated:
                    layer.update(sw3=_glorot(next(keys), (e, fs), dt))
                layer.update(sw2=_glorot(next(keys), (fs, e), dt))
        params["layers"].append(layer)
    return params


def _init_ssm(cfg: DecoderConfig, keys, dt) -> Dict[str, Any]:
    """An ssm layer's weights: the projections Glorot, the rates and
    steps as the published Mamba-2 initialisation draws them (``A_log =
    log(1..H)``, ``dt`` log-uniform in ``cfg.ssm_dt_range`` through the
    inverse softplus, ``D = 1``), so that the decays are a trained model's
    in scale. The three vectors stay float32, as the router does."""
    e, h, di, cw = cfg.hidden_size, cfg.ssm_heads, cfg.ssm_inner, cfg.ssm_conv_width
    lo, hi, floor = cfg.ssm_dt_range
    step = jnp.exp(jax.random.uniform(next(keys), (h,), jnp.float32) * (math.log(hi) - math.log(lo)) + math.log(lo))
    step = jnp.maximum(step, floor)
    return dict(
        ssm_in=_glorot(next(keys), (e, di + cw + h), dt), ssm_conv_w=_glorot(next(keys), (cw, cfg.ssm_conv_kernel), dt),
        ssm_conv_b=jnp.zeros((cw,), dt), ssm_dt_bias=step + jnp.log(-jnp.expm1(-step)),
        ssm_a_log=jnp.log(jnp.arange(1, h + 1, dtype=jnp.float32)), ssm_d=jnp.ones((h,), jnp.float32),
        ssm_norm_g=jnp.ones((di,), dt), ssm_out=_glorot(next(keys), (di, e), dt),
    )


def _init_mamba(cfg: DecoderConfig, keys, dt) -> Dict[str, Any]:
    """A Mamba-1 layer's weights: the projections Glorot, the rates and
    steps as the published initialisation draws them (``A_log = log(1..N)``
    a channel, ``dt`` log-uniform in ``cfg.ssm_dt_range`` through the
    inverse softplus, ``D = 1``). ``A_log``, ``D`` and the step's bias stay
    float32, as the router does."""
    e, di, n, r = cfg.hidden_size, cfg.ssm_inner, cfg.ssm_state_size, cfg.dt_rank
    lo, hi, floor = cfg.ssm_dt_range
    step = jnp.exp(jax.random.uniform(next(keys), (di,), jnp.float32) * (math.log(hi) - math.log(lo)) + math.log(lo))
    step = jnp.maximum(step, floor)
    return dict(
        ssm_in=_glorot(next(keys), (e, 2 * di), dt), ssm_conv_w=_glorot(next(keys), (di, cfg.ssm_conv_kernel), dt),
        ssm_conv_b=jnp.zeros((di,), dt), ssm_x=_glorot(next(keys), (di, r + 2 * n), dt),
        ssm_dt_w=_glorot(next(keys), (r, di), dt), ssm_dt_bias=step + jnp.log(-jnp.expm1(-step)),
        ssm_a_log=jnp.log(jnp.broadcast_to(jnp.arange(1, n + 1, dtype=jnp.float32), (di, n))),
        ssm_d=jnp.ones((di,), jnp.float32), ssm_out=_glorot(next(keys), (di, e), dt),
    )


# ------------------------------------------------------------------ pieces
def _mm(eq: str, x, w):
    """A matmul that accumulates in float32 and hands its result on in
    the activations' type (for float32 operands: the plain einsum)."""
    return jnp.einsum(eq, x, w, preferred_element_type=jnp.float32).astype(x.dtype)


def _ln(x, g, b, eps=1e-5):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * g + b


def _rms(x, g, eps):
    ms = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(ms + eps) * g


def _norm(cfg: DecoderConfig, x, where: Dict, name: str):
    """``where[name_g]`` (and ``name_b``) applied over the last axis, in
    float32, handed on in ``x``'s type."""
    xf = x.astype(jnp.float32)
    if cfg.norm == "layernorm":
        out = _ln(xf, where[f"{name}_g"], where[f"{name}_b"], cfg.norm_eps)
    elif cfg.norm == "layernorm_nobias":
        out = _ln(xf, where[f"{name}_g"].astype(jnp.float32), 0.0, cfg.norm_eps)
    else:
        out = _rms(xf, where[f"{name}_g"].astype(jnp.float32), cfg.norm_eps)
    return out.astype(x.dtype)


def _yarn(inv, d: int, theta: float, yarn: Dict[str, float]):
    """YaRN's frequencies: dimensions that turn fewer than ``beta_slow``
    times over the original context are slowed by ``factor``, those that
    turn more than ``beta_fast`` times are left, a linear ramp between."""
    span = float(yarn["original_max_position_embeddings"])
    turns = lambda r: d * math.log(span / (2 * math.pi * r)) / (2 * math.log(theta))  # noqa: E731
    low = max(math.floor(turns(float(yarn["beta_fast"]))), 0)
    high = min(math.ceil(turns(float(yarn["beta_slow"]))), d - 1)
    ramp = jnp.clip((jnp.arange(d // 2, dtype=jnp.float32) - low) / max(high - low, 0.001), 0.0, 1.0)
    return inv / float(yarn["factor"]) * ramp + inv * (1.0 - ramp)


def _rope(x, positions, theta: float, yarn: Optional[Dict[str, float]] = None):
    """Rotate-half rotary embedding over all of the head's dimensions:
    x [..., H, D], positions [...] (the leading axes of x). ``yarn``
    (the kind's ``rope_parameters`` where they hold a ``factor``): the
    frequencies scaled as :func:`_yarn` says, and cos and sin both
    multiplied by ``attention_factor``."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    if yarn:
        inv = _yarn(inv, d, theta, yarn)
    ang = positions.astype(jnp.float32)[..., None] * inv  # [..., D/2]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], axis=-1)[..., None, :]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], axis=-1)[..., None, :]
    if yarn:
        cos, sin = cos * float(yarn["attention_factor"]), sin * float(yarn["attention_factor"])
    xf = x.astype(jnp.float32)
    half = jnp.concatenate([-xf[..., d // 2:], xf[..., : d // 2]], axis=-1)
    return (xf * cos + half * sin).astype(x.dtype)


def _embed(cfg: DecoderConfig, params, tokens, positions):
    x = params["tok_embed"][tokens]
    if cfg.positions == "learned":
        x = x + params["pos_embed"][positions]
    return x


def _head(cfg: DecoderConfig, params, x):
    x = _norm(cfg, x, params, "final_ln")
    if cfg.tied_head:
        return jnp.einsum("...e,ve->...v", x, params["tok_embed"], preferred_element_type=jnp.float32)
    return jnp.einsum("...e,ev->...v", x, params["lm_head"], preferred_element_type=jnp.float32)


def _qkv(cfg: DecoderConfig, layer, h, positions, kind: str = "attention"):
    # (a cross layer has W_q alone: the K/V it attends is another ATTENTION layer's, already normed and rotated)
    cross = "wk" not in layer
    q = _mm("...e,ehd->...hd", h, layer["wq"])
    k, v = (None, None) if cross else (_mm("...e,ehd->...hd", h, layer["wk"]), _mm("...e,ehd->...hd", h, layer["wv"]))
    if cfg.qk_norm:
        q = _rms(q.astype(jnp.float32), layer["q_norm_g"].astype(jnp.float32), cfg.norm_eps).astype(h.dtype)
        k = k if cross else _rms(k.astype(jnp.float32), layer["k_norm_g"].astype(jnp.float32), cfg.norm_eps).astype(h.dtype)
    rope = cfg.rope_parameters.get("attention" if cross else kind, {})
    if cfg.positions == "rotary" and rope.get("positions") != "none":
        theta, yarn = float(rope.get("theta", cfg.rope_theta)), rope if "factor" in rope else None
        rotate = (lambda x: _rope_pairs(x, positions, theta)) if cfg.rope_interleave else (lambda x: _rope(x, positions, theta, yarn))
        q, k = rotate(q), k if cross else rotate(k)
    return q, k, v


def _rope_pairs(x, positions, theta: float):
    """Rotary embedding over interleaved pairs ``(2i, 2i+1)`` of the
    head's dimensions (``rope_interleave``): x [..., H, D], positions
    [...] (the leading axes of x), as :func:`_rope` takes them."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = (positions.astype(jnp.float32)[..., None] * inv)[..., None, :]  # [..., 1, D/2]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    xf = x.astype(jnp.float32).reshape(*x.shape[:-1], d // 2, 2)
    even, odd = xf[..., 0], xf[..., 1]
    out = jnp.stack([even * cos - odd * sin, odd * cos + even * sin], axis=-1)
    return out.reshape(x.shape).astype(x.dtype)


def _latent_qkv(cfg: DecoderConfig, layer, h, positions):
    """A latent layer's projections (module docstring): the queries
    [..., H, qk_nope + qk_rope], their rotary part rotated, and the
    position's cache row [..., RW]: ``[c, k_r]`` after the norm, the
    scale (``latent_kv_scale``, on ``c`` alone) and the rotation,
    zero-filled to the stored width. ``latent_q_scale`` multiplies the
    queries behind ``W_UQ``."""
    dn, rkv = cfg.qk_nope_head_dim, cfg.kv_lora_rank
    rotate = _rope_pairs if cfg.rope_interleave else _rope
    c_q = _mm("...e,er->...r", h, layer["w_dq"])
    c_q = _rms(c_q.astype(jnp.float32), layer["q_lora_g"].astype(jnp.float32), cfg.norm_eps).astype(h.dtype)
    q = _mm("...r,rhd->...hd", c_q, layer["w_uq"])
    if cfg.latent_q_scale != 1.0:
        q = (q.astype(jnp.float32) * cfg.latent_q_scale).astype(h.dtype)
    q = jnp.concatenate([q[..., :dn], rotate(q[..., dn:], positions, cfg.rope_theta)], axis=-1)
    kv = _mm("...e,er->...r", h, layer["w_dkv"])
    c = _rms(kv[..., :rkv].astype(jnp.float32), layer["kv_lora_g"].astype(jnp.float32), cfg.norm_eps)
    if cfg.latent_kv_scale != 1.0:  # the row holds the scaled c; k_r is not scaled
        c = c * cfg.latent_kv_scale
    c = c.astype(h.dtype)
    k_r = rotate(kv[..., None, rkv:], positions, cfg.rope_theta)[..., 0, :]  # ONE rotary key, all heads'
    fill = jnp.zeros(kv.shape[:-1] + (latent_row_width(cfg.latent_width) - cfg.latent_width,), h.dtype)
    return q, jnp.concatenate([c, k_r, fill], axis=-1)


def _expanded(cfg: DecoderConfig, q, rows, w_ukv, lens, backend: str = "cpu"):
    """The latent layer's EXPANDED form over a whole window of rows
    ([B, S, RW]): K and V per head out of the rows, then a prefill's
    causal attention (ops/attention.py ``prefill_attention``: scores
    materialised while they stay under its bound, streamed past it, on a
    TPU by the Pallas call ``prefill_stream_attention`` at one query head
    a K/V head) at score width ``qk_nope + qk_rope`` and value width
    ``v_head_dim``. Returns [B, S, H, v_head_dim]."""
    rkv, dn = cfg.kv_lora_rank, cfg.qk_nope_head_dim
    kv = _mm("bsc,chd->bshd", rows[..., :rkv], w_ukv)
    k_r = jnp.broadcast_to(rows[:, :, None, rkv:cfg.latent_width], kv.shape[:3] + (cfg.qk_rope_head_dim,))
    k = jnp.concatenate([kv[..., :dn], k_r], axis=-1)
    return prefill_attention(q, k, kv[..., dn:], lens, backend=backend)


def _absorbed(cfg: DecoderConfig, q, w_ukv, cache, at: int, tables, q_positions, backend: str):
    """The latent layer's ABSORBED form over the cache: q [B, W, H,
    qk_nope + qk_rope] (its rows already written at ``q_positions`` [B,
    W]) -> [B, W, H, v_head_dim]. ``W_UK`` goes into the query and
    ``W_UV`` onto the attended rows, both halves of ``w_ukv`` sliced
    here; the scale is the expanded scores' (``1 / sqrt(qk_nope +
    qk_rope)``, NOT of the row's width)."""
    rkv, dn = cfg.kv_lora_rank, cfg.qk_nope_head_dim
    q_abs = _mm("bwhd,chd->bwhc", q[..., :dn], w_ukv[..., :dn])
    fill = jnp.zeros(q.shape[:-1] + (cache.shape[-1] - cfg.latent_width,), q.dtype)
    q_row = jnp.concatenate([q_abs, q[..., dn:], fill], axis=-1)  # laid out as a cache row is
    ctx = latent_attention_core(
        q_row, cache, at, tables, q_positions, value_width=rkv, scale=(dn + cfg.qk_rope_head_dim) ** -0.5,
        backend=backend,
    )
    return _mm("bwhc,chd->bwhd", ctx, w_ukv[..., dn:])


def conv_window(state, z):
    """``z`` [B, T, E] behind the ``K - 1`` rows that came before it
    (``state`` [B, K-1, E]; zeros at the start of a sequence): the
    padded rows [B, T + K - 1, E] a causal convolution of kernel ``K``
    reads. Row ``t + K - 1`` is ``z_t``; the state after ``n`` of the
    window's tokens is rows ``n .. n + K - 2`` (:func:`state_at`)."""
    return jnp.concatenate([state.astype(z.dtype), z], axis=1)


def state_at(zpad, n, k: int):
    """The convolution state after each sequence's first ``n[b]`` window
    tokens, out of its padded rows: [B, T + K - 1, E] -> [B, K - 1, E]."""
    idx = n[:, None] + jnp.arange(k - 1)[None, :]
    return jnp.take_along_axis(zpad, idx[:, :, None], axis=1)


def _conv_mix(layer, zpad, t: int):
    """``c_t = sum_j w[:, j] * z_{t-K+1+j}`` for the window's ``t``
    tokens, in float32, out of the padded rows."""
    w = layer["conv_w"].astype(jnp.float32)
    zf = zpad.astype(jnp.float32)
    return sum(w[:, j] * zf[:, j : j + t] for j in range(w.shape[1])).astype(zpad.dtype)


# the router's arithmetic is float32 throughout (the configuration's
# `assumed`): at a near-tie of two experts' scores a coarser product
# picks another expert, which is another model
_ROUTER_PRECISION = jax.lax.Precision.HIGHEST
def route(cfg: DecoderConfig, layer, v):
    """Gates [T, N] (zero where an expert is not among a token's top-k)
    and the choice [T, k], for rows ``v`` [T, E]: ``s = sigmoid(W_g v)``,
    ``I = top_k(s + b)``, ``g_i = s_i / (sum_{j in I} s_j + 1e-6) *
    routed_scaling_factor``. The bias moves the choice, never the gate.
    A ``softmax`` router: ``s = softmax(W_g v)`` over the experts, ``I =
    top_k(s)`` (``router_softmax_bias``: ``top_k(s + b)``), ``g_i = s_i /
    sum_{j in I} s_j * routed_scaling_factor``.
    A sigmoid router without a selection bias (``router_selection_bias``
    False): ``I = top_k(s)`` and the same gates over ``s = sigmoid(W_g
    v)``. ``router_renormalise`` False: ``g_i = s_i *
    routed_scaling_factor``, whatever the picked scores add up to.
    ``N`` is ``cfg.router_outputs``: the real experts and, behind them,
    the ``zero_experts`` identity ones."""
    scores = jnp.dot(v.astype(jnp.float32), layer["router"].astype(jnp.float32), precision=_ROUTER_PRECISION)
    if cfg.router == "softmax" or not cfg.router_selection_bias:
        s = jax.nn.softmax(scores, axis=-1) if cfg.router == "softmax" else jax.nn.sigmoid(scores)
        if cfg.router_softmax_bias:
            _, chosen = jax.lax.top_k(s + layer["router_bias"].astype(jnp.float32), cfg.experts_per_token)
            picked = jnp.take_along_axis(s, chosen, axis=-1)
        else:
            picked, chosen = jax.lax.top_k(s, cfg.experts_per_token)
        gate = picked / jnp.sum(picked, axis=-1, keepdims=True) if cfg.router_renormalise else picked
        gate = gate * cfg.routed_scaling_factor
        return jnp.zeros_like(s).at[jnp.arange(v.shape[0])[:, None], chosen].set(gate), chosen
    s = jax.nn.sigmoid(scores)
    _, chosen = jax.lax.top_k(s + layer["router_bias"].astype(jnp.float32), cfg.experts_per_token)
    picked = jnp.take_along_axis(s, chosen, axis=-1)
    gate = picked / (jnp.sum(picked, axis=-1, keepdims=True) + 1e-6) * cfg.routed_scaling_factor
    rows = jnp.arange(v.shape[0])[:, None]
    return jnp.zeros_like(s).at[rows, chosen].set(gate), chosen


def expert_ffn(cfg: DecoderConfig, layer, v, held: Optional[Sequence[int]] = None, live=None, routed=None):
    """The routed feed-forward of rows ``v`` [T, E]: ``sum_{i in I} g_i
    W2_i (silu(W1_i v) * W3_i v)`` (``expert_activation: "relu2"``: ``W2_i
    relu(W1_i v)^2``, no third matrix), exactly (no capacity, no dropped
    token), and the gates it used ([T, N], for the counters).

    ``held`` names the experts whose weights this call has, in the order
    ``layer["ew1"]`` / ``ew3`` / ``ew2`` stack them (None: all of them).
    The router always scores every expert; the result is the sum over
    the held ones alone, so the results of calls that hold disjoint
    shares add up to the whole layer's (a chip of an expert-sharded
    deployment runs this with its share and the exchange adds them).

    The sum has two lowerings, chosen by what the call's shapes show
    (ops/expert_product.py ``expert_form``: its table of chip timings is
    there): the dense one below, and from the row count where the rows'
    arithmetic outweighs the weights' reads the one over (row, chosen
    expert) pairs grouped by expert, which also skips rows that are not
    ``live`` ([T] bool: padding behind a prompt's length, whose result
    nothing reads; they get zeros).

    ``cfg.zero_experts``: the router's outputs behind the real experts
    are identity experts, and the result gains ``(sum of their gates) x
    v``, added in float32 before the one cast in both lowerings. They
    have no weights, so the term is whole under any ``held``.
    ``routed``: :func:`route`'s result for these rows, where the caller
    has it (and has named it apart in the program).
    """
    gates, chosen = routed if routed is not None else route(cfg, layer, v)
    # identity experts (the router's outputs behind the real ones): a pick
    # adds gate x v, whatever is held: they have no weights
    identity = jnp.sum(gates[:, cfg.num_experts:], axis=1) if cfg.zero_experts else None
    if expert_lowering(v.shape[0], layer["ew1"].shape[0], cfg.experts_per_token, gates.shape[1]) == "grouped":
        out = grouped_expert_sum(
            v, gates, chosen, layer["ew1"], layer.get("ew3"), layer["ew2"], held=held, live=live, identity=identity,
        )
        return out, gates
    if held is not None:
        mine = gates[:, jnp.asarray(tuple(held))]
    else:
        mine = gates[:, : cfg.num_experts] if cfg.zero_experts else gates
    # every expert multiplies every row, masked by the gate: the weights
    # are read once either way, and at a decode step's or a short
    # bucket's rows the 8 x multiply-adds hide behind those reads
    up = jnp.einsum("te,nef->ntf", v, layer["ew1"], preferred_element_type=jnp.float32)
    if cfg.expert_activation == "relu2":  # ungated: W2 relu(W1 v)^2
        hidden = (jnp.square(jax.nn.relu(up)) * mine.T[:, :, None]).astype(v.dtype)
    else:
        gate_up = jnp.einsum("te,nef->ntf", v, layer["ew3"], preferred_element_type=jnp.float32)
        hidden = (jax.nn.silu(up) * gate_up * mine.T[:, :, None]).astype(v.dtype)
    out = jnp.einsum("ntf,nfe->te", hidden, layer["ew2"], preferred_element_type=jnp.float32)
    if identity is not None:
        with jax.named_scope("experts.zero"):
            out = out + identity[:, None] * v.astype(jnp.float32)
    return out.astype(v.dtype), gates


def _swiglu(h, w1, w3, w2):
    """``W2 (silu(W1 h) * W3 h)``: both products in float32, their gated
    product handed on in the activations' type."""
    up = jnp.einsum("...e,ef->...f", h, w1, preferred_element_type=jnp.float32)
    gate_up = jnp.einsum("...e,ef->...f", h, w3, preferred_element_type=jnp.float32)
    return _mm("...f,fe->...e", (jax.nn.silu(up) * gate_up).astype(h.dtype), w2)


def _relu2(h, w1, w2):
    """``W2 relu(W1 h)^2``, the ungated feed-forward: the product in
    float32, its squared rectification handed on in the activations'
    type."""
    up = jnp.einsum("...e,ef->...f", h, w1, preferred_element_type=jnp.float32)
    return _mm("...f,fe->...e", jnp.square(jax.nn.relu(up)).astype(h.dtype), w2)


def _count_row(cfg: DecoderConfig, gates, live):
    """An expert layer's counter row from its ``gates`` [T, N] and the
    rows that are ``live`` (over T, any shape): the tokens every expert was chosen for;
    under a share (``experts_held``) the held ones' tokens, in the order
    they are held, and in one more column the live tokens none of them
    was chosen for. With ``zero_experts``, behind those: the picks that
    went to an identity expert, and ``experts_per_token + 1`` columns
    that count the live tokens by how many REAL experts they picked (0 ..
    k: the model's compute a token, as a histogram)."""
    picked = (gates > 0) & live.reshape(-1, 1)
    chosen = real = picked[:, : cfg.num_experts] if cfg.zero_experts else picked  # (a real expert's columns)
    if cfg.experts_held:
        chosen = chosen[:, jnp.asarray(cfg.experts_held)]
        nowhere = jnp.sum(live.reshape(-1) & ~jnp.any(chosen, axis=1), dtype=jnp.int32)
        row = jnp.concatenate([jnp.sum(chosen, axis=0, dtype=jnp.int32), nowhere[None]])
    else:
        row = jnp.sum(chosen, axis=0, dtype=jnp.int32)
    if cfg.zero_experts:
        per_token = jnp.sum(real, axis=1)  # [T]: real experts a token picked
        hist = jnp.sum((per_token[:, None] == jnp.arange(cfg.experts_per_token + 1)) & live.reshape(-1, 1), axis=0, dtype=jnp.int32)
        zero = jnp.sum(picked[:, cfg.num_experts:], dtype=jnp.int32)
        row = jnp.concatenate([row, zero[None], hist])
    return row


def _shortcut(cfg: DecoderConfig, layer, u, live, counts: Optional[List]):
    """A shortcut branch's routed sum of ``u`` (the normed input of the
    sub-layer's dense feed-forward, [..., E]), for :func:`_layers` to add
    behind the last sub-layer of the period: the held real experts' sum
    and the identity experts' term (:func:`expert_ffn`)."""
    rows, alive = u.reshape(-1, u.shape[-1]), live.reshape(-1)
    with jax.named_scope("router"):
        routed = route(cfg, layer, rows)
    with jax.named_scope("experts.shortcut"):
        out, gates = expert_ffn(cfg, layer, rows, held=cfg.experts_held or None, live=alive, routed=routed)
    if counts is not None:
        with jax.named_scope("router"):
            counts.append(_count_row(cfg, gates, alive))
    return out.reshape(u.shape)


def _ffn(cfg: DecoderConfig, li: int, layer, x, live, counts: Optional[List], normed=None):
    """``x + FFN(norm(x))`` of layer ``li``. ``live`` ([...] bool, the
    leading axes of x) says which rows are real tokens: only those are
    counted into ``counts`` (one [N] int32 row per expert layer).
    ``normed`` (a parallel block): the layer's one normed input, and the
    result is ``FFN(normed)`` alone, for the block to add."""
    kind = cfg.ffn_kind(li)
    if kind == "experts":
        with jax.named_scope("router"):
            h = _norm(cfg, x, layer, "ln2") if normed is None else normed
            rows = h.reshape(-1, h.shape[-1])
        routed, inner = None, rows
        if cfg.moe_latent_size:
            # the router reads h; the routed experts read, and write, its latent projection
            with jax.named_scope("router"):
                routed = route(cfg, layer, rows)
            with jax.named_scope("experts.latent"):
                inner = _mm("te,el->tl", rows, layer["lat_down"])
        with jax.named_scope("experts"):
            out, gates = expert_ffn(cfg, layer, inner, held=cfg.experts_held or None, live=live.reshape(-1), routed=routed)
        if cfg.moe_latent_size:
            with jax.named_scope("experts.latent"):
                out = _mm("tl,le->te", out, layer["lat_up"])
        if cfg.num_shared_experts:
            with jax.named_scope("shared_expert" if cfg.num_shared_experts == 1 else "shared_experts"):
                if cfg.expert_activation == "relu2":
                    shared = _relu2(rows, layer["sw1"], layer["sw2"])
                else:
                    shared = _swiglu(rows, layer["sw1"], layer["sw3"], layer["sw2"])
                if cfg.shared_experts == "average":
                    # one SwiGLU of the summed width IS the sum of the experts' outputs
                    shared = (shared.astype(jnp.float32) / cfg.num_shared_experts).astype(shared.dtype)
                out = out + shared
        if counts is not None:
            with jax.named_scope("router"):
                counts.append(_count_row(cfg, gates, live))
        return x + out.reshape(x.shape) if normed is None else out.reshape(x.shape)
    with jax.named_scope("mlp"):
        h = _norm(cfg, x, layer, "ln2") if normed is None else normed
        if kind == "swiglu":
            out = _swiglu(h, layer["w1"], layer["w3"], layer["w2"])
            return x + out if normed is None else out
        h = jax.nn.gelu(_mm("...e,ef->...f", h, layer["ff1"]) + layer["ff1_b"])
        out = _mm("...f,fe->...e", h, layer["ff2"])
        return x + out + layer["ff2_b"] if normed is None else out + layer["ff2_b"]


def _ssm_conv(layer, xpad, t: int):
    """``silu(conv1d_causal(.) + b)`` of a state-space layer for the
    window's ``t`` tokens out of its padded rows (depthwise, float32)."""
    w, xf = layer["ssm_conv_w"].astype(jnp.float32), xpad.astype(jnp.float32)
    return jax.nn.silu(sum(w[:, j] * xf[:, j : j + t] for j in range(w.shape[1])) + layer["ssm_conv_b"].astype(jnp.float32))


def _ssm(cfg: DecoderConfig, layer, h, live, si: int, window: Callable, scan: Callable):
    """A Mamba-2 layer's mixer of its normed input ``h`` ([B, S, E], or
    [B, E] for a decode step's one position): ``[z, xBC, dt] = W_in h``;
    ``xBC <- silu(conv1d_causal(xBC) + b)`` (depthwise, kernel K, in
    float32), split into ``x`` [H, P], ``B`` and ``C`` [G, N]; ``dt <-
    softplus(dt + dt_bias)``, ``A = -exp(A_log)``; the recurrence
    (ops/ssm.py) through ``scan``; ``y <- (y + D x) * silu(z)``,
    RMS-normalised over each of the G groups apart, times a weight; ``out
    = W_out y``. Rows that are not ``live`` get ``dt = 0`` and ``x = 0``:
    the state passes them unchanged. ``window`` hands the convolution
    the rows before the sequence (:func:`conv_window`) and takes what it
    leaves; ``dt``, the decay, the recurrence, the skip, the gate and the
    norm are float32."""
    one = h.ndim == 2
    if one:
        h, live = h[:, None], live[:, None]
    di, gn, hh, p = cfg.ssm_inner, cfg.ssm_groups * cfg.ssm_state_size, cfg.ssm_heads, cfg.ssm_head_dim
    proj = _mm("...e,ef->...f", h, layer["ssm_in"])
    z, xbc, dt = proj[..., :di], proj[..., di : di + cfg.ssm_conv_width], proj[..., di + cfg.ssm_conv_width :]
    xpad = window(si, xbc)  # [B, S + K - 1, width]
    with jax.named_scope("ssm.conv"):
        xbc = _ssm_conv(layer, xpad, xbc.shape[1]).astype(h.dtype)
    lead = xbc.shape[:2]
    x = jnp.where(live[..., None], xbc[..., :di], 0).reshape(*lead, hh, p)
    b = xbc[..., di : di + gn].reshape(*lead, cfg.ssm_groups, cfg.ssm_state_size)
    c = xbc[..., di + gn :].reshape(*lead, cfg.ssm_groups, cfg.ssm_state_size)
    dt = jnp.where(live[..., None], jax.nn.softplus(dt.astype(jnp.float32) + layer["ssm_dt_bias"]), 0.0)
    y = scan(si, x, dt, -jnp.exp(layer["ssm_a_log"]), b, c)  # [B, S, H, P] float32
    y = y + layer["ssm_d"][:, None] * x.astype(jnp.float32)
    y = y.reshape(*lead, di) * jax.nn.silu(z.astype(jnp.float32))
    y = _rms(y.reshape(*lead, cfg.ssm_groups, -1), 1.0, cfg.norm_eps).reshape(*lead, di) * layer["ssm_norm_g"].astype(jnp.float32)
    out = _mm("...f,fe->...e", y.astype(h.dtype), layer["ssm_out"])
    return out[:, 0] if one else out


def lambda_init(layer: int) -> float:
    """Differential attention's constant of layer index ``layer``."""
    return 0.8 - 0.6 * math.exp(-0.3 * layer)


def _diff_qkv(cfg: DecoderConfig, layer, h):
    """A differential layer's projections in the PADDED-QUERY form (module
    docstring): ``q`` [..., H, 2D], query head ``2i`` as ``[q1_i ‖ 0]`` and
    ``2i + 1`` as ``[0 ‖ q2_i]`` (behind them zero heads where the stored
    pairs are padded: ``cfg.attend_heads``), times ``sqrt(2)`` in float32 before the one
    cast (a call's own ``1 / sqrt(2D)`` then comes to ``1 / sqrt(D)``); ``k``
    / ``v`` [..., Hkv / 2, 2D], neighbouring heads side by side, which is
    the projection's own row-major order: nothing moves. A ``cross`` layer
    has no K/V of its own: ``k`` and ``v`` are None."""
    f32 = jnp.float32
    q = jnp.einsum("...e,ehd->...hd", h, layer["wq"], preferred_element_type=f32)
    if cfg.attention_bias:
        q = q + layer["bq"].astype(f32)
    q = (q * math.sqrt(2.0)).astype(h.dtype)
    pair = q.reshape(*q.shape[:-2], q.shape[-2] // 2, 2, q.shape[-1])
    zero = jnp.zeros_like(pair[..., 0, :])
    q = jnp.stack(
        [jnp.concatenate([pair[..., 0, :], zero], axis=-1), jnp.concatenate([zero, pair[..., 1, :]], axis=-1)], axis=-2
    ).reshape(*q.shape[:-1], 2 * q.shape[-1])

    def filled(x, heads):  # zero heads behind the real ones, where the stored pairs are padded (`cache_kv_heads`)
        return x if x.shape[-2] == heads else jnp.pad(x, [(0, 0)] * (x.ndim - 2) + [(0, heads - x.shape[-2]), (0, 0)])

    q = filled(q, cfg.attend_heads)
    if "wk" not in layer:
        return q, None, None

    def stored(w, b):
        out = jnp.einsum("...e,ehd->...hd", h, layer[w], preferred_element_type=f32)
        if cfg.attention_bias:
            out = out + layer[b].astype(f32)
        return filled(out.astype(h.dtype).reshape(*out.shape[:-2], cfg.kv_heads // 2, cfg.cache_head_dim), cfg.cache_kv_heads)

    return q, stored("wk", "bk"), stored("wv", "bv")


def _diff_out(cfg: DecoderConfig, li: int, layer, ctx):
    """The epilogue of a differential layer behind its attention call:
    ``ctx`` [..., H, 2D] holds ``a1_i`` at head ``2i`` and ``a2_i`` at ``2i +
    1``; ``o_i = (1 - lambda_init) RMSNorm_2D(a1_i - lambda a2_i; g)``, the
    ``H / 2`` x ``2D`` read as ``H`` x ``D`` into ``W_o``. Lambda and the norm
    in float32."""
    f32 = jnp.float32
    init = lambda_init(li)
    lam = (jnp.exp(jnp.sum(layer["lambda_q1"] * layer["lambda_k1"])) - jnp.exp(jnp.sum(layer["lambda_q2"] * layer["lambda_k2"])) + init)
    ctx = ctx[..., : cfg.num_heads, :]  # (the padded pairs' groups, where there are any, are dropped)
    pair = ctx.astype(f32).reshape(*ctx.shape[:-2], ctx.shape[-2] // 2, 2, ctx.shape[-1])
    o = (1.0 - init) * _rms(pair[..., 0, :] - lam * pair[..., 1, :], layer["subln_g"].astype(f32), cfg.norm_eps)
    o = o.astype(ctx.dtype).reshape(*ctx.shape[:-2], ctx.shape[-2], ctx.shape[-1] // 2)
    out = _mm("...hd,hde->...e", o, layer["wo"])
    return out + layer["bo"] if cfg.attention_bias else out


def _mamba(cfg: DecoderConfig, layer, h, live, si: int, window: Callable, scan: Callable):
    """A Mamba-1 layer's mixer of its normed input ``h`` ([B, S, E], or [B,
    E] for a decode step's one position): ``[x, z] = W_in h``; ``x <-
    silu(conv1d_causal(x) + b)`` (depthwise, kernel K, float32); ``[delta,
    B, C] = W_x x``; ``dt = softplus(W_dt delta + b_dt)``, ``A =
    -exp(A_log)`` [D, N]; the recurrence (ops/ssm.py ``selective_*``)
    through ``scan``; ``y <- y + D x``; ``out = W_out (y * silu(z))``.
    Returns ``out`` and ``y`` (before the gate: the MEMORY a ``gmu`` layer
    reads, float32). Rows that are not ``live`` get ``dt = 0`` and ``x =
    0``: the state passes them unchanged. ``window`` and ``scan`` as
    :func:`_ssm` takes them; ``dt``, the decay, the recurrence, the skip and
    the gate are float32."""
    one = h.ndim == 2
    if one:
        h, live = h[:, None], live[:, None]
    di, n, r = cfg.ssm_inner, cfg.ssm_state_size, cfg.dt_rank
    proj = _mm("...e,ef->...f", h, layer["ssm_in"])
    x, z = proj[..., :di], proj[..., di:]
    xpad = window(si, x)  # [B, S + K - 1, D]
    with jax.named_scope("ssm.conv"):
        x = jnp.where(live[..., None], _ssm_conv(layer, xpad, x.shape[1]).astype(h.dtype), 0)
    dbc = _mm("...f,fr->...r", x, layer["ssm_x"])
    delta, b, c = dbc[..., :r], dbc[..., r : r + n], dbc[..., r + n :]
    dt = jnp.einsum("...r,rf->...f", delta, layer["ssm_dt_w"], preferred_element_type=jnp.float32)
    dt = jnp.where(live[..., None], jax.nn.softplus(dt + layer["ssm_dt_bias"]), 0.0)
    y = scan(si, x, dt, -jnp.exp(layer["ssm_a_log"]), b, c)  # [B, S, D] float32
    y = y + layer["ssm_d"] * x.astype(jnp.float32)
    out = _mm("...f,fe->...e", (y * jax.nn.silu(z.astype(jnp.float32))).astype(h.dtype), layer["ssm_out"])
    return (out[:, 0], y[:, 0]) if one else (out, y)


def _gmu(layer, h, memory):
    """A Gated Memory Unit: ``W_2 (m * silu(W_1 h))``, ``m`` the memory
    layer's scan output at the same row (float32), the gate in float32."""
    gate = jnp.einsum("...e,ef->...f", h, layer["gmu_in"], preferred_element_type=jnp.float32)
    return _mm("...f,fe->...e", (memory * jax.nn.silu(gate)).astype(h.dtype), layer["gmu_out"])


def _layers(
    cfg: DecoderConfig,
    params: DecoderParams,
    x,
    positions,
    live,
    attend: Callable,
    convolve: Callable,
    counts: Optional[List] = None,
    recur: Optional[Tuple[Callable, Callable]] = None,
    span: Optional[Tuple[int, int]] = None,
    memory=None,
):
    """THE block definition: every layer of ``params`` applied to ``x``
    ([..., E]; ``positions`` and ``live`` over its leading axes). What
    differs between the four forwards is where the operators' context
    comes from, and that is all the two callbacks hold:

    * ``attend(ai, q, k, v)`` -> the attention context of the ``ai``-th
      attention layer for its projected (normed, rotated) q, k, v;
    * ``convolve(ci, z)`` -> the padded rows (:func:`conv_window`) of the
      ``ci``-th convolution layer behind its gated input ``z``;
    * ``recur = (window, scan)`` (a configuration with ssm layers):
      ``window(si, xbc)`` -> the padded rows of the ``si``-th ssm layer's
      convolution behind its projected ``[x, B, C]``, and ``scan(si, x,
      dt, a, b, c)`` -> the recurrence's ``y`` (:func:`_ssm`).

    Scope names land in the instructions' op_name, so a device trace can
    be grouped by them: ``layer<i>/attention | cache_write | conv |
    conv_state | mlp | router | experts | shared_expert`` (``shared_experts``
    where there are several; a shortcut branch's held experts' sum and the
    add that lands it are ``experts.shortcut``, the identity experts' term
    inside it ``experts.zero``); a parallel block's norm and its one sum are
    ``block.parallel``; a configuration
    with window layers names the two kinds apart, ``attention.window``
    and ``attention.full`` (:func:`attention_scope`); a latent layer is
    ``attention.latent``, its attention proper ``attention.latent.expand``
    or ``attention.latent.absorb`` by the form the forward runs; a ``mamba``
    layer is ``ssm`` (``ssm.conv`` inside), a ``gmu`` layer ``gmu``, a
    ``cross`` layer ``attention.cross``.

    ``span = (lo, hi)``: the layers ``lo <= l < hi`` alone (a prefill runs
    the cross-decoder, ``cfg.cross_from`` on, over other rows than the
    rest). Returns ``x`` and the MEMORY: the scan output of layer
    ``cfg.memory_source`` where the span held it, else ``memory`` as given
    (what the ``gmu`` layers of the span read)."""
    lo, hi = span or (0, cfg.num_layers)
    ai, ci, si = (sum(l < lo for l in ls) for ls in (cfg.attention_layers, cfg.conv_layers, cfg.ssm_layers))
    for li in range(lo, hi):
        layer = params["layers"][li]
        with jax.named_scope(f"layer{li}"):
            kind = cfg.operator(li)
            if cfg.block == "single":
                # ONE norm and ONE branch: the state-space mixer, attention or the feed-forward
                if kind == "ssm":
                    with jax.named_scope("ssm"):
                        x = x + _ssm(cfg, layer, _norm(cfg, x, layer, "ln1"), live, si, *recur)
                    si += 1
                elif kind == "ffn":
                    with jax.named_scope("router"):
                        n = _norm(cfg, x, layer, "ln1")
                    x = x + _ffn(cfg, li, layer, x, live, counts, normed=n)
                else:
                    with jax.named_scope("attention"):
                        q, k, v = _qkv(cfg, layer, _norm(cfg, x, layer, "ln1"), positions, kind)
                    ctx = attend(ai, q, k, v)
                    with jax.named_scope("attention"):
                        x = x + _mm("...hd,hde->...e", ctx, layer["wo"])
                    ai += 1
                continue
            if kind == "latent":
                # the callback takes the position's cache row for k and the
                # layer's up-projection for v: it expands or absorbs
                with jax.named_scope("attention.latent"):
                    h = _norm(cfg, x, layer, "ln1")
                    q, row = _latent_qkv(cfg, layer, h, positions)
                ctx = attend(ai, q, row, layer["w_ukv"])
                with jax.named_scope("attention.latent"):
                    x = x + _mm("...hd,hde->...e", ctx, layer["wo"])
                ai += 1
            elif cfg.block == "parallel":
                # ONE norm; attention and the feed-forward read it and
                # neither reads the other: y = x + Attn(n) + FFN(n)
                scope = attention_scope(cfg, kind)
                with jax.named_scope("block.parallel"):
                    n = _norm(cfg, x, layer, "ln1")
                with jax.named_scope(scope):
                    q, k, v = _qkv(cfg, layer, n, positions, kind)
                ctx = attend(ai, q, k, v)
                with jax.named_scope(scope):
                    attended = _mm("...hd,hde->...e", ctx, layer["wo"])
                ai += 1
                fed = _ffn(cfg, li, layer, x, live, counts, normed=n)
                with jax.named_scope("block.parallel"):
                    x = x + attended + fed
                continue
            elif kind == "mamba":
                with jax.named_scope("ssm"):
                    out, scanned = _mamba(cfg, layer, _norm(cfg, x, layer, "ln1"), live, si, *recur)
                    x = x + out
                if li == cfg.memory_source:
                    memory = scanned  # handed to the gmu layers inside this forward; nothing of it is cached
                si += 1
            elif kind == "gmu":
                with jax.named_scope("gmu"):
                    x = x + _gmu(layer, _norm(cfg, x, layer, "ln1"), memory)
            elif cfg.differential:
                scope = attention_scope(cfg, kind)
                with jax.named_scope(scope):
                    q, k, v = _diff_qkv(cfg, layer, _norm(cfg, x, layer, "ln1"))
                ctx = attend(ai, q, k, v)
                with jax.named_scope(scope):
                    x = x + _diff_out(cfg, li, layer, ctx)
                ai += 1
            elif kind != "conv":
                scope = attention_scope(cfg, kind)
                with jax.named_scope(scope):
                    h = _norm(cfg, x, layer, "ln1")
                    q, k, v = _qkv(cfg, layer, h, positions, kind)
                ctx = attend(ai, q, k, v)
                with jax.named_scope(scope):
                    x = x + _mm("...hd,hde->...e", ctx, layer["wo"])
                ai += 1
            else:
                with jax.named_scope("conv"):
                    h = _norm(cfg, x, layer, "ln1")
                    b_, c_, x_ = jnp.split(_mm("...e,ef->...f", h, layer["conv_in"]), 3, axis=-1)
                    z = b_ * x_
                zpad = convolve(ci, z)
                with jax.named_scope("conv"):
                    t = zpad.shape[1] - layer["conv_w"].shape[1] + 1
                    c = _conv_mix(layer, zpad, t).reshape(z.shape)
                    x = x + _mm("...e,ef->...f", c_ * c, layer["conv_out"])
                ci += 1
            if not cfg.shortcut_experts:
                x = _ffn(cfg, li, layer, x, live, counts)
                continue
            # a dense feed-forward, and from the first sub-layer of a period a routed
            # branch off the same normed input, added behind the period's last
            with jax.named_scope("mlp"):
                u = _norm(cfg, x, layer, "ln2")
            if cfg.shortcut(li):
                branch = _shortcut(cfg, layer, u, live, counts)
            x = x + _ffn(cfg, li, layer, x, live, counts, normed=u)
            if cfg.shortcut(li + 1):  # (the period's last sub-layer)
                with jax.named_scope("experts.shortcut"):
                    x = x + branch
    return x, memory


def attention_scope(cfg: DecoderConfig, kind: str) -> str:
    """The scope an attention layer's operations are named under."""
    if kind == "cross":
        return "attention.cross"
    if not cfg.window_layers:
        return "attention"
    return "attention.window" if kind == "window" else "attention.full"


def _no_conv(ci, z):
    raise ValueError("a convolution layer needs its configuration: pass cfg")


# ---------------------------------------------------------------- forwards
def forward_full(
    params: DecoderParams,
    tokens: jax.Array,
    lengths: Optional[jax.Array] = None,
    cfg: Optional[TransformerConfig] = None,
) -> jax.Array:
    """Full-context causal forward: [B, S] int32 -> logits [B, S, V].
    ``lengths`` masks padded key positions (bucketed prompts)."""
    return prefill(params, tokens, lengths, cfg)[0]


def prefill(
    params: DecoderParams,
    tokens: jax.Array,
    lengths: Optional[jax.Array] = None,
    cfg: Optional[TransformerConfig] = None,
    counts: Optional[List] = None,
    backend: str = "cpu",
    head: bool = True,
    last_only: bool = False,
):
    """Prefill forward: logits [B, S, V] plus every attention layer's
    K/V ([n_attn, B, S, Hkv, D] each, both kinds in layer order:
    ``cfg.kv_index`` says which array each belongs in; latent layers:
    their rows [n, B, S, RW] and a V of no width) for the engine to
    write into the cache and, for a configuration with convolution layers, a fourth
    result: their padded ``z`` rows [n_conv, B, S + K - 1, E]; for one
    with ssm layers the fourth result is ``{"xbc": [n_ssm, B, S + K - 1,
    width], "state": [n_ssm, B, H, P, N]}``: their convolutions' padded
    input rows, and each sequence's state after its own length.
    ``head`` False (a prefill that samples nothing: block diffusion's):
    the first result is the last layer's output [B, S, E] and the head,
    whose product over all S rows is the peak temporary of every other
    prefill, is not run. A Mamba-1 configuration's fourth result is
    ``{"xbc": [n, B, S + K - 1, D], "state": [n, B, D, N]}``. K/V comes
    back for the layers that STORE it (``cfg.stored_index``).

    ``last_only`` (a configuration with a cross-decoder, ``cfg.cross_from``
    < ``num_layers``): the layers from ``cfg.cross_from`` on, and the head,
    run on each sequence's LAST live row alone and the first result is [B,
    1, V]. They store nothing a later position reads (their K/V is the
    ``kv_source`` layer's, which the layers before have produced for every
    row; the memory is needed at that row alone), so that row's logits are
    the same numbers as the full forward's."""
    cfg = decoder_config(cfg) if cfg is not None else _config_of(params)
    b, s = tokens.shape
    lens = lengths if lengths is not None else jnp.full((b,), s, jnp.int32)
    positions = jnp.arange(s)[None, :]
    with jax.named_scope("embed"):
        x = _embed(cfg, params, tokens, positions)
    ks, vs, zs = [], [], []
    xs, finals = [], []  # the ssm layers' padded [x, B, C] rows, and their states after each sequence's length

    def window(si, xbc):
        with jax.named_scope("ssm.conv"):
            xs.append(conv_window(jnp.zeros((b, cfg.ssm_conv_kernel - 1, xbc.shape[-1]), xbc.dtype), xbc))
        return xs[-1]

    def scan(si, x_, dt, a, b_, c_):
        with jax.named_scope("ssm.scan"):
            form = ssm_ops.selective_scan if cfg.mamba_layers else ssm_ops.chunk_scan
            y, final = form(x_, dt, a, b_, c_, cfg.ssm_chunk)
        finals.append(final)
        return y

    stored = {}  # the storing layers' place in ks / vs, by (kind, index in that kind's arrays)
    tail = {"rows": False}  # the cross-decoder is on each sequence's last row alone

    def attend(ai, q, k, v):
        kind, at = cfg.kv_index[ai]
        if kind == "cross":
            src = stored[("attention", at)]
            with jax.named_scope("attention.cross"), jax.named_scope("prefill_attention"):
                if tail["rows"]:  # one query a sequence over the producer's rows: every live position is seen
                    return masked_attention(q, ks[src], vs[src], lens, causal=False)
                return prefill_attention(q, ks[src], vs[src], lens, backend=backend)
        stored[(kind, at)] = len(ks)
        if kind == "latent":
            ks.append(k)  # the rows, as stored; V has no width
            vs.append(k[..., :0])
            with jax.named_scope("attention.latent.expand"):
                return _expanded(cfg, q, k, v, lens, backend)
        ks.append(k)
        vs.append(v)
        with jax.named_scope(attention_scope(cfg, kind)), jax.named_scope("prefill_attention"):
            return prefill_attention(
                q, k, v, lens, window=cfg.window if kind == "window" else 0, backend=backend, block=cfg.block_mask
            )

    def convolve(ci, z):
        with jax.named_scope("conv_state"):
            zs.append(conv_window(jnp.zeros((b, cfg.conv_kernel - 1, z.shape[-1]), z.dtype), z))
        return zs[-1]

    live = positions < lens[:, None]
    if last_only and cfg.cross_from < cfg.num_layers:
        x, memory = _layers(cfg, params, x, positions, live, attend, convolve, counts, recur=(window, scan), span=(0, cfg.cross_from))
        with jax.named_scope("prefill.last_row"):
            last = jnp.maximum(lens - 1, 0)[:, None]  # [B, 1]
            x = jnp.take_along_axis(x, last[:, :, None], axis=1)
            memory = memory if memory is None else jnp.take_along_axis(memory, last[:, :, None], axis=1)
        tail["rows"] = True
        x, _ = _layers(
            cfg, params, x, last, jnp.ones_like(last, bool), attend, convolve, counts, span=(cfg.cross_from, cfg.num_layers), memory=memory,
        )
    else:
        x, _ = _layers(cfg, params, x, positions, live, attend, convolve, counts, recur=(window, scan))
    with jax.named_scope("head"):
        empty = jnp.zeros((0,), x.dtype)  # a configuration without attention layers
        out = (_head(cfg, params, x) if head else x, jnp.stack(ks) if ks else empty, jnp.stack(vs) if vs else empty)
    if xs:
        # (rows behind a sequence's length have dt = 0: `state` is the state AT its length)
        return out + ({"xbc": jnp.stack(xs), "state": jnp.stack(finals)},)
    return out + (jnp.stack(zs),) if zs else out


def write_rows(cache, layer: int, block, offset, rows):
    """Write a step's rows ([n, H, D]) of static ``layer`` at
    ``(block[n], offset[n])`` with ONE scatter on the whole [L,
    num_blocks, block_size, R, LW] operand (a position's H x D values
    stored row-major as R x LW: generation/cache.py; a latent layer's
    [n, RW] rows into [L, num_blocks, block_size, RW]). With the operand
    donated the scatter runs in place: the step touches n rows, not a
    layer. Taking ``cache[layer]`` out, or writing a rebuilt layer back,
    would make every step copy layer-sized values (ISSUE 24)."""
    rows = rows.reshape(rows.shape[0], *cache.shape[3:]).astype(cache.dtype)
    return cache.at[layer, block, offset].set(rows)


def decode_step(
    params: DecoderParams,
    tokens: jax.Array,
    positions: jax.Array,
    cache_k: jax.Array,
    cache_v: jax.Array,
    block_tables: jax.Array,
    context_lens: jax.Array,
    backend: str = "cpu",
    mesh=None,
    cfg: Optional[TransformerConfig] = None,
    conv: Optional[jax.Array] = None,
    counts: Optional[List] = None,
    window: Optional[Dict[str, jax.Array]] = None,
    ssm: Optional[Dict[str, jax.Array]] = None,
):
    """One decode step for every batch slot.

    tokens/positions: [B] int32 (the token being decoded and its cache
    position); cache_k/cache_v: [n_attn, num_blocks, block_size, R, LW]
    (the stored form of [..., Hkv, D], one entry per ATTENTION layer);
    block_tables: [B, max_blocks]; context_lens: [B] — valid cache
    positions INCLUDING this token (``positions + 1`` for live slots, 0
    for inactive ones, whose writes land in scratch block 0).
    ``conv``: [n_conv, B, K - 1, E], every slot's convolution state.
    Returns (logits [B, V], cache_k, cache_v) with the K/V written — the
    two arrays pass through whole (rows scattered in place when the
    caller donates them, the kernel reading blocks of the same arrays) —
    and, given ``conv``, a fourth result: the state shifted by this
    token for the live slots, written in place likewise.

    ``window`` (a configuration with window layers): ``{"k", "v"}`` the
    window layers' arrays [n_window, num_blocks_w, block_size, R, LW],
    ``"tables"`` [B, columns] the blocks each sequence still holds of
    them and ``"first"`` [B] the cache position of column 0
    (generation/cache.py); ``cache_k`` / ``cache_v`` / ``block_tables``
    are then the full layers' alone. The last result is then ``{"k",
    "v"}``, the window layers' arrays with the token's rows written.

    ``ssm`` (a configuration with ssm layers): ``{"ssm_conv": [n_ssm, B, K
    - 1, width], "ssm": [n_ssm, B, H / pack, N, lanes]}``, every slot's
    convolution rows and stored recurrent state (ops/ssm.py) under the
    names the cache keeps them by. The fourth
    result is then the same dict after this token: the live slots' rows
    shifted, their state updated in ONE pass a layer (on a TPU the Pallas
    call ``ssm_state_update``, in place when the caller donates); a slot
    that is not live keeps both, bit for bit.
    """
    cfg = decoder_config(cfg) if cfg is not None else _config_of(params)
    bs = cache_k.shape[2]
    state = {"k": cache_k, "v": cache_v, "conv": conv}
    if ssm is not None:
        state["ssm"] = ssm["ssm"]
    live = context_lens > 0
    with jax.named_scope("embed"):
        x = _embed(cfg, params, tokens, positions)  # [B, E]
        block, offset = jax.vmap(lambda bt, p: slot_mapping(bt, p, bs))(block_tables, positions)
        if window is not None:
            state.update(wk=window["k"], wv=window["v"])
            wblock, woffset = jax.vmap(lambda bt, p: slot_mapping(bt, p, bs))(
                window["tables"], positions - window["first"]
            )

    def attend(ai, q, k, v):
        # write this token's K/V, then attend over the updated cache
        # so the token sees itself (context_lens includes it)
        kind, at = cfg.kv_index[ai]
        if kind == "cross":  # the producer layer has written this token's K/V already; nothing is written here
            with jax.named_scope("attention.cross"):
                return decode_attention_core(q, state["k"], state["v"], at, block_tables, context_lens, backend=backend, mesh=mesh)
        if kind == "latent":
            with jax.named_scope("cache_write"):
                state["k"] = write_rows(state["k"], at, block, offset, k)
            with jax.named_scope("attention.latent.absorb"):
                return _absorbed(cfg, q[:, None], v, state["k"], at, block_tables, context_lens[:, None] - 1, backend)[:, 0]
        # a window layer's arrays, table and bounds, or the full layers'
        kk, vv, tables, blk, off, bounds = ("k", "v", block_tables, block, offset, {}) if kind != "window" else (
            "wk", "wv", window["tables"], wblock, woffset, {"window": cfg.window, "first_positions": window["first"]})
        with jax.named_scope("cache_write"):
            state[kk] = write_rows(state[kk], at, blk, off, k)
            state[vv] = write_rows(state[vv], at, blk, off, v)
        with jax.named_scope(attention_scope(cfg, kind)):
            return decode_attention_core(
                q, state[kk], state[vv], at, tables, context_lens,
                backend=backend, mesh=mesh, **bounds,
            )

    def convolve(ci, z):
        with jax.named_scope("conv_state"):
            old = state["conv"][ci]
            zpad = conv_window(old, z[:, None])
            new = jnp.where(live[:, None, None], zpad[:, 1:], old)
            state["conv"] = state["conv"].at[ci].set(new.astype(old.dtype))
        return zpad

    conv_rows = []  # every ssm layer's convolution rows after this token

    def ssm_window(si, xbc):
        with jax.named_scope("ssm.conv"):
            old = ssm["ssm_conv"][si]
            xpad = conv_window(old, xbc)
            conv_rows.append(jnp.where(live[:, None, None], xpad[:, 1:], old).astype(old.dtype))
        return xpad

    def ssm_scan(si, x_, dt, a, b_, c_):
        with jax.named_scope("ssm.update"):
            step = ssm_ops.selective_update if cfg.mamba_layers else ssm_ops.update
            y, state["ssm"] = step(state["ssm"], si, x_[:, 0], dt[:, 0], a, b_[:, 0], c_[:, 0], backend=backend)
        return y[:, None]

    x, _ = _layers(
        cfg, params, x, positions, live, attend, convolve if conv is not None else _no_conv, counts,
        recur=(ssm_window, ssm_scan) if ssm is not None else None,
    )
    with jax.named_scope("head"):
        out = (_head(cfg, params, x), state["k"], state["v"])
    if conv is not None:
        out += (state["conv"],)
    if ssm is not None:
        # the rows are written ONCE, from the array the step was given: updated layer by layer in place
        # (`.at[si].set`), the TPU compiler rematerialised a layer's old rows AFTER their update under
        # memory pressure, and from 176 slots on every sequence's rows were shifted twice a step (PR 48)
        out += ({"ssm_conv": jnp.stack(conv_rows), "ssm": state["ssm"]},)
    return out if window is None else out + ({"k": state["wk"], "v": state["wv"]},)


def verify_step(
    params: DecoderParams,
    tokens: jax.Array,
    positions: jax.Array,
    cache_k: jax.Array,
    cache_v: jax.Array,
    block_tables: jax.Array,
    backend: str = "cpu",
    mesh=None,
    cfg: Optional[TransformerConfig] = None,
    conv_in: Optional[jax.Array] = None,
    counts: Optional[List] = None,
    window: Optional[Dict[str, jax.Array]] = None,
    attend_positions: Optional[jax.Array] = None,
    head: bool = True,
):
    """One chunked-append (speculative verification) step for every
    batch slot.

    tokens/positions: [B, W] int32 — the window being scored (the last
    committed token followed by up to W-1 drafted tokens) and each
    window token's cache position. ``positions < 0`` marks padding
    window slots (fixed-shape windows with fewer real drafts): their
    K/V scatter to scratch block 0 and their attention/logits rows are
    meaningless (the caller's acceptance logic never reads them).
    cache_k/cache_v: [n_attn, num_blocks, block_size, R, LW];
    block_tables: [B, max_blocks]. Returns (logits [B, W, V], cache_k,
    cache_v) with
    all W tokens' K/V written — accepted positions hold exactly the K/V
    sequential decode would have written (a window token's K/V depends
    only on its prefix, which is valid up to the first rejection);
    rejected/later positions hold garbage that the next window
    overwrites before any masked read can see it.

    ``conv_in`` ([n_conv, B, K - 1, E]) is the convolution state the
    window continues from (a suffix prefill behind a prefix hit: the
    real tokens are the window's first, padding after them). A fourth
    result then holds the layers' padded ``z`` rows [n_conv, B,
    W + K - 1, E], as :func:`prefill` returns them. ``window`` as in
    :func:`decode_step`, and the window layers' arrays the last result.

    ``attend_positions`` ([B, W]; None: ``positions``): the last cache
    position each row attends, where that is not its own (a block of
    rows that see each other: :func:`block_step`; the row is still
    rotated and written at ``positions``). ``head`` False: the first
    result is the last layer's output [B, W, E], no logits.
    """
    cfg = decoder_config(cfg) if cfg is not None else _config_of(params)
    if cfg.cross_layers:
        raise NotImplementedError(
            "an append window over cross layers (speculative verification, a prefix hit's suffix prefill) is not written "
            "down: they are served beside state-space layers, whose every row's state would have to be kept"
        )
    if cfg.ssm_layers:
        raise NotImplementedError(
            "an append window over ssm layers (speculative verification, a prefix hit's suffix prefill) is not "
            "written down: each row's recurrent state would have to be kept to choose one at the accepted length"
        )
    bs = cache_k.shape[2]
    state = {"k": cache_k, "v": cache_v}
    zs = []
    attended = positions if attend_positions is None else attend_positions

    def slots(tables, pos):
        block, offset = jax.vmap(lambda bt, p: slot_mapping(bt, p, bs))(tables, pos)
        # padding -> scratch block 0, offset 0
        return jnp.where(positions >= 0, block, 0).reshape(-1), jnp.where(positions >= 0, offset, 0).reshape(-1)

    with jax.named_scope("embed"):
        safe_pos = jnp.maximum(positions, 0)
        x = _embed(cfg, params, tokens, safe_pos)  # [B, W, E]
        block, offset = slots(block_tables, safe_pos)
        if window is not None:
            state.update(wk=window["k"], wv=window["v"])
            wblock, woffset = slots(window["tables"], jnp.maximum(positions - window["first"][:, None], 0))

    def attend(ai, q, k, v):
        # write the whole window's K/V, then attend over the updated
        # cache with per-query position masks (each token sees itself
        # and everything before it, nothing after)
        kind, at = cfg.kv_index[ai]
        if kind == "latent":
            with jax.named_scope("cache_write"):
                state["k"] = write_rows(state["k"], at, block, offset, k.reshape(-1, k.shape[-1]))
            with jax.named_scope("attention.latent.absorb"):
                return _absorbed(cfg, q, v, state["k"], at, block_tables, attended, backend)
        kk, vv, tables, blk, off, bounds = ("k", "v", block_tables, block, offset, {}) if kind != "window" else (
            "wk", "wv", window["tables"], wblock, woffset, {"window": cfg.window, "first_positions": window["first"]})
        with jax.named_scope("cache_write"):
            state[kk] = write_rows(state[kk], at, blk, off, k.reshape(-1, *k.shape[2:]))
            state[vv] = write_rows(state[vv], at, blk, off, v.reshape(-1, *v.shape[2:]))
        with jax.named_scope(attention_scope(cfg, kind)):
            return append_attention_core(
                q, state[kk], state[vv], at, tables, attended,
                backend=backend, mesh=mesh, **bounds,
            )

    def convolve(ci, z):
        with jax.named_scope("conv_state"):
            zs.append(conv_window(conv_in[ci], z))
        return zs[-1]

    x, _ = _layers(
        cfg, params, x, safe_pos, positions >= 0, attend,
        convolve if conv_in is not None else _no_conv, counts,
    )
    with jax.named_scope("head"):
        out = (_head(cfg, params, x) if head else x, state["k"], state["v"])
    if zs:
        out += (jnp.stack(zs),)
    return out if window is None else out + ({"k": state["wk"], "v": state["wv"]},)


def block_positions(base, active, block: int):
    """A block step's two position arrays from each slot's block base
    ([B] int32) and its 0 / 1 ``active`` mask: the rows' own positions
    ``base + 0 .. block - 1`` and their attend bound, the block's last
    position for every row; -1 (padding) in an inactive slot."""
    own = base[:, None] + jnp.arange(block, dtype=jnp.int32)[None, :]
    live = active[:, None] > 0
    return jnp.where(live, own, -1), jnp.where(live, jnp.broadcast_to(base[:, None] + block - 1, own.shape), -1)


def block_step(
    params: DecoderParams,
    tokens: jax.Array,
    fixed: jax.Array,
    base: jax.Array,
    active: jax.Array,
    mask_token: int,
    cache_k: jax.Array,
    cache_v: jax.Array,
    block_tables: jax.Array,
    backend: str = "cpu",
    mesh=None,
    cfg: Optional[TransformerConfig] = None,
    counts: Optional[List] = None,
):
    """One block-diffusion forward for every batch slot: the block of
    ``B = cfg.block_mask`` positions from ``base`` ([slots]) on, rows
    that are ``fixed`` ([slots, B] bool) embedded as their ``tokens``
    and the others as ``mask_token``. The rows' K/V is written at the
    block's positions (as :func:`verify_step` writes a window's: the
    same function, given the attend bound ``base + B - 1`` for every
    row), each row rotated at its own position and attending every
    earlier block's kept K/V and all ``B`` rows of this one. Returns
    (logits [slots, B, V], cache_k, cache_v): the logits at a row are
    the distribution of the token AT that row. A forward over a block
    whose rows are all fixed is its commit: what it writes is the K/V
    later blocks read."""
    cfg = decoder_config(cfg) if cfg is not None else _config_of(params)
    positions, attended = block_positions(base, active, cfg.block_mask)
    return verify_step(
        params, jnp.where(fixed, tokens, mask_token), positions, cache_k, cache_v, block_tables,
        backend=backend, mesh=mesh, cfg=cfg, counts=counts, attend_positions=attended,
    )
