"""MultiHeadAttention operator.

Reference: src/ops/attention.cc (926 LoC) lowering to a monolithic
``cudnnMultiHeadAttnForward`` (src/ops/attention.cu:35) with qkv+output
projection weights woven into one tensor. TPU-native: explicit q/k/v/o
projections (MXU matmuls) around a fused attention core — a Pallas
flash-attention kernel on the TPU backend (ops/kernels/flash_attention.py)
and the einsum/softmax composition on the CPU backend. On a TPU a shape
a kernel's gate refuses also takes the composition, and says so once in
the log. Unlike the
reference (no causal masking, no long-context support at all — SURVEY
§2.2), this op supports causal masks and, via the strategy layer,
sequence-parallel ring attention.
"""
from __future__ import annotations

import dataclasses
import functools
import logging
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp

from ..core.tensor import TensorSpec
from ..core.types import DataType, OpType
from ..device import on_tpu
from .base import LowerCtx, OpCost, OpDef, WeightSpec, io_cost, register_op
from .kernels.decode_attention import (
    default_kv_splits,
    kernel_body,
    latent_columns_per_step,
    latent_kernel_refusal,
    paged_append_attention,
    paged_decode_attention,
    paged_kernel_refusal,
    paged_latent_attention,
    paged_walk,
    query_group,
    reference_paged_append_attention,
    reference_paged_attention,
    reference_paged_latent_attention,
    sharded_paged_append_attention,
    sharded_paged_decode_attention,
)
from .kernels.flash_attention import (
    flash_attention,
    flash_attention_sharded,
    prefill_stream_attention,
    prefill_stream_refusal,
    reference_prefill_stream_attention,
    supports_shapes,
)

_log = logging.getLogger(__name__)


@functools.lru_cache(maxsize=None)
def _note_refusal(kernel: str, reason: str) -> None:
    """One log line per distinct refusal: which kernel a TPU program is
    NOT running, and why (traced once per program, so this is quiet)."""
    _log.warning("%s refused on the TPU backend, using the XLA composition: %s", kernel, reason)


@dataclasses.dataclass(frozen=True)
class MultiHeadAttentionParams:
    embed_dim: int
    num_heads: int
    kdim: int = 0  # 0 -> embed_dim // num_heads
    vdim: int = 0
    dropout: float = 0.0
    use_bias: bool = False  # reference: bias flag
    causal: bool = False  # new capability (absent in reference)
    dtype: DataType = DataType.FLOAT

    @property
    def head_dim(self) -> int:
        return self.kdim or self.embed_dim // self.num_heads

    @property
    def v_head_dim(self) -> int:
        return self.vdim or self.embed_dim // self.num_heads


@register_op
class MultiHeadAttentionOp(OpDef):
    op_type = OpType.MULTIHEAD_ATTENTION
    params_cls = MultiHeadAttentionParams

    @staticmethod
    def infer_output_specs(params: MultiHeadAttentionParams, input_specs: List[TensorSpec]):
        q = input_specs[0]
        return [TensorSpec(q.shape[:-1] + (params.embed_dim,), params.dtype)]

    @staticmethod
    def weight_specs(params: MultiHeadAttentionParams, input_specs: List[TensorSpec]) -> List[WeightSpec]:
        q, k, v = input_specs
        h, dk, dv, e = params.num_heads, params.head_dim, params.v_head_dim, params.embed_dim
        dt = params.dtype
        ws = [
            WeightSpec("wq", TensorSpec((q.shape[-1], h, dk), dt), "glorot_uniform"),
            WeightSpec("wk", TensorSpec((k.shape[-1], h, dk), dt), "glorot_uniform"),
            WeightSpec("wv", TensorSpec((v.shape[-1], h, dv), dt), "glorot_uniform"),
            WeightSpec("wo", TensorSpec((h, dv, e), dt), "glorot_uniform"),
        ]
        if params.use_bias:
            ws += [
                WeightSpec("bq", TensorSpec((h, dk), dt), "zeros"),
                WeightSpec("bk", TensorSpec((h, dk), dt), "zeros"),
                WeightSpec("bv", TensorSpec((h, dv), dt), "zeros"),
                WeightSpec("bo", TensorSpec((e,), dt), "zeros"),
            ]
        return ws

    @staticmethod
    def lower(params: MultiHeadAttentionParams, inputs, weights, ctx: LowerCtx):
        q, k, v = inputs
        # projections: [B, S, E] x [E, H, D] -> [B, S, H, D]
        qh = jnp.einsum("bse,ehd->bshd", q, weights["wq"])
        kh = jnp.einsum("bse,ehd->bshd", k, weights["wk"])
        vh = jnp.einsum("bse,ehd->bshd", v, weights["wv"])
        if params.use_bias:
            qh = qh + weights["bq"]
            kh = kh + weights["bk"]
            vh = vh + weights["bv"]
        cp_axis = getattr(ctx, "cp_axis", None)
        mesh = getattr(ctx, "mesh", None)
        seq_cp = (
            mesh is not None
            and "seq" in mesh.axis_names
            and mesh.shape["seq"] > 1
            and qh.shape[1] % mesh.shape["seq"] == 0
            # cross-attention: K/V carry their OWN sequence length (the
            # encoder side), which must also divide or the kernel's
            # in_specs reject it at trace time — fall back to dense
            and kh.shape[1] % mesh.shape["seq"] == 0
        )
        if cp_axis is not None and getattr(ctx, "kv_seq_replicated", False):
            # pp x cp cross-attention whose shared K/V seq dim couldn't
            # shard: K/V are FULL-LENGTH on every cp shard, so dense
            # attention over the local complete memory gives the exact
            # result — a ring over cp identical copies computes the same
            # softmax at cp x the FLOPs plus cp-1 full-size ppermutes
            # (ADVICE r4)
            if params.causal:
                raise ValueError(
                    "pp x cp: causal attention over cp-replicated K/V has "
                    "no well-defined local mask; use a seq length divisible "
                    "by cp or drop cp"
                )
            ctx_out = attention_core(qh, kh, vh, causal=False, backend=ctx.backend)
        elif cp_axis is not None:
            # manual context parallelism (inside a pipeline stage's
            # shard_map): the sequence dim of q/k/v is sharded over
            # cp_axis — K/V ride the ring (pp x cp composition); shares
            # the projection/bias/dropout tail below
            from .kernels.ring_attention import ring_attention

            ctx_out = ring_attention(
                qh, kh, vh, axis_name=cp_axis, causal=params.causal
            )
        elif seq_cp:
            # context parallelism: sequence dim sharded on the "seq" axis,
            # K/V ride the ICI ring (new capability; reference has none).
            # cp x tp: Megatron-sharded projections keep their heads on
            # "model" through the kernel instead of re-gathering
            from .kernels.ring_attention import ring_attention_sharded

            head_axis = (
                "model"
                if (
                    "model" in mesh.axis_names
                    and mesh.shape["model"] > 1
                    and qh.shape[2] % mesh.shape["model"] == 0
                )
                else None
            )
            ctx_out = ring_attention_sharded(
                qh, kh, vh, mesh, seq_axis="seq", causal=params.causal,
                head_axis=head_axis,
            )
        else:
            ctx_out = attention_core(
                qh, kh, vh, causal=params.causal, backend=ctx.backend, mesh=mesh
            )
        out = jnp.einsum("bshd,hde->bse", ctx_out, weights["wo"])
        # manual tensor parallelism (inside shard_map — GPipe stages):
        # head-sharded wq/wk/wv make ctx_out carry H/tp local heads and
        # wo sharded on H is row-parallel — reduce the partial output
        # projections over the tp axis before the (replicated) bias
        if ctx.weight_sharded_dim("wo") == 0:
            out = jax.lax.psum(out, ctx.tp_axis)
        if params.use_bias:
            out = out + weights["bo"]
        if params.dropout > 0.0 and ctx.training:
            keep = 1.0 - params.dropout
            # per-shard key: every manual shard (seq and/or data) must
            # draw an INDEPENDENT mask — one shared key would repeat the
            # pattern every S/cp positions and across batch shards
            key = ctx.shard_rng()
            mask = jax.random.bernoulli(key, keep, out.shape)
            out = jnp.where(mask, out / keep, 0.0).astype(out.dtype)
        return [out.astype(params.dtype.jnp)]

    @staticmethod
    def cost(params: MultiHeadAttentionParams, input_specs, output_specs) -> OpCost:
        q, k, v = input_specs
        b, sq = q.shape[0], q.shape[1]
        sk = k.shape[1]
        h, dk, dv, e = params.num_heads, params.head_dim, params.v_head_dim, params.embed_dim
        proj = 2.0 * b * (sq * q.shape[-1] * h * dk + sk * k.shape[-1] * h * dk + sk * v.shape[-1] * h * dv + sq * h * dv * e)
        core = 2.0 * b * h * sq * sk * (dk + dv)
        w_elems = q.shape[-1] * h * dk + k.shape[-1] * h * dk + v.shape[-1] * h * dv + h * dv * e
        w_bytes = w_elems * params.dtype.size_bytes
        c = io_cost(input_specs, output_specs, flops=proj + core, extra_mem=w_bytes)
        c.bytes_accessed += w_bytes
        return c


def attention_core(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    causal: bool = False,
    backend: str = "tpu",
    scale: Optional[float] = None,
    mesh=None,
) -> jax.Array:
    """Scaled dot-product attention over [B, S, H, D] tensors.

    The Pallas flash-attention kernel on the TPU backend, the XLA einsum
    composition on the CPU backend (and for shapes the kernel refuses).
    ``mesh`` is the GSPMD mesh the caller's operands are sharded over
    (None inside a manual shard_map region): with more than one device
    the kernel runs per shard (:func:`flash_attention_sharded`).
    """
    if scale is None:
        scale = q.shape[-1] ** -0.5
    if backend == "tpu" and on_tpu():
        if supports_shapes(q.shape, k.shape):
            if mesh is not None and mesh.size > 1:
                return flash_attention_sharded(q, k, v, mesh, causal=causal, scale=scale)
            return flash_attention(q, k, v, causal=causal, scale=scale)
        _note_refusal("flash_attention", f"q{tuple(q.shape)} k{tuple(k.shape)}")
    return reference_attention(q, k, v, causal=causal, scale=scale)


def _paged_kernel_tp(kernel, backend, mesh, head_axis, num_heads, head_dim, cache, window):
    """Head shards to run the paged kernel over (1 = unsharded), or 0
    for the XLA reference: the CPU backend, or a shape the kernel's gate
    refuses — which on a TPU is logged."""
    if backend != "tpu" or not on_tpu():
        return 0
    tp = 1 if mesh is None else int(dict(mesh.shape).get(head_axis, 1))
    # grouped queries: the cache holds the K/V heads, and each of a
    # group's query heads is one more window query of the kernel
    kv_heads = cache.shape[3] * cache.shape[4] // head_dim
    group = max(1, num_heads // kv_heads)
    if num_heads % tp or kv_heads % tp:
        reason = f"{num_heads} heads over {kv_heads} K/V heads do not divide over {tp} shards"
    else:
        reason = paged_kernel_refusal(
            kv_heads // tp, head_dim, cache.shape[2], window * group, cache.dtype.itemsize, group=group
        )
    if reason is not None:
        _note_refusal(kernel, reason)
        return 0
    return tp


def paged_call_lowering(
    num_heads, head_dim, cache, backend="tpu", mesh=None, head_axis="model", window=1, *, batch: int, max_blocks: int
):
    """What a paged call of ``num_heads`` query heads over ``cache``
    ([L, num_blocks, block_size, R, LW]; an array or its shape and
    dtype) lowers to, as :func:`decode_attention_core` will decide it
    for ``batch`` sequences over tables of ``max_blocks`` columns:
    ``{"body", "group", "columns_per_step", "grid_steps",
    "walk_steps_at_most"}``. The body is
    the kernel's for the group the shapes show (``"mxu"`` grouped,
    ``"vpu"`` plain multi-head: kernels/decode_attention.py
    ``kernel_body``), or ``"reference"``, the XLA composition, on the
    CPU backend and where the gate refuses; the walk is the kernel's
    (``paged_walk``: table columns a step of the walk, the grid steps of a
    call, the steps its walk takes at most); the composition walks no
    grid and has ``{"body", "group"}`` alone."""
    group = query_group(num_heads, head_dim, cache.shape[3:])
    tp = _paged_kernel_tp("paged_decode_attention", backend, mesh, head_axis, num_heads, head_dim, cache, window)
    if not tp:
        return {"body": "reference", "group": group}
    splits = default_kv_splits(batch, max_blocks) if window == 1 else 1  # the decode call's own rule
    walk = paged_walk(
        num_heads // group // tp, head_dim, cache.shape[2], window * group, cache.dtype.itemsize, group, batch, max_blocks, splits
    )
    return {"body": kernel_body(group), "group": group, **walk}


def _windowed(tp: int, kernel, reference):
    """The lowering of a sliding-window layer's paged call: the kernel
    on one device, the XLA composition where the kernel is refused. The
    head-sharded kernel does not carry the window (the engine refuses
    ``tp_degree > 1`` for such a configuration by name)."""
    if tp > 1:
        raise NotImplementedError("the head-sharded paged kernel carries no attention window")
    return kernel if tp == 1 else reference


def decode_attention_core(
    q: jax.Array,
    k_cache: jax.Array,
    v_cache: jax.Array,
    layer: int,
    block_tables: jax.Array,
    context_lens: jax.Array,
    backend: str = "tpu",
    scale: Optional[float] = None,
    mesh=None,
    head_axis: str = "model",
    window: int = 0,
    first_positions: Optional[jax.Array] = None,
) -> jax.Array:
    """Decode-mode attention: one query token per sequence ([B, H, D])
    over static ``layer`` of the whole block-structured KV cache
    ([L, num_blocks, block_size, R, LW], the stored form of [..., H, D]:
    never a sliced-out layer) with
    position masking, so incremental decode reproduces full-context
    causal logits.

    The Pallas paged-attention kernel on the TPU backend
    (kernels/decode_attention.py), the XLA gather + masked softmax
    composition on the CPU backend. ``mesh`` with a >1 ``head_axis``
    selects the HEAD-SHARDED kernel path (ISSUE 15): each shard's kernel
    runs over its local KV heads via shard_map; the reference path needs
    no mesh plumb — GSPMD partitions the plain-XLA composition itself.

    ``window`` > 0 is a sliding-window layer's call: ``k_cache`` /
    ``v_cache`` are the window layers' arrays, ``block_tables`` holds the
    columns a sequence keeps and ``first_positions`` [B] the cache
    position of column 0 (kernels/decode_attention.py).
    """
    tp = _paged_kernel_tp(
        "paged_decode_attention", backend, mesh, head_axis,
        q.shape[1], q.shape[2], k_cache, 1,
    )
    if window:
        return _windowed(tp, paged_decode_attention, reference_paged_attention)(
            q, k_cache, v_cache, layer, block_tables, context_lens, scale=scale,
            window=window, first_positions=first_positions,
        )
    if tp > 1:
        return sharded_paged_decode_attention(
            q, k_cache, v_cache, layer, block_tables, context_lens,
            mesh, axis=head_axis, scale=scale,
        )
    if tp == 1:
        return paged_decode_attention(
            q, k_cache, v_cache, layer, block_tables, context_lens, scale=scale
        )
    return reference_paged_attention(
        q, k_cache, v_cache, layer, block_tables, context_lens, scale=scale
    )


def append_attention_core(
    q: jax.Array,
    k_cache: jax.Array,
    v_cache: jax.Array,
    layer: int,
    block_tables: jax.Array,
    q_positions: jax.Array,
    backend: str = "tpu",
    scale: Optional[float] = None,
    mesh=None,
    head_axis: str = "model",
    window: int = 0,
    first_positions: Optional[jax.Array] = None,
) -> jax.Array:
    """Chunked-append attention: a W-token window per sequence
    ([B, W, H, D], K/V already written) over static ``layer`` of the
    whole block-structured KV cache. Query (b, w) attends cache positions ``<= q_positions[b, w]``
    — causal within the window, full history before it — so verifying a
    k+1-token speculative window in one forward reproduces the k+1
    sequential decode steps' logits exactly. ``q_positions < 0`` marks
    fixed-shape padding queries (they emit zeros). Decode-mode attention
    is the W = 1 special case.

    Dispatch as in :func:`decode_attention_core`; windows past the
    kernel's bound (suffix-prefill buckets) take the XLA composition.
    ``window`` / ``first_positions`` as in :func:`decode_attention_core`.
    """
    tp = _paged_kernel_tp(
        "paged_append_attention", backend, mesh, head_axis,
        q.shape[2], q.shape[3], k_cache, q.shape[1],
    )
    if window:
        return _windowed(tp, paged_append_attention, reference_paged_append_attention)(
            q, k_cache, v_cache, layer, block_tables, q_positions, scale=scale,
            window=window, first_positions=first_positions,
        )
    if tp > 1:
        return sharded_paged_append_attention(
            q, k_cache, v_cache, layer, block_tables, q_positions,
            mesh, axis=head_axis, scale=scale,
        )
    if tp == 1:
        return paged_append_attention(
            q, k_cache, v_cache, layer, block_tables, q_positions, scale=scale
        )
    return reference_paged_append_attention(
        q, k_cache, v_cache, layer, block_tables, q_positions, scale=scale
    )


def latent_call_lowering(num_heads: int, cache, backend: str = "tpu", window: int = 1, *, batch: int, max_blocks: int):
    """What a latent layer's paged call of ``window`` queries of
    ``num_heads`` heads over ``cache`` ([L, num_blocks, block_size, RW])
    lowers to, as :func:`latent_attention_core` will decide it for
    ``batch`` sequences over tables of ``max_blocks`` columns: ``{"body",
    "group", "columns_per_step", "grid_steps", "walk_steps_at_most"}``, the body ``"mxu"``
    (kernels/decode_attention.py ``paged_latent_attention``: every head
    reads the one row, a group of all of them; its walk
    ``latent_columns_per_step``) or ``"reference"``, the XLA composition
    (``{"body", "group"}`` alone), on the CPU backend and where the gate
    refuses."""
    if backend == "tpu" and on_tpu():
        reason = latent_kernel_refusal(window * num_heads, cache.shape[3], cache.shape[2], cache.dtype.itemsize)
        if reason is None:
            columns = latent_columns_per_step(max_blocks)
            steps = batch * (max_blocks // columns)
            return {"body": "mxu", "group": num_heads, "columns_per_step": columns, "grid_steps": steps, "walk_steps_at_most": steps}
        _note_refusal("paged_latent_attention", reason)
    return {"body": "reference", "group": num_heads}


def latent_attention_core(
    q: jax.Array,
    cache: jax.Array,
    layer: int,
    block_tables: jax.Array,
    q_positions: jax.Array,
    value_width: int,
    scale: float,
    backend: str = "tpu",
) -> jax.Array:
    """A latent layer's absorbed attention: a window of queries laid out
    as cache rows ([B, W, H, RW]) over static ``layer`` of the whole
    latent cache ([L, num_blocks, block_size, RW]: one row a position,
    read once for scores and values). Returns [B, W, H, value_width].
    The Pallas kernel on the TPU backend, the same arithmetic as an XLA
    composition on the CPU backend and for windows past the kernel's
    bound (a suffix-prefill bucket)."""
    lowering = latent_call_lowering(q.shape[2], cache, backend, window=q.shape[1], batch=q.shape[0], max_blocks=block_tables.shape[1])
    call = paged_latent_attention if lowering["body"] == "mxu" else reference_paged_latent_attention
    return call(q, cache, layer, block_tables, q_positions, value_width, scale)


# A prefill call whose float32 scores [B, H, S, S] would pass this takes
# the streamed form; one under it materialises them, as every prefill did
# before the streamed form existed (the accepted configurations' calls
# stay under it: at most 537 MB). The rule asks the call's shapes and
# nothing else.
STREAM_SCORE_BYTES = 1 << 30


def prefill_call_lowering(
    q_shape, k_shape, itemsize: int, backend: str = "tpu", v_shape=None, block: int = 0
) -> Dict[str, Optional[str]]:
    """What a prefill's attention call of these shapes lowers to, as
    :func:`prefill_attention` will decide it: ``{"form", "kernel",
    "refused"}``. ``form`` is ``"materialised"`` (:func:`masked_attention`,
    scores of the sequence's square) or ``"streamed"``; a streamed call's
    ``kernel`` is ``"prefill_stream_attention"`` (the Pallas call: a
    group of whole tiles of query heads a K/V head or of one, a score
    width the lanes take and a value width of its own, ``v_shape``'s;
    ``k_shape``'s where none is given) or ``"xla_chunks"`` (the same
    arithmetic as a scan over chunks of query rows: the CPU backend, and
    a shape the kernel's gate refuses, whose reason is ``refused``). No
    call falls from the streamed form to materialised scores. ``block``
    > 0 (a block mask, :func:`block_seen`): the Pallas call's diagonal
    tiles are causal, so it refuses such a call by name and the XLA
    chunks serve it."""
    b, s, h, _ = q_shape
    if 4 * b * h * s * k_shape[1] <= STREAM_SCORE_BYTES:
        return {"form": "materialised", "kernel": "masked_attention", "refused": None}
    refused = None
    if backend == "tpu" and on_tpu():
        refused = prefill_stream_refusal(tuple(q_shape), tuple(k_shape), itemsize, v_shape)
        if refused is None and block:
            refused = f"a block mask of {block} positions (the kernel's diagonal tiles are causal)"
        if refused is None:
            return {"form": "streamed", "kernel": "prefill_stream_attention", "refused": None}
        _note_refusal("prefill_stream_attention", refused)
    return {"form": "streamed", "kernel": "xla_chunks", "refused": refused}


def prefill_attention(q, k, v, lengths, window: int = 0, backend: str = "cpu", block: int = 0):
    """A prefill's causal attention over [B, S, H, D] (grouped K/V, a
    valid length a sequence, ``window`` > 0 for a sliding-window layer;
    ``v``'s width may differ from the scores', whose scale is of ``q``'s
    width: a latent layer's expanded form), in the lowering
    :func:`prefill_call_lowering` names for the three shapes. ``block``
    > 0: the block mask instead of the causal one (:func:`block_seen`:
    a query sees its whole block of ``block`` positions, later rows
    included, and every block before it)."""
    low = prefill_call_lowering(q.shape, k.shape, q.dtype.itemsize, backend, v_shape=v.shape, block=block)
    if low["form"] == "materialised":
        return masked_attention(q, k, v, lengths, causal=True, window=window, block=block)
    if low["kernel"] == "prefill_stream_attention":
        return prefill_stream_attention(q, k, v, lengths, window=window)
    return reference_prefill_stream_attention(q, k, v, lengths, window=window, block=block)


def block_seen(q_pos, k_pos, block: int):
    """[Sq, Sk] bool, the block mask: key ``j`` is admitted for query
    ``i`` iff ``j // block <= i // block``, i.e. ``j < (i // block + 1)
    * block`` (block diffusion: full history, and the whole of the
    query's own block)."""
    return k_pos[None, :] < (q_pos[:, None] // block + 1) * block


def _seen(sq: int, sk: int, block: int):
    """[Sq, Sk] bool: the causal mask (queries the last ``sq`` of ``sk``
    positions), or with ``block`` the block mask over the same positions."""
    if block:
        return block_seen(jnp.arange(sq) + (sk - sq), jnp.arange(sk), block)
    return jnp.tril(jnp.ones((sq, sk), bool), k=sk - sq)


def _behind_window(sq: int, sk: int, window: int):
    """[Sq, Sk] bool: key ``s`` lies within the ``window`` positions up
    to query ``t`` (``s > t - window``; the causal mask holds ``s <= t``)."""
    return jnp.triu(jnp.ones((sq, sk), bool), k=sk - sq - window + 1)


def masked_attention(q, k, v, lengths, causal=True, scale=None, window=0, block=0):
    """Causal attention over [B, S, H, D] (v's width may differ from q's
    and k's: a latent layer scores at 192 and weighs values of 128) with
    a per-sequence valid length: key positions >= lengths[b] are masked. The prefill side of
    the decode split — bucketed (padded) prompts attend only over their
    real tokens, so prefill logits match the unpadded forward.
    ``window`` > 0 (a sliding-window layer): a query attends only the
    ``window`` positions up to its own. ``block`` > 0: the causal mask
    is the block mask (:func:`block_seen`)."""
    if scale is None:
        scale = q.shape[-1] ** -0.5
    if q.shape[2] != k.shape[2]:
        return _grouped_masked_attention(q, k, v, lengths, causal, scale, window, block)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32) * scale
    sq, sk = logits.shape[-2], logits.shape[-1]
    mask = jnp.arange(sk)[None, :] < lengths[:, None]  # [B, Sk]
    mask = mask[:, None, None, :]
    if causal:
        mask = jnp.logical_and(mask, _seen(sq, sk, block)[None, None])
    if window:
        mask = jnp.logical_and(mask, _behind_window(sq, sk, window)[None, None])
    logits = jnp.where(mask, logits, -jnp.inf)
    m = jnp.max(logits, axis=-1, keepdims=True)
    # fully-masked rows (padding queries) get uniform-zero probs, not NaN
    p = jnp.where(mask, jnp.exp(logits - jnp.maximum(m, -1e30)), 0.0)
    l = jnp.maximum(jnp.sum(p, axis=-1, keepdims=True), 1e-30)
    return jnp.einsum("bhqk,bkhd->bqhd", (p / l).astype(v.dtype), v)


def _grouped_masked_attention(q, k, v, lengths, causal, scale, window=0, block=0):
    """:func:`masked_attention` with grouped queries: q [B, S, H, D] over
    k/v [B, S, Hkv, D], query head ``i`` reading K/V head ``i // (H //
    Hkv)``. K and V are never repeated: the group is an axis of q."""
    b, sq, h, d = q.shape
    sk, hk = k.shape[1], k.shape[2]
    qg = q.reshape(b, sq, hk, h // hk, d)
    logits = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k, preferred_element_type=jnp.float32) * scale
    mask = (jnp.arange(sk)[None, :] < lengths[:, None])[:, None, None, None, :]
    if causal:
        mask = jnp.logical_and(mask, _seen(sq, sk, block)[None, None, None])
    if window:
        mask = jnp.logical_and(mask, _behind_window(sq, sk, window)[None, None, None])
    logits = jnp.where(mask, logits, -jnp.inf)
    m = jnp.max(logits, axis=-1, keepdims=True)
    p = jnp.where(mask, jnp.exp(logits - jnp.maximum(m, -1e30)), 0.0)
    l = jnp.maximum(jnp.sum(p, axis=-1, keepdims=True), 1e-30)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", (p / l).astype(v.dtype), v, preferred_element_type=jnp.float32)
    return out.reshape(b, sq, h, v.shape[-1]).astype(q.dtype)


def reference_attention(q, k, v, causal=False, scale=None):
    if scale is None:
        scale = q.shape[-1] ** -0.5
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32) * scale
    if causal:
        sq, sk = logits.shape[-2], logits.shape[-1]
        mask = jnp.tril(jnp.ones((sq, sk), bool), k=sk - sq)
        logits = jnp.where(mask, logits, -jnp.inf)
    probs = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", probs.astype(v.dtype), v)
