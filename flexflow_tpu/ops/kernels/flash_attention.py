"""Pallas TPU flash attention (forward + backward).

The reference's attention is a monolithic cuDNN call
(src/ops/attention.cu:35 cudnnMultiHeadAttnForward) with no long-context
story (SURVEY §2.2: no ring/blockwise attention anywhere). This kernel is
the TPU-native replacement for the attention core: online-softmax
blockwise attention that never materializes the [Sq, Sk] score matrix in
HBM, keeping the working set in VMEM and the matmuls on the MXU.

Layout: [B, H, S, D] inside the kernels (batch*heads on the grid's first
axes, sequence blocked on the last); the public API takes [B, S, H, D] to
match ops/attention.py.

Backward follows the FlashAttention-2 decomposition: residuals are the
output O and the per-row logsumexp L; dQ is computed by a kernel gridded
over Q blocks, dK/dV by a kernel gridded over KV blocks, which scores
K Q^T (the scores transposed) so that dV = P^T dO and dK = dS^T Q are
plain products of it.

What each product runs in (all three kernels): q, k, v and dO reach the
MXU as they were loaded, P and dS are cast to that type as the operand
of their product (P V, P^T dO, dS K, dS^T Q), and every product
accumulates in float32; the scores, the softmax scale, the mask, the
running max and sum, ``lse``, ``delta``, ``exp`` and the accumulators
are float32. So bfloat16 operands give nine bfloat16 products a layer
and float32 operands nine float32 ones: the type is the input's, there
is no switch. (On the v5e under libtpu 0.0.34 Mosaic rounds a float32
product's operands to bfloat16 anyway, one pass: see PERF.md, PR 47.)
"""
from __future__ import annotations

import functools
import math
import os as _os
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30

# Sequence block sizes, from the chip (v5e; `chip_smoke.py --flash-sweep`, PR 47): the three kernels inside one
# program (a layer's attention and its gradient) at [16, 512, 16, 64] bfloat16, not causal, ms a call on the
# device's clock as fwd / bwd_dq / bwd_dkv:
#                 block_k 128             block_k 256             block_k 512
#   block_q 128   1.667 / 1.395 / 1.397   0.998 / 0.888 / 0.906   0.656 / 0.669 / 0.722
#   block_q 256   1.099 / 0.869 / 1.105   0.658 / 0.600 / 0.752   0.527 / 0.508 / 0.616
#   block_q 512   0.846 / 0.600 / 0.589   0.538 / 0.449 / 0.442   0.327 / 0.361 / 0.393
# Mosaic compiles any of them in 0.13-0.31 s a kernel (the program: 3.7-4.3 s at every pair). The largest pair
# wins in every kernel, and of the two the block a kernel LOOPS over counts more (keys in fwd and bwd_dq, queries
# in bwd_dkv); 512 is this sequence whole, in one trip. Policy: the largest of _BLOCK_CANDIDATES dividing the
# sequence, for queries and for keys, in all three kernels. FF_FLASH_BLOCK_Q/K override it for a sweep across
# clean child processes: read once at import; a malformed value falls back to the policy, not a failed import.
_BLOCK_CANDIDATES = (512, 256, 128)


def _env_block(name: str) -> Optional[int]:
    raw = _os.environ.get(name)
    if raw is None:
        return None
    try:
        v = int(raw)
    except (TypeError, ValueError):
        return None
    return v if v > 0 else None


ENV_BLOCK_Q = _env_block("FF_FLASH_BLOCK_Q")
ENV_BLOCK_K = _env_block("FF_FLASH_BLOCK_K")


def pick_block(seq: int, env: Optional[int]) -> int:
    """Effective block for a sequence length: the env override clamped
    to the sequence (an override that does not divide the sequence is an
    error said aloud: the kernel's gate would refuse the call and the
    dense path run in its place), else the largest default candidate
    dividing it, else the largest power-of-two divisor (a non-dividing
    block would leave sq // bq grid steps covering only a prefix of the rows)."""
    if env is not None:
        block = min(env, seq)
        if seq % block:
            raise ValueError(
                f"FF_FLASH_BLOCK_Q/K = {env} does not divide the sequence length {seq}: the flash kernel would "
                f"be refused and the dense path taken in its place; unset it or give a divisor"
            )
        return block
    for b in _BLOCK_CANDIDATES + (64, 32, 16, 8):
        if seq >= b and seq % b == 0:
            return b
    return seq


def effective_blocks(sq: int, sk: int) -> Tuple[int, int]:
    return pick_block(sq, ENV_BLOCK_Q), pick_block(sk, ENV_BLOCK_K)


def supports_shapes(q_shape: Tuple[int, ...], k_shape: Tuple[int, ...]) -> bool:
    """Shapes the kernel handles without falling back: head_dim a lane
    multiple and sequence lengths divisible by the block size."""
    if len(q_shape) != 4 or len(k_shape) != 4:
        return False
    _, sq, _, d = q_shape
    _, sk, _, _ = k_shape
    if d not in (64, 128, 256):
        return False
    bq, bk = effective_blocks(sq, sk)
    # sequence lengths must tile into blocks and respect the (8, 128)
    # sublane/lane tiling of the TPU vector memory
    return sq % bq == 0 and sk % bk == 0 and sq % 8 == 0 and sk % 8 == 0 and sq >= 8 and sk >= 8


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------
_NT = (((1,), (1,)), ((), ()))  # [m, d] x [n, d] -> [m, n]: the contraction over both operands' last dimension
_NN = (((1,), (0,)), ((), ()))  # [m, n] x [n, d] -> [m, d]


def _mxu(a, b, dims):
    """A product on the MXU in the operands' type, accumulated in
    float32: bfloat16 operands are multiplied as bfloat16, float32 ones
    as float32 (in as many passes as the compiler makes of that)."""
    return jax.lax.dot_general(a, b, dims, preferred_element_type=jnp.float32)


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, scale, causal, block_k, sk):
    # q_ref: [bq, d]; k_ref/v_ref: [sk, d] (whole key sequence for this head)
    bq, d = q_ref.shape
    iq = pl.program_id(2)
    q = q_ref[:]
    m = jnp.full((bq, 1), NEG_INF, jnp.float32)
    l = jnp.zeros((bq, 1), jnp.float32)
    acc = jnp.zeros((bq, d), jnp.float32)
    nk = sk // block_k

    q_pos = iq * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, block_k), 0)

    def body(j, carry):
        m, l, acc = carry
        k = k_ref[pl.ds(j * block_k, block_k), :]
        v = v_ref[pl.ds(j * block_k, block_k), :]
        s = _mxu(q, k, _NT) * scale  # [bq, bk]
        if causal:
            k_pos = j * block_k + jax.lax.broadcasted_iota(jnp.int32, (bq, block_k), 1)
            s = jnp.where(q_pos >= k_pos, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m - m_new)
        l_new = l * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc_new = acc * corr + _mxu(p.astype(v.dtype), v, _NN)
        return m_new, l_new, acc_new

    # causal: skip key blocks entirely above the diagonal
    nk_eff = jnp.minimum(nk, (iq + 1) * bq // block_k + 1) if causal else nk
    m, l, acc = jax.lax.fori_loop(0, nk_eff, body, (m, l, acc))
    l = jnp.maximum(l, 1e-30)
    o_ref[:] = (acc / l).astype(o_ref.dtype)
    lse_ref[:] = m + jnp.log(l)  # [bq, 1]


def _flash_fwd(q, k, v, scale, causal, block_q, block_k, interpret):
    # q,k,v: [B, H, S, D]
    b, h, sq, d = q.shape
    sk = k.shape[2]
    bq, bk = min(block_q, sq), min(block_k, sk)
    if sq % bq or sk % bk:  # a non-dividing block would silently compute only the first (sq // bq) * bq query rows
        raise ValueError(f"sequence lengths ({sq}, {sk}) not divisible by blocks ({bq}, {bk})")
    grid = (b, h, sq // bq)
    kernel = functools.partial(_fwd_kernel, scale=scale, causal=causal, block_k=bk, sk=sk)
    o, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, None, bq, d), lambda ib, ih, iq: (ib, ih, iq, 0)),
            pl.BlockSpec((None, None, sk, d), lambda ib, ih, iq: (ib, ih, 0, 0)),
            pl.BlockSpec((None, None, sk, d), lambda ib, ih, iq: (ib, ih, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((None, None, bq, d), lambda ib, ih, iq: (ib, ih, iq, 0)),
            pl.BlockSpec((None, None, bq, 1), lambda ib, ih, iq: (ib, ih, iq, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, sq, d), q.dtype),
            jax.ShapeDtypeStruct((b, h, sq, 1), jnp.float32),
        ],
        interpret=interpret,
        name="flash_attention_fwd",
    )(q, k, v)
    return o, lse


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, *, scale, causal, block_k, sk):
    bq, d = q_ref.shape
    iq = pl.program_id(2)
    q = q_ref[:]
    do = do_ref[:]
    lse = lse_ref[:]  # [bq, 1]
    delta = delta_ref[:]
    dq = jnp.zeros((bq, d), jnp.float32)
    nk = sk // block_k
    q_pos = iq * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, block_k), 0)

    def body(j, dq):
        k = k_ref[pl.ds(j * block_k, block_k), :]
        v = v_ref[pl.ds(j * block_k, block_k), :]
        s = _mxu(q, k, _NT) * scale
        if causal:
            k_pos = j * block_k + jax.lax.broadcasted_iota(jnp.int32, (bq, block_k), 1)
            s = jnp.where(q_pos >= k_pos, s, NEG_INF)
        p = jnp.exp(s - lse)  # [bq, bk]
        ds = p * (_mxu(do, v, _NT) - delta)
        return dq + _mxu(ds.astype(k.dtype), k, _NN)

    nk_eff = jnp.minimum(nk, (iq + 1) * bq // block_k + 1) if causal else nk
    dq = jax.lax.fori_loop(0, nk_eff, body, dq)
    dq_ref[:] = (dq * scale).astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref, dv_ref, *, scale, causal, block_q, sq):
    # lse_ref / delta_ref: [1, sq] (a row: the query positions on the lanes)
    bk, d = k_ref.shape
    jk = pl.program_id(2)
    k = k_ref[:]
    v = v_ref[:]
    dk = jnp.zeros((bk, d), jnp.float32)
    dv = jnp.zeros((bk, d), jnp.float32)
    nq = sq // block_q
    k_pos = jk * bk + jax.lax.broadcasted_iota(jnp.int32, (bk, block_q), 0)

    def body(i, carry):
        dk, dv = carry
        q = q_ref[pl.ds(i * block_q, block_q), :]
        do = do_ref[pl.ds(i * block_q, block_q), :]
        lse = lse_ref[:, pl.ds(i * block_q, block_q)]  # [1, bq]
        delta = delta_ref[:, pl.ds(i * block_q, block_q)]
        s = _mxu(k, q, _NT) * scale  # [bk, bq]: the scores transposed
        if causal:
            q_pos = i * block_q + jax.lax.broadcasted_iota(jnp.int32, (bk, block_q), 1)
            s = jnp.where(q_pos >= k_pos, s, NEG_INF)
        p = jnp.exp(s - lse)
        dv = dv + _mxu(p.astype(do.dtype), do, _NN)
        ds = p * (_mxu(v, do, _NT) - delta)
        dk = dk + _mxu(ds.astype(q.dtype), q, _NN)
        return dk, dv

    start = jk * bk // block_q if causal else 0  # causal: query blocks strictly below this key block see nothing
    dk, dv = jax.lax.fori_loop(start, nq, body, (dk, dv))
    dk_ref[:] = (dk * scale).astype(dk_ref.dtype)
    dv_ref[:] = dv.astype(dv_ref.dtype)


def _flash_bwd(res, g, scale, causal, block_q, block_k, interpret):
    q, k, v, o, lse = res
    do = g
    b, h, sq, d = q.shape
    sk = k.shape[2]
    bq, bk = min(block_q, sq), min(block_k, sk)
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1, keepdims=True)  # [B,H,Sq,1]

    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, scale=scale, causal=causal, block_k=bk, sk=sk),
        grid=(b, h, sq // bq),
        in_specs=[
            pl.BlockSpec((None, None, bq, d), lambda ib, ih, iq: (ib, ih, iq, 0)),
            pl.BlockSpec((None, None, sk, d), lambda ib, ih, iq: (ib, ih, 0, 0)),
            pl.BlockSpec((None, None, sk, d), lambda ib, ih, iq: (ib, ih, 0, 0)),
            pl.BlockSpec((None, None, bq, d), lambda ib, ih, iq: (ib, ih, iq, 0)),
            pl.BlockSpec((None, None, bq, 1), lambda ib, ih, iq: (ib, ih, iq, 0)),
            pl.BlockSpec((None, None, bq, 1), lambda ib, ih, iq: (ib, ih, iq, 0)),
        ],
        out_specs=pl.BlockSpec((None, None, bq, d), lambda ib, ih, iq: (ib, ih, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, sq, d), q.dtype),
        interpret=interpret,
        name="flash_attention_bwd_dq",
    )(q, k, v, do, lse, delta)

    # the same two vectors with the query positions on the lanes, as the transposed scores meet them
    lse_row, delta_row = lse.reshape(b, h, 1, sq), delta.reshape(b, h, 1, sq)
    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, scale=scale, causal=causal, block_q=bq, sq=sq),
        grid=(b, h, sk // bk),
        in_specs=[
            pl.BlockSpec((None, None, sq, d), lambda ib, ih, jk: (ib, ih, 0, 0)),
            pl.BlockSpec((None, None, bk, d), lambda ib, ih, jk: (ib, ih, jk, 0)),
            pl.BlockSpec((None, None, bk, d), lambda ib, ih, jk: (ib, ih, jk, 0)),
            pl.BlockSpec((None, None, sq, d), lambda ib, ih, jk: (ib, ih, 0, 0)),
            pl.BlockSpec((None, None, 1, sq), lambda ib, ih, jk: (ib, ih, 0, 0)),
            pl.BlockSpec((None, None, 1, sq), lambda ib, ih, jk: (ib, ih, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((None, None, bk, d), lambda ib, ih, jk: (ib, ih, jk, 0)),
            pl.BlockSpec((None, None, bk, d), lambda ib, ih, jk: (ib, ih, jk, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, sk, d), k.dtype),
            jax.ShapeDtypeStruct((b, h, sk, d), v.dtype),
        ],
        interpret=interpret,
        name="flash_attention_bwd_dkv",
    )(q, k, v, do, lse_row, delta_row)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# public API
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash_bhsd(q, k, v, scale, causal, block_q, block_k, interpret):
    o, _ = _flash_fwd(q, k, v, scale, causal, block_q, block_k, interpret)
    return o


def _flash_bhsd_fwd(q, k, v, scale, causal, block_q, block_k, interpret):
    o, lse = _flash_fwd(q, k, v, scale, causal, block_q, block_k, interpret)
    return o, (q, k, v, o, lse)


def _flash_bhsd_bwd(scale, causal, block_q, block_k, interpret, res, g):
    return _flash_bwd(res, g, scale, causal, block_q, block_k, interpret)


_flash_bhsd.defvjp(_flash_bhsd_fwd, _flash_bhsd_bwd)


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    causal: bool = False,
    scale: Optional[float] = None,
    block_q: Optional[int] = None,
    block_k: Optional[int] = None,
    interpret: bool = False,
) -> jax.Array:
    """Flash attention over [B, S, H, D] tensors (differentiable).

    ``block_q``/``block_k`` default to the adaptive policy (env override
    or the largest candidate dividing the sequence). Compiled by Mosaic;
    only a test passes ``interpret=True``, and without it Pallas refuses
    to lower for a CPU backend.
    """
    if block_q is None:
        block_q = pick_block(q.shape[1], ENV_BLOCK_Q)
    if block_k is None:
        block_k = pick_block(k.shape[1], ENV_BLOCK_K)
    if scale is None:
        scale = q.shape[-1] ** -0.5
    qt, kt, vt = (jnp.swapaxes(x, 1, 2) for x in (q, k, v))  # [B, S, H, D] -> [B, H, S, D]
    o = _flash_bhsd(qt, kt, vt, float(scale), bool(causal), int(block_q), int(block_k), bool(interpret))
    return jnp.swapaxes(o, 1, 2)


def flash_attention_sharded(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    mesh,
    causal: bool = False,
    scale: Optional[float] = None,
    interpret: bool = False,
) -> jax.Array:
    """:func:`flash_attention` under a multi-device GSPMD mesh. Mosaic
    kernels cannot be partitioned automatically (lowering one with
    sharded operands raises), so the call runs under shard_map: each
    device's kernel sees its own batch rows (mesh axis "data") and heads
    ("model") — attention is independent across both, so nothing
    crosses devices. A mesh axis that is absent, trivial, or does not
    divide its dimension leaves that dimension whole on every device."""
    from jax.sharding import PartitionSpec as P

    def axis_for(name: str, dim: int) -> Optional[str]:
        size = dict(mesh.shape).get(name, 1)
        return name if size > 1 and dim % size == 0 else None

    spec = P(axis_for("data", q.shape[0]), None, axis_for("model", q.shape[2]), None)
    fn = jax.shard_map(
        functools.partial(flash_attention, causal=causal, scale=scale, interpret=interpret),
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        check_vma=False,
    )
    return fn(q, k, v)


# (The lines below keep their numbers from commit to commit where they can: a Mosaic module carries its source
# locations into the compile cache's key, so shifting them costs every streamed-prefill program one cold compile.)
# ---------------------------------------------------------------------------
# the serving prefill: streamed, forward only
# ---------------------------------------------------------------------------
# A prefill whose float32 scores would pass ops/attention.py's
# STREAM_SCORE_BYTES never builds them: K/V are folded block by block
# into a running softmax, a query row reaching no further back than its
# window and no further on than its own position or the sequence's
# length. Two lowerings of one signature: the Pallas call below (a kernel
# of its own name, so that a device trace shows it), and the same
# arithmetic as an XLA scan over chunks of query rows, each against the
# keys its rows can reach, which the CPU backend takes and a TPU call
# whose shapes the gate refuses. The Pallas call lays its query rows out
# by what the shapes show: a group that is a whole tile of query heads a
# K/V head scores ``positions x group`` rows a step out of q as it comes;
# a group of ONE (every head has K/V of its own: plain multi-head, a
# latent layer's expanded form) takes q and the result head-major and
# scores a block of positions of one head a step. The score's width
# (q's and k's) and the value's (v's and the result's) may differ.

STREAM_QUERY_ROWS = 1024  # query rows (positions x the group's heads) a grid step scores at once
STREAM_BLOCK_K = 512  # key positions folded into the running softmax at a time
_STREAM_VMEM_LIMIT = 64 << 20  # the call's scoped VMEM: K and V of one K/V head whole, twice, and the score tiles
_STREAM_KV_BYTES = 24 << 20  # most that K and V of one K/V head, double-buffered, may take of it
CHUNK_SCORE_BYTES = 256 << 20  # float32 scores one chunk of the XLA composition may build


def stream_blocks(seq: int, group: int) -> Tuple[int, int]:
    """(query positions, key positions) a grid step of the streamed
    kernel takes of a sequence of ``seq`` positions at ``group`` query
    heads a K/V head: :data:`STREAM_QUERY_ROWS` rows, and no more
    positions than a key block holds (a group of one: the block pair on
    the diagonal is then the only one the causal mask cuts in half)."""
    bk = STREAM_BLOCK_K
    while bk > 128 and seq % bk:
        bk //= 2
    bq = max(8, min(STREAM_QUERY_ROWS // group, bk))
    while bq > 8 and seq % bq:
        bq //= 2
    return min(bq, seq), min(bk, seq)


def prefill_stream_refusal(
    q_shape: Tuple[int, ...], k_shape: Tuple[int, ...], itemsize: int, v_shape: Optional[Tuple[int, ...]] = None,
) -> Optional[str]:
    """Why :func:`prefill_stream_attention` will not take this shape, or
    None when it will (the XLA composition of the same arithmetic takes
    a refused one; ops/attention.py counts and names the refusal). It
    takes a group of one query head a K/V head or of whole tiles of them,
    a score width (``q``'s and ``k``'s) of 128 or more in steps of 64 and
    a value width (``v_shape``'s; ``k``'s where none is given) in steps
    of 128."""
    _, s, h, d = q_shape
    hk = k_shape[2]
    dv = k_shape[3] if v_shape is None else v_shape[3]
    if h % hk:
        return f"{h} query heads over {hk} K/V heads"
    group = h // hk
    if d < 128 or d % 64:
        return f"head_dim {d} does not fill the 128 lanes"
    if dv % 128:
        return f"value width {dv} does not fill the 128 lanes"
    if group != 1 and group % (32 // itemsize):
        return f"a group of {group} query heads is no whole tile of {32 // itemsize} rows (and not 1)"
    bq, bk = stream_blocks(s, group)
    if s % bq or s % bk or s % 8:
        return f"sequence {s} does not divide into blocks ({bq}, {bk})"
    lanes = sum(-(-width // 128) * 128 for width in (d, dv))  # a row of K and one of V, as fast memory holds them
    if 2 * s * lanes * itemsize > _STREAM_KV_BYTES:
        return f"K and V of one head over {s} positions pass {_STREAM_KV_BYTES >> 20} MiB of VMEM"
    return None


def _stream_kernel(lens_ref, q_ref, k_ref, v_ref, o_ref, *, scale, window, block_k):
    # q_ref [bq, G, D] / o_ref [bq, G, Dv]: a block of positions of one K/V head's query heads; at a
    # group of one, head-major, [bq, D] / [bq, Dv]: a block of positions of one head;
    # k_ref [S, D] / v_ref [S, Dv]: that K/V head's whole sequence
    lead, d = q_ref.shape[:-1], q_ref.shape[-1]
    bq, rows = lead[0], math.prod(lead)
    sk, dv = v_ref.shape
    length = lens_ref[pl.program_id(0)]
    first = pl.program_id(2) * bq  # the block's first query position
    q = q_ref[...].reshape(rows, d)  # row t * G + g: position first + t, the group's g-th head
    row_pos = first + jax.lax.broadcasted_iota(jnp.int32, lead + (block_k,), 0).reshape(rows, block_k)
    # key blocks any row of this block can reach: not past its last row or the
    # sequence's length, and (a window) not wholly behind its first row's window
    reach = jnp.minimum(first + bq, length)
    j_hi = jnp.minimum((jnp.maximum(reach, 0) + block_k - 1) // block_k, sk // block_k)
    j_lo = jnp.maximum(first - window + 1, 0) // block_k if window else 0

    def body(j, carry):
        m, l, acc = carry
        k = k_ref[pl.ds(j * block_k, block_k), :]
        v = v_ref[pl.ds(j * block_k, block_k), :]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32) * scale
        k_pos = j * block_k + jax.lax.broadcasted_iota(jnp.int32, (rows, block_k), 1)
        seen = jnp.logical_and(k_pos <= row_pos, k_pos < length)
        if window:
            seen = jnp.logical_and(seen, k_pos > row_pos - window)
        s = jnp.where(seen, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.where(seen, jnp.exp(s - m_new), 0.0)  # a row that has reached nothing yet keeps zeros
        corr = jnp.exp(m - m_new)
        l_new = l * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc_new = acc * corr + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        return m_new, l_new, acc_new

    init = (
        jnp.full((rows, 1), NEG_INF, jnp.float32), jnp.zeros((rows, 1), jnp.float32), jnp.zeros((rows, dv), jnp.float32),
    )
    _, l, acc = jax.lax.fori_loop(j_lo, j_hi, body, init)
    o_ref[...] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype).reshape(o_ref.shape)


def prefill_stream_attention(
    q: jax.Array, k: jax.Array, v: jax.Array, lengths: jax.Array, window: int = 0,
    scale: Optional[float] = None, interpret: bool = False,
) -> jax.Array:
    """Causal attention of a prefill, q [B, S, H, D] over k [B, S, Hkv,
    D] and v [B, S, Hkv, Dv] (query head ``i`` reads K/V head ``i // (H
    // Hkv)``; ``Dv`` may differ from ``D``: a latent layer's expanded
    form scores at 192 and weighs values of 128), key positions ``>=
    lengths[b]`` masked and, with ``window`` > 0, those ``window`` or
    more behind a query: :func:`~flexflow_tpu.ops.attention.masked_attention`'s
    result, [B, S, H, Dv], without its ``[B, H, S, S]`` scores. A Pallas
    call of its own name; the grid is ``(batch, K/V heads, query
    blocks)``, a step scores ``block x group`` query rows against one
    block of keys at a time on the MXU in the operands' type with float32
    accumulation (the contraction over ``D`` whole, whatever its width),
    keeps the softmax state in float32, and walks only the key blocks its
    rows can reach. At a group of one query head a K/V head the rows of a
    step are a block of positions of ONE head: q and the result go
    through the call head-major (``[B, H, S, .]``, as K and V always do).
    Compiled by Mosaic; only a test passes ``interpret=True``."""
    b, s, h, d = q.shape
    hk, dv = k.shape[2], v.shape[3]
    group = h // hk
    if scale is None:
        scale = d ** -0.5
    bq, bk = stream_blocks(s, group)
    kt, vt = jnp.swapaxes(k, 1, 2), jnp.swapaxes(v, 1, 2)  # [B, Hkv, S, .]: a head's sequence is one block
    kernel = functools.partial(_stream_kernel, scale=float(scale), window=int(window), block_k=bk)

    def whole(width):
        return pl.BlockSpec((None, None, s, width), lambda ib, ih, iq, lens: (ib, ih, 0, 0))

    head_major = group == 1  # no tile holds one head of 64: a block of positions of one head, out of [B, H, S, .]
    if head_major:
        q = jnp.swapaxes(q, 1, 2)

    def rows(width):
        if head_major:
            return pl.BlockSpec((None, None, bq, width), lambda ib, ih, iq, lens: (ib, ih, iq, 0))
        return pl.BlockSpec((None, bq, group, width), lambda ib, ih, iq, lens: (ib, iq, ih, 0))

    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(b, hk, s // bq), in_specs=[rows(d), whole(d), whole(dv)], out_specs=rows(dv),
        ),
        out_shape=jax.ShapeDtypeStruct((b, h, s, dv) if head_major else (b, s, h, dv), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"), vmem_limit_bytes=_STREAM_VMEM_LIMIT,
        ),
        interpret=interpret,
        name="prefill_stream_attention",
    )(lengths.astype(jnp.int32), q, kt, vt)
    return jnp.swapaxes(out, 1, 2) if head_major else out


def stream_chunk(batch: int, heads: int, span: int) -> int:
    """Query rows a chunk of the XLA composition takes: the largest
    power of two up to 512 whose float32 scores against ``span`` keys
    stay under :data:`CHUNK_SCORE_BYTES`."""
    chunk = 512
    while chunk > 8 and 4 * batch * heads * chunk * span > CHUNK_SCORE_BYTES:
        chunk //= 2
    return chunk


def reference_prefill_stream_attention(
    q: jax.Array, k: jax.Array, v: jax.Array, lengths: jax.Array, window: int = 0,
    scale: Optional[float] = None, chunk: Optional[int] = None, block: int = 0,
) -> jax.Array:
    """:func:`prefill_stream_attention`'s arithmetic as an XLA
    composition: a scan over chunks of query rows, each scored against
    the ``chunk + window - 1`` keys its rows can reach (a full layer's:
    the sequence), masked and weighed as ``masked_attention`` does it
    (``v``'s width may differ from the scores': a latent layer's expanded
    form). The largest temporary is one chunk's scores. ``block`` > 0:
    a query sees key ``j`` iff ``j < (i // block + 1) * block`` (the
    block mask: its whole block, later rows included) and not ``j <= i``."""
    b, s, h, d = q.shape
    dv = v.shape[-1]
    hk = k.shape[2]
    group = h // hk
    if scale is None:
        scale = d ** -0.5
    chunk = min(chunk or stream_chunk(b, h, s), s)
    while s % chunk:
        chunk //= 2
    span = min(s, chunk + window - 1) if window else s
    qg = q.reshape(b, s // chunk, chunk, hk, group, d)

    def one(c):
        q_pos = c * chunk + jnp.arange(chunk)
        start = jnp.clip((c + 1) * chunk - span, 0, s - span)
        kc = jax.lax.dynamic_slice_in_dim(k, start, span, axis=1)
        vc = jax.lax.dynamic_slice_in_dim(v, start, span, axis=1)
        k_pos = start + jnp.arange(span)
        logits = jnp.einsum("bqhgd,bkhd->bhgqk", qg[:, c], kc, preferred_element_type=jnp.float32) * scale
        seen = k_pos[None, :] < (q_pos[:, None] // block + 1) * block if block else k_pos[None, :] <= q_pos[:, None]
        if window:
            seen = jnp.logical_and(seen, k_pos[None, :] > q_pos[:, None] - window)
        mask = jnp.logical_and(seen[None], (k_pos[None, :] < lengths[:, None])[:, None, :])[:, None, None]
        logits = jnp.where(mask, logits, -jnp.inf)
        m = jnp.max(logits, axis=-1, keepdims=True)
        p = jnp.where(mask, jnp.exp(logits - jnp.maximum(m, -1e30)), 0.0)
        l = jnp.maximum(jnp.sum(p, axis=-1, keepdims=True), 1e-30)
        out = jnp.einsum("bhgqk,bkhd->bqhgd", (p / l).astype(v.dtype), vc, preferred_element_type=jnp.float32)
        return out.reshape(b, chunk, h, dv).astype(q.dtype)

    out = jax.lax.map(one, jnp.arange(s // chunk))  # [chunks, B, chunk, H, Dv]
    return jnp.moveaxis(out, 0, 1).reshape(b, s, h, dv)
