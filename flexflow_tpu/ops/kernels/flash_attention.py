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
over Q blocks, dK/dV by a kernel gridded over KV blocks.
"""
from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG_INF = -1e30


# Sequence block sizes. 128 matches the MXU systolic dimension; 256x256
# blocks mean fewer grid invocations and online-softmax rescale passes
# per output row, and 512x512 is left out because it did not finish
# compiling in 20 minutes when it was last tried. Default policy: the
# largest block in _BLOCK_CANDIDATES that divides the sequence, so long
# sequences get 256 and seq 128 keeps 128.
# Env-overridable (FF_FLASH_BLOCK_Q/K) for sweeps across clean child
# processes; read once at import; malformed values fall back to the
# adaptive policy rather than breaking every import of the package.
import os as _os

_BLOCK_CANDIDATES = (256, 128)


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
    to the sequence, else the largest default candidate dividing it,
    else the largest power-of-two divisor (a non-dividing block would
    leave sq // bq grid steps covering only a prefix of the rows)."""
    if env is not None:
        return min(env, seq)
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


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, scale, causal, block_k, sk):
    # q_ref: [bq, d]; k_ref/v_ref: [sk, d] (whole key sequence for this head)
    bq, d = q_ref.shape
    iq = pl.program_id(2)
    q = q_ref[:].astype(jnp.float32) * scale
    m = jnp.full((bq, 1), NEG_INF, jnp.float32)
    l = jnp.zeros((bq, 1), jnp.float32)
    acc = jnp.zeros((bq, d), jnp.float32)
    nk = sk // block_k

    q_pos = iq * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, block_k), 0)

    def body(j, carry):
        m, l, acc = carry
        k = k_ref[pl.ds(j * block_k, block_k), :].astype(jnp.float32)
        v = v_ref[pl.ds(j * block_k, block_k), :].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )  # [bq, bk]
        if causal:
            k_pos = j * block_k + jax.lax.broadcasted_iota(jnp.int32, (bq, block_k), 1)
            s = jnp.where(q_pos >= k_pos, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m - m_new)
        l_new = l * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc_new = acc * corr + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        return m_new, l_new, acc_new

    if causal:
        # skip key blocks entirely above the diagonal
        nk_eff = jnp.minimum(nk, (iq + 1) * bq // block_k + 1)
    else:
        nk_eff = nk
    m, l, acc = jax.lax.fori_loop(0, nk_eff, body, (m, l, acc))
    l = jnp.maximum(l, 1e-30)
    o_ref[:] = (acc / l).astype(o_ref.dtype)
    lse_ref[:] = m + jnp.log(l)  # [bq, 1]


def _flash_fwd(q, k, v, scale, causal, block_q, block_k, interpret):
    # q,k,v: [B, H, S, D]
    b, h, sq, d = q.shape
    sk = k.shape[2]
    bq = min(block_q, sq)
    bk = min(block_k, sk)
    if sq % bq or sk % bk:
        # a non-dividing block would silently compute only the first
        # (sq // bq) * bq query rows — fail loudly instead
        raise ValueError(
            f"sequence lengths ({sq}, {sk}) not divisible by blocks ({bq}, {bk})"
        )
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
    q = q_ref[:].astype(jnp.float32) * scale
    do = do_ref[:].astype(jnp.float32)
    lse = lse_ref[:]  # [bq, 1]
    delta = delta_ref[:]
    dq = jnp.zeros((bq, d), jnp.float32)
    nk = sk // block_k
    q_pos = iq * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, block_k), 0)

    def body(j, dq):
        k = k_ref[pl.ds(j * block_k, block_k), :].astype(jnp.float32)
        v = v_ref[pl.ds(j * block_k, block_k), :].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
        if causal:
            k_pos = j * block_k + jax.lax.broadcasted_iota(jnp.int32, (bq, block_k), 1)
            s = jnp.where(q_pos >= k_pos, s, NEG_INF)
        p = jnp.exp(s - lse)  # [bq, bk]
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
        ds = p * (dp - delta)
        return dq + jax.lax.dot_general(ds, k, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    if causal:
        nk_eff = jnp.minimum(nk, (iq + 1) * bq // block_k + 1)
    else:
        nk_eff = nk
    dq = jax.lax.fori_loop(0, nk_eff, body, dq)
    dq_ref[:] = (dq * scale).astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref, dv_ref, *, scale, causal, block_q, sq):
    bk, d = k_ref.shape
    jk = pl.program_id(2)
    k = k_ref[:].astype(jnp.float32)
    v = v_ref[:].astype(jnp.float32)
    dk = jnp.zeros((bk, d), jnp.float32)
    dv = jnp.zeros((bk, d), jnp.float32)
    nq = sq // block_q
    k_pos = jk * bk + jax.lax.broadcasted_iota(jnp.int32, (block_q, bk), 1)

    def body(i, carry):
        dk, dv = carry
        q = q_ref[pl.ds(i * block_q, block_q), :].astype(jnp.float32) * scale
        do = do_ref[pl.ds(i * block_q, block_q), :].astype(jnp.float32)
        lse = lse_ref[pl.ds(i * block_q, block_q), :]  # [bq, 1]
        delta = delta_ref[pl.ds(i * block_q, block_q), :]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
        if causal:
            q_pos = i * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, bk), 0)
            s = jnp.where(q_pos >= k_pos, s, NEG_INF)
        p = jnp.exp(s - lse)  # [bq, bk]
        dv = dv + jax.lax.dot_general(p, do, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
        ds = p * (dp - delta)
        dk = dk + jax.lax.dot_general(ds, q, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        return dk, dv

    if causal:
        # query blocks strictly below this key block see nothing
        start = jk * bk // block_q
    else:
        start = 0
    dk, dv = jax.lax.fori_loop(start, nq, body, (dk, dv))
    # q entered the loop pre-scaled, so dk = scale * dS^T Q already
    dk_ref[:] = dk.astype(dk_ref.dtype)
    dv_ref[:] = dv.astype(dv_ref.dtype)


def _flash_bwd(res, g, scale, causal, block_q, block_k, interpret):
    q, k, v, o, lse = res
    do = g
    b, h, sq, d = q.shape
    sk = k.shape[2]
    bq = min(block_q, sq)
    bk = min(block_k, sk)
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

    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, scale=scale, causal=causal, block_q=bq, sq=sq),
        grid=(b, h, sk // bk),
        in_specs=[
            pl.BlockSpec((None, None, sq, d), lambda ib, ih, jk: (ib, ih, 0, 0)),
            pl.BlockSpec((None, None, bk, d), lambda ib, ih, jk: (ib, ih, jk, 0)),
            pl.BlockSpec((None, None, bk, d), lambda ib, ih, jk: (ib, ih, jk, 0)),
            pl.BlockSpec((None, None, sq, d), lambda ib, ih, jk: (ib, ih, 0, 0)),
            pl.BlockSpec((None, None, sq, 1), lambda ib, ih, jk: (ib, ih, 0, 0)),
            pl.BlockSpec((None, None, sq, 1), lambda ib, ih, jk: (ib, ih, 0, 0)),
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
    )(q, k, v, do, lse, delta)
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
    # [B, S, H, D] -> [B, H, S, D]
    qt = jnp.swapaxes(q, 1, 2)
    kt = jnp.swapaxes(k, 1, 2)
    vt = jnp.swapaxes(v, 1, 2)
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
