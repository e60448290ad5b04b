"""Operations and bytes that the algorithm needs for one call of each
Pallas kernel, from the call's shapes alone. The roofline share of a
kernel is ``least_seconds`` of the calls made over the device time the
trace shows for them. Kept with the benchmark so that no PR that speeds
a kernel up can also change what it is measured against.

Counted: multiply-adds as two operations in the matmuls of attention
(scores and weighted values); the exponentials, maxima and rescales of
the online softmax are left out, as is usual. Bytes: every operand read
once and every result written once, at its stored width. A kernel that
re-reads K/V per query block moves more than this; that shows as a lower
share, which is the point.
"""
from __future__ import annotations

from typing import Dict, Tuple


def paged_attention_call(
    context_tokens: int, query_rows: int, num_heads: int, head_dim: int, cache_itemsize: int,
    io_itemsize: int = 4,
) -> Tuple[float, float]:
    """(operations, bytes) of ONE layer's decode-attention call.

    ``context_tokens`` is the sum over the batch's live rows of the
    cache positions each row attends (its context length, the new token
    included); ``query_rows`` the live rows. Per attended position and
    head: ``head_dim`` multiply-adds for the score and as many for the
    weighted value. K and V of every attended position are read once;
    q is read and the output written once per row.
    """
    ops = 4.0 * context_tokens * num_heads * head_dim
    kv_bytes = 2.0 * context_tokens * num_heads * head_dim * cache_itemsize
    io_bytes = 2.0 * query_rows * num_heads * head_dim * io_itemsize
    return ops, kv_bytes + io_bytes


# matmuls of [S, D] x [D, S] or [S, S] x [S, D] size per (batch, head),
# and [B, S, H, D] tensors moved, for each flash kernel
_FLASH = {
    # scores, weighted values | reads q k v, writes o
    "flash_attention_fwd": (2, 4),
    # scores again, dP = dO V^T, dQ = dS K | reads q k v dO, writes dQ
    "flash_attention_bwd_dq": (3, 5),
    # scores again, dP, dV = P^T dO, dK = dS^T Q | reads q k v dO, writes dK dV
    "flash_attention_bwd_dkv": (4, 6),
}
FLASH_KERNELS = tuple(_FLASH)
PAGED_KERNELS = ("paged_append_attention", "paged_append_attention_split")


def flash_attention_call(
    kernel: str, batch: int, seq: int, num_heads: int, head_dim: int, itemsize: int = 2,
    causal: bool = False,
) -> Tuple[float, float]:
    """(operations, bytes) of one call of a flash-attention kernel on
    ``[batch, seq, num_heads, head_dim]`` operands. The per-row
    log-sum-exp and delta vectors ([B, H, S] float32) are counted too.
    A causal call needs half the score-sized work."""
    matmuls, tensors = _FLASH[kernel]
    ops = matmuls * 2.0 * batch * num_heads * seq * seq * head_dim
    if causal:
        ops *= 0.5
    rows = batch * num_heads * seq * 4.0
    vectors = {"flash_attention_fwd": 1, "flash_attention_bwd_dq": 2, "flash_attention_bwd_dkv": 2}[kernel]
    return ops, tensors * batch * seq * num_heads * head_dim * float(itemsize) + vectors * rows


def least_seconds(ops: float, nbytes: float, peaks: Dict) -> Tuple[float, str]:
    """The least time the chip could take, and which roof sets it."""
    t_ops = ops / peaks["bf16_flops_per_s"]
    t_bytes = nbytes / peaks["hbm_bytes_per_s"]
    return (t_ops, "compute") if t_ops >= t_bytes else (t_bytes, "memory")
