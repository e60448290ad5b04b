"""Operations and bytes the algorithm needs for the kernels and step
programs that LFM2-8B-A1B (``configs/lfm2-8b-a1b.json``) added, from the
shapes alone, beside ``kernel_model.py`` (whose ``least_seconds`` turns
them into a least time) and under the same rules: multiply-adds as two
operations in matmuls; every operand read once and every result written
once at its stored width. Kept with the benchmark so that no PR that
speeds a program up can also change what it is measured against.
"""
from __future__ import annotations

from typing import Dict, Tuple


def paged_gqa_attention_call(
    context_tokens: int, query_rows: int, num_heads: int, kv_heads: int, head_dim: int, cache_itemsize: int,
    io_itemsize: int = 2,
) -> Tuple[float, float]:
    """(operations, bytes) of ONE layer's grouped-query decode-attention
    call: operations over the ``num_heads`` QUERY heads (per attended
    position and query head, ``head_dim`` multiply-adds for the score and
    as many for the weighted value), bytes over the ``kv_heads`` K/V
    heads (K and V of every attended position read once, whatever the
    number of query heads that read them); q read and the output written
    once per row, at ``num_heads``."""
    ops = 4.0 * context_tokens * num_heads * head_dim
    kv_bytes = 2.0 * context_tokens * kv_heads * head_dim * cache_itemsize
    io_bytes = 2.0 * query_rows * num_heads * head_dim * io_itemsize
    return ops, kv_bytes + io_bytes


def weights(model: Dict) -> Dict[str, float]:
    """Parameter counts of one layer of each kind and of the embedding,
    from ``ctx["model"]`` (the driver's: the configuration's sizes)."""
    e, d = model["hidden_size"], model["head_dim"]
    return {
        "attention": e * d * (2 * model["num_heads"] + 2 * model["kv_heads"]) + 2 * d,
        "conv": 4 * e * e + model["conv_kernel"] * e,
        "dense_ffn": 3 * e * model["ff_size"],
        "expert": 3 * e * model["moe_ff_size"],
        "router": e * model["num_experts"] + model["num_experts"],
        "norms": 2 * e,
        "embedding": model["vocab_size"] * e,
    }


def moe_decode_step(model: Dict, rows: float, context_tokens: float, experts_touched: float) -> Tuple[float, float]:
    """(operations, bytes) of ONE decode step of ``rows`` live tokens
    that attend ``context_tokens`` cache positions in all (summed over
    the rows) and touch ``experts_touched`` experts per expert layer.

    Bytes: every weight the step reads, once — the embedding's rows of
    the live tokens and all of it again as the tied head, every
    operator, norm, router and dense FFN, and of each expert layer the
    experts that some token was routed to (an expert no token chose need
    not be read) — plus K/V of the attended positions (K/V heads only),
    the convolution state read and written, and the rows' K/V written.
    Operations: a row's matmuls through every operator, the dense FFNs,
    the ``experts_per_token`` experts it is routed to, the router and the
    head; its attention over its context."""
    w, it = weights(model), model["weight_itemsize"]
    n_attn, n_conv = model["attention_layers"], model["conv_layers"]
    n_dense, n_moe = model["dense_layers"], model["expert_layers"]
    fixed = (
        n_attn * w["attention"] + n_conv * w["conv"] + n_dense * w["dense_ffn"]
        + (n_attn + n_conv) * w["norms"] + w["embedding"] + model["hidden_size"]
    )
    weight_bytes = it * (fixed + n_moe * experts_touched * w["expert"]) + 4.0 * n_moe * w["router"]
    kv = model["kv_heads"] * model["head_dim"] * model["cache_itemsize"]
    state = n_conv * (model["conv_kernel"] - 1) * model["hidden_size"] * model["cache_itemsize"]
    nbytes = weight_bytes + n_attn * 2.0 * kv * (context_tokens + rows) + 2.0 * rows * state
    per_row = (
        n_attn * w["attention"] + n_conv * w["conv"] + n_dense * w["dense_ffn"]
        + n_moe * (model["experts_per_token"] * w["expert"] + w["router"]) + w["embedding"]
    )
    ops = 2.0 * rows * per_row + n_attn * 4.0 * context_tokens * model["num_heads"] * model["head_dim"]
    return ops, nbytes
