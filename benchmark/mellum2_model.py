"""Operations and bytes the algorithm needs for the kernel and the step
program that Mellum2-12B-A2.5B (``configs/mellum2-12b.json``) added,
from the shapes alone, beside ``kernel_model.py`` and ``moe_model.py``
and under their rules: multiply-adds as two operations in matmuls; every
operand read once and every result written once at its stored width.
Kept with the benchmark so that no PR that speeds a program up can also
change what it is measured against.
"""
from __future__ import annotations

from typing import Dict, Iterable, Tuple

from benchmark import moe_model


def window_positions(contexts: Iterable[int], model: Dict) -> int:
    """Cache positions ONE window layer's call has to read for rows at
    ``contexts`` (each a row's context length, its new token included):
    a row reads its window and, since blocks are what is read, the rest
    of the block the window starts in: ``min(context, window + block)``."""
    reach = model["window"] + model["block_size"]
    return sum(min(int(c), reach) for c in contexts)


def paged_window_attention_call(positions: int, query_rows: int, model: Dict) -> Tuple[float, float]:
    """(operations, bytes) of ONE window layer's decode-attention call
    that reads ``positions`` cache positions in all
    (:func:`window_positions`): operations over the query heads, bytes
    over the K/V heads, as ``moe_model.paged_gqa_attention_call``."""
    return moe_model.paged_gqa_attention_call(
        positions, query_rows, model["num_heads"], model["kv_heads"], model["head_dim"], model["cache_itemsize"]
    )


def weights(model: Dict) -> Dict[str, float]:
    """Parameter counts of one layer's parts and of the two vocabulary
    matrices, from ``ctx["model"]``."""
    e, d = model["hidden_size"], model["head_dim"]
    return {
        "attention": e * d * (2 * model["num_heads"] + 2 * model["kv_heads"]) + 2 * d,
        "expert": 3 * e * model["moe_ff_size"],
        "router": e * model["num_experts"],
        "norms": 2 * e,
        "head": model["vocab_size"] * e,
    }


def window_decode_step(
    model: Dict, rows: float, full_context: float, window_positions_read: float, experts_touched: float
) -> Tuple[float, float]:
    """(operations, bytes) of ONE decode step of ``rows`` live tokens
    whose full layers attend ``full_context`` cache positions in all and
    whose window layers read ``window_positions_read`` each, touching
    ``experts_touched`` experts a layer.

    Bytes: every weight the step reads, once — the attention matrices,
    norms and router (float32) of every layer, of each layer's experts
    those that some token was routed to, the live tokens' rows of the
    embedding and the whole untied head — plus K/V by kind of layer (the
    attended positions read, the rows' K/V written). Operations: a row's
    matmuls through attention, its ``experts_per_token`` experts, the
    router and the head; its attention over what each layer reads."""
    w, it = weights(model), model["weight_itemsize"]
    n_full, n_win = model["attention_layers"], model["window_layers"]
    layers = n_full + n_win
    weight_bytes = it * (
        layers * (w["attention"] + w["norms"] + experts_touched * w["expert"]) + w["head"]
        + (rows + 1) * model["hidden_size"]
    ) + 4.0 * layers * w["router"]
    kv = model["kv_heads"] * model["head_dim"] * model["cache_itemsize"]
    kv_bytes = 2.0 * kv * (n_full * (full_context + rows) + n_win * (window_positions_read + rows))
    per_row = layers * (w["attention"] + model["experts_per_token"] * w["expert"] + w["router"]) + w["head"]
    attended = n_full * full_context + n_win * window_positions_read
    ops = 2.0 * rows * per_row + 4.0 * attended * model["num_heads"] * model["head_dim"]
    return ops, weight_bytes + kv_bytes
