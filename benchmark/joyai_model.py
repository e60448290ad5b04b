"""Operations and bytes the algorithm needs for the kernel and the step
program that JoyAI-LLM-Flash (``configs/joyai-llm-flash.json``) added,
from the shapes alone, beside ``kernel_model.py``, ``moe_model.py`` and
``mellum2_model.py`` and under their rules: multiply-adds as two
operations in matmuls; every operand read once and every result written
once. A cached latent row is counted at its PUBLISHED width (576 values:
the fill to 640 lanes is the program's, and shows as a lower share).
Kept with the benchmark so that no PR that speeds a program up can also
change what it is measured against.
"""
from __future__ import annotations

from typing import Dict, Tuple


def entry_bytes(model: Dict) -> float:
    """Bytes of ONE layer's cache row of one position, as published."""
    return (model["kv_lora_rank"] + model["qk_rope_head_dim"]) * model["cache_itemsize"]


def paged_latent_attention_call(positions: float, query_rows: float, model: Dict) -> Tuple[float, float]:
    """(operations, bytes) of ONE latent layer's absorbed decode-attention
    call that reads ``positions`` cache positions in all (the sum of the
    rows' contexts): per position and query head ``latent width``
    multiply-adds for the score and ``kv_lora_rank`` for the value; every
    attended row read ONCE, whatever the number of heads; the queries
    read at the row's width and the attended rows written at
    ``kv_lora_rank``, a head."""
    h, width, rank = model["num_heads"], model["kv_lora_rank"] + model["qk_rope_head_dim"], model["kv_lora_rank"]
    ops = 2.0 * positions * h * (width + rank)
    io_bytes = query_rows * h * (width + rank) * model["weight_itemsize"]
    return ops, positions * entry_bytes(model) + io_bytes


def weights(model: Dict) -> Dict[str, float]:
    """Parameter counts of one layer's parts and of the two vocabulary
    matrices, from ``ctx["model"]``."""
    e, h = model["hidden_size"], model["num_heads"]
    qk, rank = model["qk_nope_head_dim"] + model["qk_rope_head_dim"], model["kv_lora_rank"]
    return {
        "attention": (
            e * model["q_lora_rank"] + model["q_lora_rank"] * h * qk + e * (rank + model["qk_rope_head_dim"])
            + rank * h * (model["qk_nope_head_dim"] + model["v_head_dim"]) + h * model["v_head_dim"] * e
        ),
        "expert": 3 * e * model["moe_ff_size"],
        "dense_ffn": 3 * e * model["ff_size"],
        "router": e * model["num_experts"] + model["num_experts"],
        "norms": 2 * e + model["q_lora_rank"] + rank,
        "head": model["vocab_size"] * e,
    }


def experts_touched(model: Dict, rows: float) -> float:
    """Held experts of a layer that some token of ``rows`` live rows is
    routed to, expected: each row picks ``experts_per_token`` of the
    ``num_experts`` the router scores."""
    return model["experts_held"] * (1.0 - (1.0 - model["experts_per_token"] / model["num_experts"]) ** rows)


def latent_decode_step(model: Dict, rows: float, context: float, touched: float) -> Tuple[float, float]:
    """(operations, bytes) of ONE decode step of ``rows`` live tokens
    that attend ``context`` cache positions in all, touching ``touched``
    of a layer's held experts.

    Bytes: every held weight the step reads, once — attention, norms,
    router (float32) and shared experts of every layer, the dense
    layers' feed-forward, of each expert layer's HELD experts those some
    token was routed to, the live tokens' rows of the embedding and the
    whole untied head — plus the latent rows: the attended positions
    read and the rows' own written, every latent layer. Operations: a
    row's matmuls through attention in the absorbed form, the shared
    experts, the held experts it is routed to (``experts_per_token x
    held / experts`` expected), the router and the head; its attention
    over its context."""
    w, it = weights(model), model["weight_itemsize"]
    layers, n_moe = model["latent_layers"], model["expert_layers"]
    n_dense = layers - n_moe
    weight_bytes = it * (
        layers * (w["attention"] + w["norms"]) + n_dense * w["dense_ffn"]
        + n_moe * (model["shared_experts"] + touched) * w["expert"] + w["head"] + (rows + 1) * model["hidden_size"]
    ) + 4.0 * n_moe * w["router"]
    row_bytes = layers * (context + rows) * entry_bytes(model)
    routed_here = model["experts_per_token"] * model["experts_held"] / model["num_experts"]
    per_row = (
        layers * w["attention"] + n_dense * w["dense_ffn"]
        + n_moe * ((model["shared_experts"] + routed_here) * w["expert"] + w["router"]) + w["head"]
    )
    attention_ops, _ = paged_latent_attention_call(context, rows, model)
    return 2.0 * rows * per_row + layers * attention_ops, weight_bytes + row_bytes


def latent_share(model: Dict, rows: float, context: float) -> float:
    """The share of a decode step's bytes that are latent rows."""
    layers = model["latent_layers"]
    _, total = latent_decode_step(model, rows, context, experts_touched(model, rows))
    return layers * (context + rows) * entry_bytes(model) / total
