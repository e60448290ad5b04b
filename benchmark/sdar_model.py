"""Operations and bytes the algorithm needs for the step program and the
paged call that SDAR-30B-A3B-Chat (``configs/sdar-30b-a3b-chat.json``)
added, from the shapes alone, beside ``kernel_model.py`` and
``moe_model.py`` and under their rules: multiply-adds as two operations
in matmuls; every operand read once and every result written once at
its stored width. Kept with the benchmark so that no PR that speeds a
program up can also change what it is measured against.

A block forward runs ``B`` rows a live slot: each at its own position,
all attending every earlier block's K/V and the block's own ``B`` rows.
The K/V a slot's rows attend is read ONCE for the slot (the rows share
it), the block's own K/V rows are written at every forward.
"""
from __future__ import annotations

from typing import Dict, Tuple

from benchmark.mellum2_model import weights  # the same layer's parts: attention over 32 / 4 heads, an expert, the router, norms, a vocabulary matrix


def experts_touched(model: Dict, rows: float) -> float:
    """Experts of a layer that some row of a forward of ``rows`` rows is
    routed to, expected under a uniform router: ``N (1 - (1 - k/N)^rows)``
    (an expert no row chose need not be read)."""
    n, k = model["num_experts"], model["experts_per_token"]
    return n * (1.0 - (1.0 - k / n) ** rows)


def paged_block_attention_call(context_positions: float, slots: float, model: Dict) -> Tuple[float, float]:
    """(operations, bytes) of ONE layer's paged call of a block forward:
    ``slots`` live sequences of ``block_length`` rows each, which attend
    ``context_positions`` cache positions in all, summed over the SLOTS
    (a slot's context: its block's last position + 1). Operations over
    the rows' query heads; K and V of a slot's context read once for all
    its rows, over the K/V heads; q read and the output written a row."""
    rows = slots * model["block_length"]
    ops = 4.0 * context_positions * model["block_length"] * model["num_heads"] * model["head_dim"]
    kv_bytes = 2.0 * context_positions * model["kv_heads"] * model["head_dim"] * model["cache_itemsize"]
    io_bytes = 2.0 * rows * model["num_heads"] * model["head_dim"] * model["weight_itemsize"]
    return ops, kv_bytes + io_bytes


def block_forward(model: Dict, slots: float, context_positions: float) -> Tuple[float, float]:
    """(operations, bytes) of ONE block forward of ``slots`` live slots
    whose contexts add up to ``context_positions``.

    Bytes: every weight the forward reads, once — attention, norms and
    the float32 router of every layer, of each layer's experts those some
    row was routed to (:func:`experts_touched` at ``slots x B`` rows), the
    rows' rows of the embedding and the whole untied head — plus every
    layer's K/V: the slots' contexts read once, the blocks' rows written.
    Operations: a row's matmuls through attention, its
    ``experts_per_token`` experts, the router and the head; its attention
    over its slot's context."""
    w, it, layers = weights(model), model["weight_itemsize"], model["num_layers"]
    rows = slots * model["block_length"]
    touched = experts_touched(model, rows)
    weight_bytes = it * (
        layers * (w["attention"] + w["norms"] + touched * w["expert"]) + w["head"] + (rows + 1) * model["hidden_size"]
    ) + 4.0 * layers * w["router"]
    kv = model["kv_heads"] * model["head_dim"] * model["cache_itemsize"]
    kv_bytes = 2.0 * kv * layers * (context_positions + rows)
    per_row = layers * (w["attention"] + model["experts_per_token"] * w["expert"] + w["router"]) + w["head"]
    attention = layers * paged_block_attention_call(context_positions, slots, model)[0]
    return 2.0 * rows * per_row + attention, weight_bytes + kv_bytes

