"""Operations and bytes the algorithm needs for the update call, the call
that reads the ONE shared K/V, a decode step and a prefill that
Phi-4-mini-flash-reasoning (``configs/phi-4-mini-flash-reasoning.json``)
added, from the shapes alone, beside ``kernel_model.py`` and the other
``*_model.py`` and under their rules: multiply-adds as two operations in
matmuls; every operand read once and every result written once at its stored
width. Kept with the benchmark so that no PR that speeds a program up can also
change what it is measured against.

``model`` is the driver's ``ctx["model"]`` (``serve_phi4flash.model_sizes``):
the PUBLISHED sizes (40 query heads over 20 K/V heads of 64), not the padded
form the program hands its attention calls: a program that pads its queries
to use a kernel as it stands moves and multiplies more than this, and that
shows as a lower share, which is the point.

A Mamba-1 layer's decode step reads a live sequence's state ``S`` [D, N]
float32 once and writes it once: 6 operations a state value (the decay's
product with ``dt``, its product with ``S``, the input's product and sum, the
product with ``C`` and its sum; the exponential is left out, as the softmax's
are) at 8 bytes moved: far under the chip's ratio, the call is bound by bytes.
"""
from __future__ import annotations

from typing import Dict, Tuple


def weights(model: Dict) -> Dict[str, float]:
    """Parameters of each kind of layer WITHOUT its feed-forward, of the
    feed-forward (a SwiGLU and the layer's two LayerNorms) and of the tied
    embedding (counts, not bytes)."""
    e, d, n, r, k = model["hidden_size"], model["mamba_inner"], model["state_size"], model["dt_rank"], model["conv_kernel"]
    q, kv, hd = model["num_heads"] * model["head_dim"], model["kv_heads"] * model["head_dim"], model["head_dim"]
    return {
        "mamba": e * 2 * d + d * k + d + d * (r + 2 * n) + r * d + d + d * n + d + d * e,
        "attention": e * q + q + 2 * (e * kv + kv) + q * e + e + 6 * hd,
        "cross": e * q + q + q * e + e + 6 * hd,
        "gmu": 2.0 * e * d,
        "ffn": 3.0 * e * model["ff_size"] + 4 * e,
        "head": float(e * model["vocab_size"]),
    }


def state_values(model: Dict) -> float:
    """One sequence's recurrent state of ONE Mamba-1 layer, in values."""
    return float(model["mamba_inner"] * model["state_size"])


def update_call(model: Dict, slots: float) -> Tuple[float, float]:
    """(operations, bytes) of ONE layer's state-update call over ``slots``
    slots (the call visits every slot of the engine: a slot that is not live
    is passed through): the float32 state read and written, a slot's ``dt``
    and ``x`` in and ``y`` out [D] float32, ``B`` and ``C`` [N] float32, and
    the rates ``A`` [N, D] float32 once a call."""
    values, d, n = state_values(model), model["mamba_inner"], model["state_size"]
    return 6.0 * slots * values, 4.0 * (slots * (2.0 * values + 3.0 * d + 2.0 * n) + values)


def shared_kv_call(model: Dict, rows: float, context_positions: float) -> Tuple[float, float]:
    """(operations, bytes) of ONE layer's attention call over the one full
    K/V for ``rows`` live rows whose contexts add up to ``context_positions``
    (the K/V layer's own call or a cross layer's: the same read).
    Differential attention: per query head and attended position 64
    multiply-adds for its score and 128 for its weighted pair of values (6
    operations a unit of the query's width, not 4); K and V of every attended
    position read once at the published width (20 heads of 64), the queries
    in and the pair outputs out (40 x 128 a row) at the cache's width."""
    q, kv = model["num_heads"] * model["head_dim"], model["kv_heads"] * model["head_dim"]
    it = model["cache_itemsize"]
    return 6.0 * context_positions * q, 2.0 * context_positions * kv * it + rows * (q + 2.0 * q) * it


def window_positions(contexts, model: Dict) -> float:
    """Cache positions ONE window layer's call reads for rows at
    ``contexts``: ``min(context, window + block)`` a row, as
    ``mellum2_model.window_positions``."""
    reach = model["window"] + model["block_size"]
    return float(sum(min(int(c), reach) for c in contexts))


def decode_step(model: Dict, rows: float, context_positions: float, window_positions_read: float) -> Tuple[float, float]:
    """(operations, bytes) of ONE decode step of ``rows`` live rows whose
    contexts add up to ``context_positions`` and whose window layers read
    ``window_positions_read`` positions each.

    Bytes: every weight once (all 32 layers; the tied embedding once, as
    the head, and the rows' rows of it); every Mamba layer's state of the
    live rows read and written (float32) and their convolution rows; the
    ONE full K/V read once a layer that attends it (the K/V layer and the
    cross layers) and the rows' K/V written once; the window layers' K/V
    read and written. Operations: a row's matmuls through every layer, the
    recurrence (6 a state value), differential attention over what each
    attending layer reads."""
    w, it = weights(model), model["weight_itemsize"]
    n_m, n_w, n_g, n_c = model["mamba_layers"], model["window_layers"], model["gmu_layers"], model["cross_layers"]
    layers = n_m + n_w + 1 + n_g + n_c
    per_row = n_m * w["mamba"] + (n_w + 1) * w["attention"] + n_g * w["gmu"] + n_c * w["cross"] + layers * w["ffn"] + w["head"]
    weight_bytes = it * (per_row + rows * model["hidden_size"])
    d = model["mamba_inner"]
    state_bytes = n_m * rows * (2.0 * 4.0 * state_values(model) + 2.0 * (model["conv_kernel"] - 1) * d * it)
    kv = model["kv_heads"] * model["head_dim"] * model["cache_itemsize"]
    kv_bytes = 2.0 * kv * ((1 + n_c) * context_positions + rows + n_w * (window_positions_read + rows))
    q = model["num_heads"] * model["head_dim"]
    ops = (2.0 * rows * per_row + 6.0 * n_m * rows * state_values(model)
           + 6.0 * q * ((1 + n_c) * context_positions + n_w * window_positions_read))
    return ops, weight_bytes + state_bytes + kv_bytes


def prefill(model: Dict, tokens: float) -> Tuple[float, float]:
    """(operations, bytes) of ONE prefill of ``tokens`` prompt tokens: the
    weights once; the self-decoder's layers (Mamba, window, the K/V layer)
    over every token, the cross-decoder's (GMU, cross) and the head over
    ONE row; the recurrence; causal attention in the K/V layer, windowed
    in the window layers, one row over every position in the cross layers;
    the state handed over and the K/V written."""
    w, it = weights(model), model["weight_itemsize"]
    n_m, n_w, n_g, n_c = model["mamba_layers"], model["window_layers"], model["gmu_layers"], model["cross_layers"]
    every = n_m * w["mamba"] + (n_w + 1) * w["attention"] + (n_m + n_w + 1) * w["ffn"]
    last = n_g * w["gmu"] + n_c * w["cross"] + (n_g + n_c) * w["ffn"] + w["head"]
    weight_bytes = it * (every + last + tokens * model["hidden_size"])
    q, win = model["num_heads"] * model["head_dim"], min(tokens, model["window"])
    attended = tokens * (tokens + 1) / 2.0 + n_w * (win * (win + 1) / 2.0 + (tokens - win) * win) + n_c * tokens
    ops = 2.0 * (tokens * every + last) + 6.0 * n_m * tokens * state_values(model) + 6.0 * q * attended
    kv = model["kv_heads"] * model["head_dim"] * model["cache_itemsize"]
    written = n_m * 4.0 * state_values(model) + 2.0 * kv * tokens * (1 + n_w)
    return ops, weight_bytes + written
