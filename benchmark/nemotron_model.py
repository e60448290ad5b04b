"""Operations and bytes the algorithm needs for the update call, a decode
step and a prefill that NVIDIA-Nemotron-3-Super-120B-A12B
(``configs/nemotron-3-super-120b-a12b.json``) added, from the shapes alone,
beside ``kernel_model.py`` and ``moe_model.py`` and under their rules:
multiply-adds as two operations in matmuls; every operand read once and
every result written once at its stored width. Kept with the benchmark so
that no PR that speeds a program up can also change what it is measured
against.

A state-space layer's decode step reads a live sequence's recurrent state
``S`` [H, P, N] float32 once and writes it once: 5 operations a state value
(the decay's product, the rank-1 update's product and sum, the product with
``C`` and its sum), which at 8 bytes moved a value is far under the chip's
ratio: the call is bound by the state's bytes.
"""
from __future__ import annotations

from typing import Dict, Tuple


def weights(model: Dict) -> Dict[str, float]:
    """Parameters of each part of the served cut (counts, not bytes)."""
    e, di = model["hidden_size"], model["ssm_heads"] * model["ssm_head_dim"]
    cw = di + 2 * model["ssm_groups"] * model["ssm_state_size"]
    q, kv = model["num_heads"] * model["head_dim"], model["kv_heads"] * model["head_dim"]
    return {
        "ssm": e * (di + cw + model["ssm_heads"]) + cw * (model["ssm_conv_kernel"] + 1) + di * e + di + e,
        "attention": 2 * e * q + 2 * e * kv + e,
        "router": e * model["num_experts"],
        "expert": 2.0 * model["moe_latent_size"] * model["moe_ff_size"],
        "expert_outside": 2.0 * e * model["moe_latent_size"] + 2.0 * e * model["shared_ff_size"] + e,
        "head": e * model["vocab_size"],
    }


def state_values(model: Dict) -> float:
    """One sequence's recurrent state of ONE state-space layer, in values."""
    return float(model["ssm_heads"] * model["ssm_head_dim"] * model["ssm_state_size"])


def update_call(model: Dict, slots: float) -> Tuple[float, float]:
    """(operations, bytes) of ONE layer's state-update call over ``slots``
    slots (the call visits every slot of the engine: a slot that is not
    live is passed through): the float32 state read and written, and a
    slot's step rows: ``exp(dt A)`` and ``dt x`` in, ``y`` out [H, P]
    float32, ``B`` and ``C`` [G, N] float32."""
    values = state_values(model)
    rows = 3.0 * model["ssm_heads"] * model["ssm_head_dim"] + 2.0 * model["ssm_groups"] * model["ssm_state_size"]
    return 5.0 * slots * values, 4.0 * slots * (2.0 * values + rows)


def experts_touched(model: Dict, rows: float) -> float:
    """Held experts of a layer that some row of a step of ``rows`` rows
    picked, expected under a uniform router over all its outputs."""
    return model["experts_held"] * (1.0 - (1.0 - model["experts_per_token"] / model["num_experts"]) ** rows)


def decode_step(model: Dict, rows: float, context_positions: float) -> Tuple[float, float]:
    """(operations, bytes) of ONE decode step of ``rows`` live rows whose
    contexts add up to ``context_positions``.

    Bytes: every weight the step reads, once — the state-space and
    attention layers whole, of each expert layer the router (float32), the
    latent projections, the shared expert and the held experts some row
    picked (:func:`experts_touched`), the rows' rows of the embedding and
    the whole head; every state-space layer's state of the LIVE rows read
    and written (float32) and their convolution rows; the attention layers'
    K/V: the contexts read, the rows written. Operations: a row's matmuls
    through every layer (of the experts those of its picks that land on a
    held one, in the mean), the recurrence (5 a state value), attention
    over its context."""
    w, it = weights(model), model["weight_itemsize"]
    n_m, n_a, n_e = model["ssm_layers"], model["attention_layers"], model["expert_layers"]
    touched = experts_touched(model, rows)
    weight_bytes = it * (
        n_m * w["ssm"] + n_a * w["attention"] + n_e * (w["expert_outside"] + touched * w["expert"]) + w["head"]
        + (rows + 1) * model["hidden_size"]
    ) + 4.0 * n_e * w["router"]
    cw = model["ssm_heads"] * model["ssm_head_dim"] + 2 * model["ssm_groups"] * model["ssm_state_size"]
    state_bytes = n_m * rows * (2.0 * 4.0 * state_values(model) + 2.0 * (model["ssm_conv_kernel"] - 1) * cw * it)
    kv_bytes = 2.0 * n_a * model["kv_heads"] * model["head_dim"] * model["cache_itemsize"] * (context_positions + rows)
    landed = model["experts_per_token"] * model["experts_held"] / model["num_experts"]
    per_row = (n_m * w["ssm"] + n_a * w["attention"] + n_e * (w["router"] + w["expert_outside"] + landed * w["expert"]) + w["head"])
    ops = 2.0 * rows * per_row + 5.0 * n_m * rows * state_values(model) + 4.0 * n_a * context_positions * model["num_heads"] * model["head_dim"]
    return ops, weight_bytes + state_bytes + kv_bytes


def prefill(model: Dict, tokens: float) -> Tuple[float, float]:
    """(operations, bytes) of ONE prefill of ``tokens`` prompt tokens: the
    weights once (every held expert), a token's matmuls, its recurrence
    and causal attention; the state handed over and the K/V written."""
    w, it = weights(model), model["weight_itemsize"]
    n_m, n_a, n_e = model["ssm_layers"], model["attention_layers"], model["expert_layers"]
    weight_bytes = it * (n_m * w["ssm"] + n_a * w["attention"] + n_e * (w["expert_outside"] + model["experts_held"] * w["expert"])
                         + w["head"] + tokens * model["hidden_size"]) + 4.0 * n_e * w["router"]
    landed = model["experts_per_token"] * model["experts_held"] / model["num_experts"]
    per_row = n_m * w["ssm"] + n_a * w["attention"] + n_e * (w["router"] + w["expert_outside"] + landed * w["expert"])
    ops = (2.0 * tokens * per_row + 2.0 * w["head"] + 5.0 * n_m * tokens * state_values(model)
           + 2.0 * n_a * tokens * (tokens + 1) * model["num_heads"] * model["head_dim"])
    written = n_m * 4.0 * state_values(model) + 2.0 * n_a * model["kv_heads"] * model["head_dim"] * model["cache_itemsize"] * tokens
    return ops, weight_bytes + written
