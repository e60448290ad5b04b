"""Operations and bytes the algorithm needs for the step program that
LongCat-Flash-Chat (``configs/longcat-flash-chat.json``) added, from the
shapes alone, beside ``kernel_model.py`` and ``joyai_model.py`` (whose
count of the latent kernel's call holds here as it stands: 64 heads over
one 576-wide row) and under their rules: multiply-adds as two operations
in matmuls; every operand read once and every result written once; a
cached latent row at its PUBLISHED width. Kept with the benchmark so that
no PR that speeds a program up can also change what it is measured
against.

The block: ``sub_layers`` sub-layers (two a published layer), each a
latent attention and a dense SwiGLU; every second one also routes: a
router over ``router_outputs`` (the real experts, then the identity
experts), ``experts_per_token`` picks a token, of which those on a HELD
real expert multiply its three matrices and those on an identity expert
add ``gate x u`` (``2 E`` operations a pick, no weight).
"""
from __future__ import annotations

from typing import Dict, List, Optional, Tuple

from benchmark import joyai_model


def weights(model: Dict) -> Dict[str, float]:
    """Parameter counts of one sub-layer's parts, of a routed branch's and
    of the two vocabulary matrices, from ``ctx["model"]``."""
    w = joyai_model.weights(dict(model, num_experts=model["router_outputs"]))
    return {k: w[k] for k in ("attention", "expert", "dense_ffn", "router", "norms", "head")}


def landed_share(model: Dict, picked: Optional[float] = None) -> float:
    """The probability that ONE live token picks ONE given held expert:
    ``picked`` where the counters gave it (held experts' tokens over held
    experts x tokens routed), else a uniform pick's ``k / outputs``."""
    return picked if picked is not None else model["experts_per_token"] / model["router_outputs"]


def experts_touched(model: Dict, rows: float, picked: Optional[float] = None) -> float:
    """Held experts of a routed branch that some token of ``rows`` live
    rows picked, expected."""
    return model["experts_held"] * (1.0 - (1.0 - landed_share(model, picked)) ** rows)


def shortcut_decode_step(model: Dict, rows: float, context: float, picked: Optional[float] = None) -> Tuple[float, float]:
    """(operations, bytes) of ONE decode step of ``rows`` live tokens that
    attend ``context`` cache positions in all.

    Bytes: every held weight the step reads, once — attention, norms and
    the dense SwiGLU of every sub-layer, the router (float32) of every
    routed branch, of its HELD experts those some token picked
    (:func:`experts_touched`), the live tokens' rows of the embedding and
    the whole untied head — plus the latent rows: the attended positions
    read and the rows' own written, every sub-layer. Operations: a row's
    matmuls through attention in the absorbed form, the dense SwiGLUs, the
    router, the held experts it picked and the identity experts' term
    (``k x zero / outputs`` picks of ``2 E``), and the head; its attention
    over its context."""
    w, it = weights(model), model["weight_itemsize"]
    subs, branches = model["sub_layers"], model["routed_branches"]
    touched = experts_touched(model, rows, picked)
    weight_bytes = it * (
        subs * (w["attention"] + w["norms"] + w["dense_ffn"]) + branches * touched * w["expert"]
        + w["head"] + (rows + 1) * model["hidden_size"]
    ) + 4.0 * branches * w["router"]
    row_bytes = subs * (context + rows) * joyai_model.entry_bytes(model)
    landed = landed_share(model, picked) * model["experts_held"]  # held experts a row picks
    zero = model["experts_per_token"] * model["zero_experts"] / model["router_outputs"]
    per_row = (
        subs * (w["attention"] + w["dense_ffn"]) + branches * (landed * w["expert"] + w["router"] + zero * model["hidden_size"])
        + w["head"]
    )
    attention_ops, _ = joyai_model.paged_latent_attention_call(context, rows, model)
    return 2.0 * rows * per_row + subs * attention_ops, weight_bytes + row_bytes


def prefill(model: Dict, prompt_len: int) -> Tuple[float, float]:
    """(operations, bytes) of ONE whole-prompt prefill of ``prompt_len``
    tokens: the rows' matmuls as in a decode step (attention EXPANDED:
    causal scores at ``qk_nope + qk_rope`` and values at ``v_head_dim``
    over ``n (n + 1) / 2`` pairs a head), every held weight read once."""
    w, it = weights(model), model["weight_itemsize"]
    subs, branches, n = model["sub_layers"], model["routed_branches"], float(prompt_len)
    landed = landed_share(model) * model["experts_held"]
    zero = model["experts_per_token"] * model["zero_experts"] / model["router_outputs"]
    per_row = (
        subs * (w["attention"] + w["dense_ffn"]) + branches * (landed * w["expert"] + w["router"] + zero * model["hidden_size"])
    )
    pairs = n * (n + 1) / 2
    scores = 2.0 * pairs * model["num_heads"] * (model["qk_nope_head_dim"] + model["qk_rope_head_dim"] + model["v_head_dim"])
    weight_bytes = it * (
        subs * (w["attention"] + w["norms"] + w["dense_ffn"]) + branches * model["experts_held"] * w["expert"] + w["head"]
    ) + 4.0 * branches * w["router"]
    return 2.0 * n * per_row + 2.0 * w["head"] + subs * scores, weight_bytes + subs * n * joyai_model.entry_bytes(model)


def percentile(histogram: List[float], q: float) -> Optional[float]:
    """The smallest value ``v`` such that at least ``q`` of the
    histogram's mass lies at ``<= v`` (``histogram[v]``: tokens that
    picked ``v`` real experts)."""
    total = float(sum(histogram))
    if total <= 0:
        return None
    seen = 0.0
    for value, count in enumerate(histogram):
        seen += count
        if seen >= q * total:
            return float(value)
    return float(len(histogram) - 1)
