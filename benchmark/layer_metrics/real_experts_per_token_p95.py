"""Experts (``generation/decoder.py::_count_row``, counted on the device
by the engine's step programs): the 95th percentile, over the tokens the
window routed (every routed branch's), of the REAL experts a token picked
among its ``experts_per_token`` picks — from the growth of the histogram
``experts.real_experts_per_token_total`` of ``/v2/stats`` (entry ``n``:
tokens that picked ``n`` real experts, the rest identity experts). The
model's "compute a token varies" as a number: 8 of 12 in the mean on
seeded weights. A program without that histogram is not read."""
from benchmark import longcat_model
from benchmark.layer_metrics.zero_expert_pick_share import grown


def read(ctx):
    real = grown(ctx, "real_experts_per_token_total")
    if real is None:
        return None
    return longcat_model.percentile([b - a for b, a in zip(*real)], 0.95)
