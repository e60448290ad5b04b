"""Experts (``generation/decoder.py::expert_ffn``, the identity experts;
counted on the device by the engine's step programs): over the window, the
growth of ``experts.zero_picks_total`` (``/v2/stats``: picks that went to
an identity "zero-computation" expert, summed over the routed branches)
over all the picks made there: ``experts_per_token`` x the growth of the
tokens routed (the sum of ``real_experts_per_token_total``). A third on
seeded weights (256 of 768 outputs); the share of a token's picks that
cost ``2 E`` operations and no weight. A program without those counters
is not read."""


def grown(ctx, key):
    a, b = (ctx.get("stats_open") or {}).get("experts"), (ctx.get("stats_close") or {}).get("experts")
    if not a or not b or key not in a or key not in b:
        return None
    return b[key], a[key]


def read(ctx):
    zero, real = grown(ctx, "zero_picks_total"), grown(ctx, "real_experts_per_token_total")
    if zero is None or real is None:
        return None
    picks = (sum(real[0]) - sum(real[1])) * ctx["stats_close"]["experts"]["experts_per_token"]
    if picks <= 0:
        return None
    return 100.0 * (zero[0] - zero[1]) / picks
