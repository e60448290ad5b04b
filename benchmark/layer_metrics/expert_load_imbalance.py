"""Experts (``generation/decoder.py::expert_ffn``, counted on the device
by the engine's step programs): over the window, the busiest expert's
growth of ``experts.tokens_total`` (``/v2/stats``: tokens handed to the
expert, summed over the expert layers) over the mean expert's. 1 is an
even load; the router's bias exists to keep it there."""


def growth(ctx):
    """Per-expert growth of ``tokens_total`` between the window's two
    snapshots, or None where the program has no such section."""
    a, b = (ctx.get("stats_open") or {}).get("experts"), (ctx.get("stats_close") or {}).get("experts")
    if not a or not b or "tokens_total" not in a or "tokens_total" not in b:
        return None
    return [y - x for x, y in zip(a["tokens_total"], b["tokens_total"])]


def read(ctx):
    grew = growth(ctx)
    if not grew or sum(grew) <= 0:
        return None
    return max(grew) / (sum(grew) / len(grew))
