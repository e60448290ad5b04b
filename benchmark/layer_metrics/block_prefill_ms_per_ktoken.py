"""Engine (``generation/engine.py``): what a thousand prompt tokens cost
to prefill UNDER THE BLOCK MASK (a prefill that caches the prompt's
whole blocks and computes no logits), host clock, over the window:
``prefill_ms_per_ktoken``'s arithmetic, read only where the program
serves by block diffusion (``/v2/stats`` has a ``diffusion`` section)."""
from benchmark.layer_metrics import prefill_ms_per_ktoken


def read(ctx):
    if not (ctx.get("stats_close") or {}).get("diffusion"):
        return None
    return prefill_ms_per_ktoken.read(ctx)
