"""Cache (``generation/cache.py``, latent rows): how much of a decode
step's reading the latent cache is. Of all the bytes
``benchmark/joyai_model.py::latent_decode_step`` reckons for a step —
held weights, of the held experts those touched, the latent rows — the
share that are latent rows, at the live contexts the ``cache.latent``
section of ``/v2/stats`` reports (``tokens_held``: the positions the last
decode step's sequences held), sampled once a second through the window,
the mean. The rows a step had: the window's token events over its decode
steps."""
from benchmark import joyai_model


def read(ctx):
    model = ctx.get("model") or {}
    held = [
        c["tokens_held"] for s in ctx.get("stats_samples", [])
        if (c := (s.get("cache") or {}).get("latent")) and c.get("tokens_held")
    ]
    if not held or "latent_layers" not in model or "records" not in ctx:
        return None
    a, b = ctx["engine_open"], ctx["engine_close"]
    steps = b["step_counts"]["decode"] - a["step_counts"]["decode"]
    w_lo, w_hi = ctx["window"]
    rows = sum(w_lo <= t < w_hi for r in ctx["records"] for t in r["token_times"][1:])
    if steps <= 0 or rows <= 0:
        return None
    shares = [joyai_model.latent_share(model, rows / steps, context) for context in held]
    return 100.0 * sum(shares) / len(shares)
