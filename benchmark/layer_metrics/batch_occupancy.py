"""Scheduler: how full the decode batch ran. Tokens that decode steps
produced inside the window (every SSE token event but a request's
first, which prefill produces) over decode steps taken x slots."""


def read(ctx):
    if "engine_open" not in ctx:
        return None
    steps = ctx["engine_close"]["step_counts"]["decode"] - ctx["engine_open"]["step_counts"]["decode"]
    if steps <= 0:
        return None
    lo, hi = ctx["window"]
    tokens = sum(lo <= t < hi for r in ctx["records"] for t in r["token_times"][1:])
    return 100.0 * tokens / (steps * ctx["slots"])
