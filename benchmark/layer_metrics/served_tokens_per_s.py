"""End to end, at the client: prompt + generated tokens of the requests
COMPLETED inside the window, over the window's seconds."""
from benchmark import stats


def read(ctx):
    if "records" not in ctx:
        return None
    lo, hi = ctx["window"]
    done = stats.completed_in_window(ctx["records"], lo, hi)
    return sum(r["prompt_len"] + len(r["tokens"]) for r in done) / (hi - lo)
