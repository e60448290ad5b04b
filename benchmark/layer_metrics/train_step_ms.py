"""Trainer step (``runtime/executor.py``): median host-clock time
between the completions of consecutive optimizer steps in the window."""
from benchmark import stats


def read(ctx):
    t = ctx.get("train")
    return stats.median(t["step_ms"]) if t and t["step_ms"] else None
