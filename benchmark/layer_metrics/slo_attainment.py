"""Service: the share of the window's requests inside both of the cell's
limits (first token from due, mean gap); failed or refused = missed.
Recorded, never judged."""
from benchmark import stats


def read(ctx):
    if "records" not in ctx:
        return None
    due = stats.due_in_window(ctx["records"], *ctx["window"])
    if not due:
        return None
    lim = ctx["cell"].workload["limits"]
    return 100.0 * sum(stats.slo_met(r, lim["ttft_ms"], lim["mean_gap_ms"]) for r in due) / len(due)
