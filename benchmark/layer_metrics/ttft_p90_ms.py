"""Service, seen from the load generator: 90th percentile of (first SSE
token event at the client - when the request was DUE) over the requests
due inside the window. Recorded, not judged: at this window length it
swings by half its value between seeds (PERF.md §6, PR 22)."""
from benchmark import stats


def read(ctx):
    ttft = stats.window_ttft_ms(ctx)
    return stats.percentile(ttft, 90) if ttft else None
