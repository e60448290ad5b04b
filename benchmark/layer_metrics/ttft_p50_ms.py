"""End to end, at the client: median of (first SSE token event - when
the request was DUE) over the requests due inside the window."""
from benchmark import stats


def read(ctx):
    ttft = stats.window_ttft_ms(ctx)
    return stats.median(ttft) if ttft else None
