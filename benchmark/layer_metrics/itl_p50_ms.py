"""End to end, at the client: median over all gaps between consecutive
SSE token events of the requests due inside the window."""
from benchmark import stats


def read(ctx):
    gaps = stats.window_gaps_ms(ctx)
    return stats.median(gaps) if gaps else None
