"""Service, seen from the load generator: the median over the window's
requests of a request's MEAN gap between consecutive SSE token events
(its streaming time over its gaps), the quantity the cell's gap limit is
set on. Where ``itl_p50_ms`` is the typical single step, this holds each
request's share of the admission stalls too. Recorded beside it since
PR 26 as a candidate for the judged gap."""
from benchmark import stats


def read(ctx):
    means = [sum(g) / len(g) for g in map(stats.inter_token_gaps_ms, stats.window_ok(ctx)) if g]
    return stats.median(means) if means else None
