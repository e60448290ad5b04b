"""Service, seen from the load generator: the share of the window's
token gaps longer than twice their median: how often a running stream
was held up (an admission's drain and prefill), counted rather than read
off one rank. The other candidate for a judged tail (see
``itl_p95_ms``); no chip run stands behind it yet (PERF.md §7, PR 22)."""
from benchmark import stats


def read(ctx):
    gaps = stats.window_gaps_ms(ctx)
    if not gaps:
        return None
    limit = 2.0 * stats.median(gaps)
    return 100.0 * sum(g > limit for g in gaps) / len(gaps)
