"""Service, seen from the load generator: 99th percentile over all gaps
between consecutive SSE token events of the requests due inside the
window: what an admission's pipeline drain and prefill do to the
streams already running. Recorded, not judged: it jumps between ~81 and
~107-134 ms from seed to seed (PERF.md §6, PR 22)."""
from benchmark import stats


def read(ctx):
    gaps = stats.window_gaps_ms(ctx)
    return stats.percentile(gaps, 99) if gaps else None
