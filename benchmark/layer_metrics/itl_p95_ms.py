"""Service, seen from the load generator: 95th percentile over all gaps
between consecutive SSE token events of the requests due inside the
window. A candidate for a judged tail: five times the samples beyond it
that ``itl_p99_ms`` has (~165 of ~3,300 gaps in ``chat-open``). Recorded
so that the ledger shows how far it spreads from run to run; no chip run
stands behind it yet (PERF.md §7, PR 22)."""
from benchmark import stats


def read(ctx):
    gaps = stats.window_gaps_ms(ctx)
    return stats.percentile(gaps, 95) if gaps else None
