"""Service, seen from the load generator: 95th percentile over all gaps
between consecutive SSE token events of the requests due inside the
window. A candidate for a judged tail: five times the samples beyond it
that ``itl_p99_ms`` has (~1,290 of ~25,900 gaps in ``chat-steady``).
There it is a step that waited for part of an admission's prefill, and
its quartiles over twelve runs lie 5.7 % of the median apart (my chip
runs, PR 26): recorded, not judged."""
from benchmark import stats


def read(ctx):
    gaps = stats.window_gaps_ms(ctx)
    return stats.percentile(gaps, 95) if gaps else None
