"""What the program counts from inside, as deltas over the window.

``/v2/stats`` (``stats_open`` / ``stats_close`` in a serving ``ctx``)
carries, since the program's PR 23, monotone totals beside its rolling
windows: every named window has ``count_total`` and ``sum_total_s``, and
``step_phases`` has ``{"<kind>.<phase>": {"count", "total_s"}}`` from the
scheduler's step anatomy. The readers of ``layer_metrics/`` that are
built on these take their growth between the two snapshots here, so the
lead-in's requests are out. A program that lacks the totals (an older
commit) gives None, and the metric is left out of the line.
"""
from __future__ import annotations

from typing import Dict, Iterable, Optional, Tuple


def window_delta(ctx: Dict, name: str) -> Optional[Tuple[int, float]]:
    """(observations, seconds) that the ``/v2/stats`` window ``name``
    grew by between open and close; None without the cumulative keys.
    A window first observed inside the run is absent at open: zero."""
    close = (ctx.get("stats_close") or {}).get(name)
    if not close or "count_total" not in close or "stats_open" not in ctx:
        return None
    start = ctx["stats_open"].get(name) or {"count_total": 0, "sum_total_s": 0.0}
    return close["count_total"] - start["count_total"], close["sum_total_s"] - start["sum_total_s"]


def mean_ms(ctx: Dict, names: Iterable[str]) -> Optional[float]:
    """Summed growth in seconds of the windows ``names`` over the growth
    in count of the first, in milliseconds: the mean per observation of
    spans that are observed once each per request or event."""
    deltas = [window_delta(ctx, n) for n in names]
    if any(d is None for d in deltas) or deltas[0][0] <= 0:
        return None
    return 1e3 * sum(d[1] for d in deltas) / deltas[0][0]


def phase_seconds(ctx: Dict, phases: Iterable[str]) -> Optional[float]:
    """Seconds that the step anatomy's ``phases`` grew by between open
    and close, summed over every kind of iteration."""
    a, b = (ctx.get("stats_open") or {}).get("step_phases"), (ctx.get("stats_close") or {}).get("step_phases")
    if not a or not b:
        return None
    wanted = set(phases)
    grew = lambda snap: sum(v["total_s"] for k, v in snap.items() if k.split(".", 1)[1] in wanted)
    return grew(b) - grew(a)


def share_of_window(ctx: Dict, seconds: Optional[float]) -> Optional[float]:
    """``seconds`` as a percentage of the window's length."""
    if seconds is None or "window" not in ctx:
        return None
    lo, hi = ctx["window"]
    return 100.0 * seconds / (hi - lo)
