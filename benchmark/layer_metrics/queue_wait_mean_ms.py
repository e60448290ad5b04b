"""Scheduler (``generation/scheduler.py``): mean time a request waited
between submit and admission, over the requests admitted INSIDE the
window: growth of ``queue_time``'s ``sum_total_s`` over growth of its
``count_total`` (``/v2/stats``, program counter). The twin of
``queue_wait_p95_ms``, whose rolling window also holds the lead-in."""
from benchmark import inside


def read(ctx):
    return inside.mean_ms(ctx, ["queue_time"])
