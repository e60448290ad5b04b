"""Scheduler (``generation/scheduler.py``): 95th percentile of the time
a request waited between submit and admission, the ``queue_time`` window
of ``/v2/stats`` at the window's close (rolling, last 512 requests)."""


def read(ctx):
    q = ctx.get("stats_close", {}).get("queue_time")
    return None if not q else q["p95_s"] * 1e3
