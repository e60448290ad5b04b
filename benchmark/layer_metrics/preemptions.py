"""Cache / scheduler: sequences preempted (evicted and recomputed)
inside the window, the ``preemptions`` counter of ``/v2/stats``."""


def read(ctx):
    if "stats_open" not in ctx:
        return None
    return ctx["stats_close"]["preemptions"] - ctx["stats_open"]["preemptions"]
