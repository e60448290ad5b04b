"""Scheduler (``generation/scheduler.py``): share of the window's decode
steps that the overlap pipeline dispatched (``_dispatch_pipeline``: the
step goes out while the step before it executes, its bookkeeping inside
that window) and not the sequential body (host and device in turn).
Growth of ``pipelined_steps_total`` over growth of ``decode_steps_total``
between the window's two snapshots of ``/v2/stats`` section ``pipeline``
(whose ``drains_total`` by reason and ``reclaims_total`` say why the rest
were not). A program without the section (before its PR 30) gives None."""


def read(ctx):
    a, b = ((ctx.get(k) or {}).get("pipeline") for k in ("stats_open", "stats_close"))
    if not a or not b:
        return None
    steps = b["decode_steps_total"] - a["decode_steps_total"]
    if steps <= 0:
        return None
    return 100.0 * (b["pipelined_steps_total"] - a["pipelined_steps_total"]) / steps
