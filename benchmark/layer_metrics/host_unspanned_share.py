"""Scheduler (``generation/scheduler.py``, ``obs/steptrace.py``): share of
the window's seconds that the scheduler's thread spent in NO named span.
Two parts, both growth between the window's two ``/v2/stats`` snapshots:
``<kind>.unspanned`` of ``step_phases`` (per working iteration, its wall
less the union of its host-lane spans) and, of section ``loop``,
``wall_total_s`` less ``working_total_s``, ``empty_total_s`` and
``idle_wait_total_s`` (the loop's own lines between iterations). With the
``host_*_share`` readers it closes the account of the thread: their sum,
this, and the ``loop`` section's empty and idle-wait seconds are the
window. A program without the section or the key (before its PR 37)
gives None."""
from benchmark import inside

OTHERS = ("working_total_s", "empty_total_s", "idle_wait_total_s")


def read(ctx):
    a, b = ((ctx.get(k) or {}).get("loop") for k in ("stats_open", "stats_close"))
    phases = (ctx.get("stats_close") or {}).get("step_phases") or {}
    if not a or not b or not any(k.endswith(".unspanned") for k in phases):
        return None
    seconds = inside.phase_seconds(ctx, ["unspanned"])
    if "wall_total_s" in b:  # absent where something else than the scheduler's own loop drives it
        seconds += sum((b[k] - a[k]) * (1 if k == "wall_total_s" else -1) for k in ("wall_total_s",) + OTHERS)
    return inside.share_of_window(ctx, seconds)
