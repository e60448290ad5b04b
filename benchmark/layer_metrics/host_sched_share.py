"""Scheduler: share of the window's seconds the scheduler thread spent
in its own spans, ``ff.sched.schedule | admit | prefix_plan | draft |
sample | bookkeep | housekeep`` (everything of an iteration that is not
the engine's dispatch, block or readback), from the growth of
``step_phases`` in ``/v2/stats``. See ``host_dispatch_share``."""
from benchmark import inside

PHASES = ("schedule", "admit", "prefix_plan", "draft", "sample", "bookkeep", "housekeep")


def read(ctx):
    return inside.share_of_window(ctx, inside.phase_seconds(ctx, PHASES))
