"""Scheduler (``generation/scheduler.py``): share of the window's seconds
the scheduler thread spent in ``ff.sched.release`` spans, the drop of a
consumed decode step's handle (its ``out``, ``ok`` and expert-counter
device arrays), as growth of ``<kind>.release`` in ``step_phases`` of
``/v2/stats``. A host-lane span between the step's bookkeeping and the
next dispatch: every array's destructor gives up the interpreter's lock,
so the span holds whatever other threads then take their turn (the
stream handlers that the bookkeeping has just woken: PERF.md §6, PR 38).
A program without the span (before its PR 37) gives None."""
from benchmark.layer_metrics import dispatch_upload_share


def read(ctx):
    return dispatch_upload_share.read(ctx, "release")
