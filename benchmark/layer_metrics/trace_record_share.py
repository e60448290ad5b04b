"""Scheduler (``obs/steptrace.py``, ``obs/flight.py``): share of the
window's seconds the scheduler thread spent keeping its own records, the
``ff.sched.observe`` spans (the step anatomy's observation of each
working iteration, the flight record, the ``admit_stall`` observations),
as growth of ``<kind>.observe`` in ``step_phases`` of ``/v2/stats``: what
the always-on tracing layer costs, beside the spans' own open and close
(about a microsecond each, inside the spans they time). A program
without the key (before its PR 37) gives None."""
from benchmark.layer_metrics import dispatch_upload_share


def read(ctx):
    return dispatch_upload_share.read(ctx, "observe")
