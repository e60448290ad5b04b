"""Scheduler: how long an admission holds the streams that are
decoding. For each admission made while a stream was running, the
program observes ``admit_stall``: the time from the last decode result
consumed before it to the next one consumed after it (``/v2/stats``,
spans on the scheduler thread). Mean over the window's admissions; one
undisturbed decode step is the floor. The mechanism behind the tails
the client sees (``itl_p95_ms``, ``itl_p99_ms``)."""
from benchmark import inside


def read(ctx):
    return inside.mean_ms(ctx, ["admit_stall"])
