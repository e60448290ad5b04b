"""Search (``search/unity.py``): seconds of ``unity_optimize`` that are
not calibration: span ``ff.startup.search`` less its child
``.calibrate``, which is the substitution search and its cost
evaluations (``.unity``), the memory fit where it runs (``.memory_fit``),
the pipeline and context-parallel candidates (``.candidates``) and the
lowering of the winner to a strategy. None before the program's PR 50."""
from benchmark import startup


def read(ctx):
    whole = startup.phase_seconds(ctx, ["search"])
    return None if whole is None else whole - (startup.phase_seconds(ctx, ["search.calibrate"]) or 0.0)
