"""Search (``search/calibration.py``): seconds resolving the cost model's
calibration inside the Unity search (span ``ff.startup.search.calibrate``;
its argument ``calibration`` says which branch: the cache's table, the
committed one, a live measurement on the chip, or analytic). With
``search_unity_s`` and ``search_init_s`` it sums to ``search_s``. None
before the program's PR 50."""
from benchmark import startup


def read(ctx):
    return startup.phase_seconds(ctx, ["search.calibrate"])
