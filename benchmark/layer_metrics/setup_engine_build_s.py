"""Start-up (``generation/engine.py``): seconds inside
``GenerationEngine.__init__`` (span ``ff.startup.engine_build``: the
block pools, staging buffers, tables, the kernels chosen and the eager
programs that fill them). Part of ``setup_s``. None before the
program's PR 50."""
from benchmark import startup


def read(ctx):
    return startup.phase_seconds(ctx, ["engine_build"])
