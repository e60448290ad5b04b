"""Engine (``generation/engine.py``): share of the window's seconds the
scheduler thread spent in ``ff.engine.*.dispatch`` spans (argument
staging and the jit call, prefill, decode and verify), from the growth
of ``step_phases`` in ``/v2/stats``. With ``host_readback_share`` and
``host_sched_share`` the program's own account of why the device could
idle: beside ``device_idle_share`` it says whether the pipelined loop
hides the host's work (shares above the idle share) or not."""
from benchmark import inside


def read(ctx):
    return inside.share_of_window(ctx, inside.phase_seconds(ctx, ["dispatch"]))
