"""Engine: share of the window's seconds the scheduler thread spent in
``ff.engine.*.readback`` spans (sampled tokens and the finiteness flags
brought to the host), from the growth of ``step_phases`` in
``/v2/stats``. See ``host_dispatch_share``."""
from benchmark import inside


def read(ctx):
    return inside.share_of_window(ctx, inside.phase_seconds(ctx, ["readback"]))
