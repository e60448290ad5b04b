"""Engine (``generation/engine.py``): host-clock milliseconds per decode
step over the window: the growth of ``engine.phase_time_s["decode"]``
(dispatch + execute + readback, ending in ``block_until_ready``) over
the growth of ``engine.step_counts["decode"]``."""


def read(ctx):
    if "engine_open" not in ctx:
        return None
    a, b = ctx["engine_open"], ctx["engine_close"]
    steps = b["step_counts"]["decode"] - a["step_counts"]["decode"]
    if steps <= 0:
        return None
    secs = sum(b["phase_time_s"]["decode"].values()) - sum(a["phase_time_s"]["decode"].values())
    return 1e3 * secs / steps
