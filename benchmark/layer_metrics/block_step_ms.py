"""Engine (``generation/engine.py``, block diffusion): host-clock
milliseconds per block step over the window, as ``decode_step_ms`` reads
a decode step's: the growth of ``engine.phase_time_s["block_step"]``
(dispatch + execute + readback) over the growth of
``engine.step_counts["block_step"]``. A program without that step kind
is not read."""


def read(ctx):
    if "engine_open" not in ctx:
        return None
    a, b = ctx["engine_open"], ctx["engine_close"]
    if "block_step" not in b["step_counts"] or "block_step" not in a["step_counts"]:
        return None
    steps = b["step_counts"]["block_step"] - a["step_counts"]["block_step"]
    if steps <= 0:
        return None
    secs = sum(b["phase_time_s"]["block_step"].values()) - sum(a["phase_time_s"]["block_step"].values())
    return 1e3 * secs / steps
