"""End to end: the trainer's tokens per second for the whole cell (all
its chips), as the MEDIAN over the window's slices of ``slice_steps``
consecutive optimizer steps: each slice's tokens over the host-clock
time between the completions of the steps at its two ends.

A median over slices and not the window's total because the window is
50 s: one stall of the host (a quarter of a second, a few times a run on
a host whose cores are shared) is 0.5 % of it, which is the whole of a
1 % bound's room, while the steps themselves repeat to 0.01 %. A slice
is long enough (16 steps, the executor's every-8th-step sync twice) that
anything a change adds to most slices moves the median; what hits fewer
than half of them shows in ``train_window_tokens_per_s`` beside it. A
window shorter than one slice is one slice."""
from benchmark import stats


def read(ctx):
    t = ctx.get("train")
    if not t or not t["step_ms"]:
        return None
    ms = t["step_ms"]
    m = min(int(t["slice_steps"]), len(ms))
    tokens = m * t["global_batch"] * t["seq"]
    return stats.median([tokens / (sum(ms[i:i + m]) / 1e3) for i in range(0, len(ms) - m + 1, m)])
