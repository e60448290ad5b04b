"""Engine: the share of the engine's step time (all kinds, all three
phases, host clock) that prefill steps took inside the window."""


def read(ctx):
    if "engine_open" not in ctx:
        return None
    a, b = ctx["engine_open"]["phase_time_s"], ctx["engine_close"]["phase_time_s"]
    grew = {k: sum(b[k].values()) - sum(a[k].values()) for k in b}
    total = sum(grew.values())
    return None if total <= 0 else 100.0 * grew["prefill"] / total
