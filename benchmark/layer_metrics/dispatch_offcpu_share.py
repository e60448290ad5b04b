"""Engine (``generation/engine.py``): share of the window's seconds that
the scheduler thread spent inside ``ff.engine.decode.dispatch`` spans
WITHOUT holding a core. The program reads ``perf_counter`` and the
thread's CPU clock (``time.thread_time``) at the span's two ends on one
iteration in 16 (the CPU clock is a system call of ~30 us in a serving
process) and keeps both sums in section ``loop`` of ``/v2/stats``
(``decode_dispatch_wall_total_s``, ``decode_dispatch_cpu_total_s``: the
same stamps' places, the same steps). This is the sampled spans'
off-CPU fraction, ``1 - delta CPU / delta wall``, of ALL the window's
decode dispatch seconds (growth of ``engine.phase_time_s["decode"]
["dispatch"]``), over the window. Off the CPU the thread waits for the
interpreter's lock (against the handler threads the step before woke),
for a lock of the runtime, or in a transfer. A program without the
section (before its PR 37), or a window with no sampled dispatch, gives
None. The chip host's CPU clock ticks in steps of 10 ms, so a window's
few hundred samples can sum to more CPU than wall: that reads 0."""
from benchmark import inside

WALL, CPU = "decode_dispatch_wall_total_s", "decode_dispatch_cpu_total_s"


def read(ctx):
    a, b = ((ctx.get(k) or {}).get("loop") for k in ("stats_open", "stats_close"))
    if not a or not b or WALL not in b or WALL not in a or "engine_open" not in ctx:
        return None
    wall, cpu = b[WALL] - a[WALL], b[CPU] - a[CPU]
    if wall <= 0:
        return None
    dispatch = [ctx[k]["phase_time_s"]["decode"]["dispatch"] for k in ("engine_open", "engine_close")]
    return inside.share_of_window(ctx, max(0.0, 1.0 - cpu / wall) * (dispatch[1] - dispatch[0]))
