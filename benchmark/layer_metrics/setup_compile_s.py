"""Start-up (``obs/capacity.py``): seconds of backend compilation
(``backend_compile_duration`` sections in which the persistent cache
did NOT hold the program, those inside another program's trace
included) before the window opened: 0 in a warm run, and then
``setup_cache_misses`` is 0 too. Part of ``setup_s``. None before the
program's PR 50."""
from benchmark import startup


def read(ctx):
    return startup.program_seconds(ctx, ["compile_s"])
