"""Start-up (``obs/capacity.py``): seconds spent fetching programs from
the persistent compile cache before the window opened: the whole
backend section of every compile request the cache answered (the key's
hashing, the read, deserialisation and load; JAX's
``cache_retrieval_time_sec`` is its inner part). Part of ``setup_s``.
None before the program's PR 50."""
from benchmark import startup


def read(ctx):
    return startup.program_seconds(ctx, ["cache_load_s"])
