"""Cache (``generation/cache.py``, two pools): what releasing the window
layers' blocks behind the window saves, ``1 - live_bytes /
one_table_bytes`` of the ``cache`` section of ``/v2/stats`` — the bytes
the sequences of the last decode step held in both pools over the bytes
ONE table for all attention layers would hold for the same sequences —
sampled once a second through the window, the mean."""


def read(ctx):
    shares = [
        1.0 - c["live_bytes"] / c["one_table_bytes"]
        for s in ctx.get("stats_samples", []) if (c := s.get("cache")) and c.get("one_table_bytes")
    ]
    if not shares:
        return None
    return 100.0 * sum(shares) / len(shares)
