"""Cache (``generation/cache.py``): the fullest the block pool got,
``cache_blocks_used / cache_blocks_total`` of ``/v2/stats`` sampled once
a second through the window."""


def read(ctx):
    samples = [s for s in ctx.get("stats_samples", []) if s.get("cache_blocks_total")]
    if not samples:
        return None
    return 100.0 * max(s["cache_blocks_used"] / s["cache_blocks_total"] for s in samples)
