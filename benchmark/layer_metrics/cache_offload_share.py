"""Cache (``generation/prefix.py``, the engine's swap-in): share of the
window's seconds spent moving blocks between the device and the host
tier, the ``ff.cache.offload`` spans (eviction: victim selection, block
reads, CRCs) and the ``ff.cache.restore`` spans (swap-in), as growth of
the ``cache_offload`` and ``cache_restore`` sums of ``/v2/stats``. Both
run on the scheduler thread inside an admission, so this is a part of
``host_sched_share``. A direction the run never took is absent: zero."""
from benchmark import inside


def read(ctx):
    deltas = [inside.window_delta(ctx, n) for n in ("cache_offload", "cache_restore")]
    if all(d is None for d in deltas):
        return None
    return inside.share_of_window(ctx, sum(d[1] for d in deltas if d is not None))
