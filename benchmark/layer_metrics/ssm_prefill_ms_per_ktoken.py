"""Engine (``generation/engine.py``): what a thousand prompt tokens cost to
prefill where the layers are state-space mixers, attention and experts in
a latent (``prefill_ms_per_ktoken``'s reading: the growth of
``engine.phase_time_s["prefill"]``, the hand-over of the slot's state
included, over the growth of ``prefill_attention.tokens_total``), under a
name of its own, as ``latent_prefill_ms_per_ktoken`` is: that metric's
list of cells is held by an accepted test. The prefill's scan is XLA (the
SSD einsums over chunks of 128): there is no prefill kernel whose roofline
could be read, and this is the state-space prefill's reader. Read only
where ``/v2/stats`` has a ``cache.ssm`` section."""
from benchmark.layer_metrics import prefill_ms_per_ktoken


def read(ctx):
    if "ssm" not in ((ctx.get("stats_close") or {}).get("cache") or {}):
        return None
    return prefill_ms_per_ktoken.read(ctx)
