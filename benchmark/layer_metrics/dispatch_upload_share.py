"""Engine (``generation/engine.py``): share of the window's seconds the
scheduler thread spent in ``ff.engine.*.dispatch.upload`` spans, the
host-to-device transfers of a dispatch (three fresh vectors a decode
step, a staging miss, a token array that is not carried; a prefill's
tokens, table and scalars), as growth of ``<kind>.dispatch.upload`` in
``step_phases`` of ``/v2/stats``. A child of the dispatch span: a part of
``host_dispatch_share``, with ``dispatch_call_share`` and the ``args``
child (read off ``/v2/stats``; no metric). A program without the key
(before its PR 37) gives None."""
from benchmark import inside

CHILD = "dispatch.upload"


def read(ctx, child=CHILD):
    phases = (ctx.get("stats_close") or {}).get("step_phases") or {}
    if not any(k.split(".", 1)[1] == child for k in phases):
        return None
    return inside.share_of_window(ctx, inside.phase_seconds(ctx, [child]))
