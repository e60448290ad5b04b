"""Device: the share, in percent, of the decode program's traced device
seconds that lie under the scopes ``attention.full`` and ``attention.cross``
(the K/V layer and the cross layers that read its ONE K/V again: their
projections, the paged calls, lambda and the pair norm, ``W_o``), from the
driver's reduction of the trace by the compiled decode program's own scope
names (``ctx["samba_scopes"]``: ``scope_s`` over ``decode_s``). Whether the
mechanism the cell exists for is most of a step, read off the chip. A program
without those scopes is not read."""
from benchmark.layer_metrics.shared_kv_attention_roofline import SCOPES


def read(ctx):
    scopes = ctx.get("samba_scopes")
    if not scopes or not scopes.get("decode_s") or not any(s in scopes["scope_s"] for s in SCOPES):
        return None
    return 100.0 * sum(scopes["scope_s"].get(s, 0.0) for s in SCOPES) / scopes["decode_s"]
