"""Device: the share, in percent, of the decode program's traced device
seconds that lie under the scope ``gmu`` (the Gated Memory Units: their norm,
``W_1``, the gate over the memory layer's scan output and ``W_2``), from the
driver's reduction of the trace by the compiled decode program's own scope
names (``ctx["samba_scopes"]``: ``scope_s["gmu"]`` over ``decode_s``). A
program without the scope is not read."""


def read(ctx):
    scopes = ctx.get("samba_scopes")
    if not scopes or not scopes.get("decode_s") or "gmu" not in scopes["scope_s"]:
        return None
    return 100.0 * scopes["scope_s"]["gmu"] / scopes["decode_s"]
