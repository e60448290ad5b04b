"""Collectives (GSPMD / ``shard_map``): the share of the traced window in
which a collective instruction runs on a chip and no other instruction
does, on the chip where that is largest. From the device trace."""


def read(ctx):
    trace = ctx.get("trace")
    if not trace or len(trace["devices"]) < 2:
        return None
    return 100.0 * max(d["collective_exposed_s"] for d in trace["devices"]) / trace["window_s"]
