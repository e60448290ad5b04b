"""Device: 1 - (union of the intervals in which an instruction runs on
the chip) / traced window, on the chip that idles most. From the device
trace alone, never from host spans."""


def read(ctx):
    trace = ctx.get("trace")
    return None if not trace or trace["idle_share"] is None else 100.0 * trace["idle_share"]
