"""End to end: seconds from process start to the window's opening:
imports, weights, compile or cache load, warm-up, lead-in. Host clock,
taken by the driver."""


def read(ctx):
    return ctx.get("setup_s")
