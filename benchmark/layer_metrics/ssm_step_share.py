"""Device: the share, in percent, of the traced device seconds that lie
inside the state-space layers' update calls (the Pallas call
``ssm_state_update``, five a decode step: ``ctx["ssm_kernels"]``) — over
the device's busy seconds in the traced part of the window. Whether the
mechanism the cell exists for is most of the device's work, read off the
chip: the state's bytes grow with the slots, every other part of a step
with the weights. The layers' projections, convolution and gate are XLA
fusions the trace does not name by layer and are NOT in this share. A
program without the call is not read."""


def read(ctx):
    trace, kernels = ctx.get("trace"), ctx.get("ssm_kernels")
    if not trace or not kernels or not trace.get("busy_s"):
        return None
    spent = sum(kernels["kernel_s"].values())
    if spent <= 0:
        return None
    return 100.0 * spent / trace["busy_s"]
