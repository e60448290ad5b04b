"""Kernels (``ops/kernels/ssm_update.py``, the Pallas call
``ssm_state_update``: a state-space layer's decode step over every slot's
stored state, read once and written once in place): its share of its
roofline over the traced part of the window. Least time of the calls made
there (``benchmark/nemotron_model.py::update_call``: the float32 state of
the engine's slots read and written, the step's ``exp(dt A)``, ``dt x``,
``B``, ``C`` rows in and ``y`` out; bound by the bytes) over the kernel's
device seconds, which the driver reads out of the trace under its name
(``ctx["ssm_kernels"]``: the harness reduces with the names it had). A
program without the call is not read."""
from benchmark import kernel_model, nemotron_model


def read(ctx):
    model, kernels = ctx.get("model") or {}, ctx.get("ssm_kernels")
    if not kernels or "ssm_layers" not in model:
        return None
    spent, calls = sum(kernels["kernel_s"].values()), sum(kernels["kernel_calls"].values())
    if spent <= 0 or calls <= 0:
        return None
    ops, nbytes = nemotron_model.update_call(model, ctx["slots"])
    least, _bound = kernel_model.least_seconds(calls * ops, calls * nbytes, ctx["peaks"])
    return 100.0 * least / spent
