"""Kernels (``ops/kernels/ssm_update.py``, the Pallas call
``selective_state_update``: a Mamba-1 layer's decode step over every slot's
stored state ``[N, D]`` float32, read once and written once in place, the
decay formed inside): its share of its roofline over the traced part of the
window. Least time of the calls made there
(``benchmark/phi4flash_model.py::update_call``: the float32 state of the
engine's slots read and written, the step's ``dt``, ``x``, ``B``, ``C`` in and
``y`` out, the rates once; bound by the bytes) over the kernel's device
seconds, which the driver reads out of the trace under its name
(``ctx["ssm_kernels"]``: the harness reduces with the names it had). A program
without the call is not read."""
from benchmark import kernel_model, phi4flash_model


def read(ctx):
    model, kernels = ctx.get("model") or {}, ctx.get("ssm_kernels")
    if not kernels or "mamba_layers" not in model:
        return None
    spent = kernels["kernel_s"].get("selective_state_update", 0.0)
    calls = kernels["kernel_calls"].get("selective_state_update", 0)
    if spent <= 0 or calls <= 0:
        return None
    ops, nbytes = phi4flash_model.update_call(model, ctx["slots"])
    least, _bound = kernel_model.least_seconds(calls * ops, calls * nbytes, ctx["peaks"])
    return 100.0 * least / spent
