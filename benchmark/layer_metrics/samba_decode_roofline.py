"""Kernels / device: the decode PROGRAM's share of its roofline over the
traced part of the window, for a decoder-hybrid-decoder (Mamba-1, window and
full differential attention, Gated Memory Units and cross layers:
Phi-4-mini-flash-reasoning), as ``hybrid_decode_roofline`` is for Nemotron.
Least time of the decode steps made there
(``benchmark/phi4flash_model.py::decode_step``: every weight once, the live
rows' float32 state read and written in every Mamba layer, the ONE full K/V
read once a layer that attends it, the window layers' K/V, the tied head, and
the rows' operations, through ``kernel_model.least_seconds``) over the device
seconds the trace gives ``jit__decode_impl``. The steps are counted from the
trace itself (the update kernel's calls over the Mamba layers); the live rows
a step had and their contexts from the client's records (every token event in
the traced part but a request's first)."""
from benchmark import kernel_model, phi4flash_model
from benchmark.layer_metrics.paged_window_attention_roofline import traced_contexts


def read(ctx):
    trace, model, kernels = ctx.get("trace"), ctx.get("model") or {}, ctx.get("ssm_kernels")
    if not trace or not kernels or "mamba_layers" not in model or not ctx.get("trace_abs") or "records" not in ctx:
        return None
    spent = sum(s for name, s in trace["programs"].items() if "decode_impl" in name)
    steps = kernels["kernel_calls"].get("selective_state_update", 0) / model["mamba_layers"]
    contexts = traced_contexts(ctx)
    if spent <= 0 or steps <= 0 or not contexts:
        return None
    rows = min(len(contexts) / steps, float(ctx["slots"]))  # live rows a step had
    ops, nbytes = phi4flash_model.decode_step(
        model, rows, sum(contexts) / steps, phi4flash_model.window_positions(contexts, model) / steps
    )
    least, _bound = kernel_model.least_seconds(steps * ops, steps * nbytes, ctx["peaks"])
    return 100.0 * least / spent
