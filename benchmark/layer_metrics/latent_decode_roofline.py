"""Kernels / device: the decode PROGRAM's share of its roofline over the
traced part of the window, for a configuration with latent layers, as
``moe_decode_roofline`` is for LFM2 and ``window_decode_roofline`` for
Mellum2. Least time of the decode steps made there —
``benchmark/joyai_model.py::latent_decode_step``: every held weight a
step reads (of the held experts, those that some token was routed to,
expected from the live rows a step had), the latent rows of the attended
positions, and the operations of the absorbed form and the experts a row
goes through here, through ``kernel_model.least_seconds`` — over the
device seconds the trace gives ``jit__decode_impl``."""
from benchmark import joyai_model, kernel_model
from benchmark.layer_metrics.paged_window_attention_roofline import traced_contexts


def read(ctx):
    trace = ctx.get("trace")
    model = ctx.get("model") or {}
    if not trace or "latent_layers" not in model or not ctx.get("trace_abs") or "records" not in ctx:
        return None
    spent = sum(s for name, s in trace["programs"].items() if "decode_impl" in name)
    contexts = traced_contexts(ctx)
    if spent <= 0 or not contexts:
        return None
    a, b = ctx["engine_open"], ctx["engine_close"]
    steps_window = b["step_counts"]["decode"] - a["step_counts"]["decode"]
    w_lo, w_hi = ctx["window"]
    rows_window = sum(w_lo <= t < w_hi for r in ctx["records"] for t in r["token_times"][1:])
    if steps_window <= 0 or rows_window <= 0:
        return None
    per_step = rows_window / steps_window  # live rows a decode step had, over the window
    steps = len(contexts) / per_step
    ops, nbytes = joyai_model.latent_decode_step(
        model, per_step, sum(contexts) / steps, joyai_model.experts_touched(model, per_step)
    )
    least, _bound = kernel_model.least_seconds(steps * ops, steps * nbytes, ctx["peaks"])
    return 100.0 * least / spent
