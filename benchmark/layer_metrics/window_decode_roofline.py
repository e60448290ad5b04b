"""Kernels / device: the decode PROGRAM's share of its roofline over the
traced part of the window, for a configuration with window layers, as
``moe_decode_roofline`` is for LFM2. Least time of the decode steps made
there — ``benchmark/mellum2_model.py::window_decode_step``: every weight
a step reads (of the experts, those that some token was routed to,
expected from the live rows a step had: ``N (1 - (1 - k/N)^rows)``), K/V
by kind of layer (the full layers the whole context, the window layers
``min(context, window + block)``), and the routed operations, through
``kernel_model.least_seconds`` — over the device seconds the trace gives
``jit__decode_impl``."""
from benchmark import kernel_model, mellum2_model
from benchmark.layer_metrics.paged_window_attention_roofline import traced_contexts


def read(ctx):
    trace = ctx.get("trace")
    model = ctx.get("model") or {}
    if not trace or "window_layers" not in model or not ctx.get("trace_abs") or "records" not in ctx:
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
    n, k = model["num_experts"], model["experts_per_token"]
    touched = n * (1.0 - (1.0 - k / n) ** per_step)
    ops, nbytes = mellum2_model.window_decode_step(
        model, per_step, sum(contexts) / steps, mellum2_model.window_positions(contexts, model) / steps, touched
    )
    least, _bound = kernel_model.least_seconds(steps * ops, steps * nbytes, ctx["peaks"])
    return 100.0 * least / spent
