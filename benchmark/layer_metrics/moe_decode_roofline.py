"""Kernels / device: the decode PROGRAM's share of its roofline over the
traced part of the window. Least time of the decode steps made there —
``benchmark/moe_model.py::moe_decode_step``: every weight a step reads
(of the experts, those that some token was routed to, expected from the
live rows a step had: ``N (1 - (1 - k/N)^rows)``), K/V of the attended
positions, the convolution state, and the routed operations, through
``kernel_model.least_seconds`` — over the device seconds the trace gives
``jit__decode_impl``. Rows and contexts are summed from the client's
records (every token event in the traced part but a request's first:
prompt length + index), steps from the trace's own count of the program
where it has one, else from the rows and the window's mean occupancy."""
from benchmark import kernel_model, moe_model
from benchmark.layer_metrics.paged_gqa_attention_roofline import traced_rows


def read(ctx):
    trace = ctx.get("trace")
    model = ctx.get("model") or {}
    if not trace or "expert_layers" not in model or not ctx.get("trace_abs") or "records" not in ctx:
        return None
    spent = sum(s for name, s in trace["programs"].items() if "decode_impl" in name)
    if spent <= 0:
        return None
    context, rows = traced_rows(ctx)
    if rows <= 0:
        return None
    a, b = ctx["engine_open"], ctx["engine_close"]
    steps_window = b["step_counts"]["decode"] - a["step_counts"]["decode"]
    w_lo, w_hi = ctx["window"]
    rows_window = sum(w_lo <= t < w_hi for r in ctx["records"] for t in r["token_times"][1:])
    if steps_window <= 0 or rows_window <= 0:
        return None
    per_step = rows_window / steps_window  # live rows a decode step had, over the window
    steps = rows / per_step
    n, k = model["num_experts"], model["experts_per_token"]
    touched = n * (1.0 - (1.0 - k / n) ** per_step)
    ops, nbytes = moe_model.moe_decode_step(model, per_step, context / steps, touched)
    least, _bound = kernel_model.least_seconds(steps * ops, steps * nbytes, ctx["peaks"])
    return 100.0 * least / spent
