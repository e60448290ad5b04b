"""Kernels (``ops/kernels/decode_attention.py``, the windowed call): the
window kernel's share of its roofline over the traced part of the
window. Least time of the window layers' calls made there — one call a
window layer a step, each reading ``min(context, window + block)``
positions a live row (``benchmark/mellum2_model.py``: bytes over the K/V
heads at the cache's width, operations over the query heads) — over the
summed device time of the ``paged_window_attention`` kernel's events,
which the driver reads out of the trace under that name
(``ctx["window_kernels"]``). Rows and contexts from the client's
records: every token event in the traced part but a request's first."""
from benchmark import kernel_model, mellum2_model


def traced_contexts(ctx):
    """The context (prompt length + index) of every decode row in the
    traced part of the window."""
    lo, hi = ctx["trace_abs"]
    return [
        r["prompt_len"] + i for r in ctx["records"] for i, t in enumerate(r["token_times"]) if i >= 1 and lo <= t < hi
    ]


def read(ctx):
    model, kernels = ctx.get("model") or {}, ctx.get("window_kernels")
    if not kernels or "window_layers" not in model or "records" not in ctx or not ctx.get("trace_abs"):
        return None
    spent = sum(kernels["kernel_s"].values())
    if spent <= 0:
        return None
    contexts = traced_contexts(ctx)
    ops, nbytes = mellum2_model.paged_window_attention_call(
        mellum2_model.window_positions(contexts, model), len(contexts), model
    )
    layers = model["window_layers"]
    least, _bound = kernel_model.least_seconds(layers * ops, layers * nbytes, ctx["peaks"])
    return 100.0 * least / spent
