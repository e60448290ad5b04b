"""Kernels (``ops/kernels/decode_attention.py``, the latent call): the
latent kernel's share of its roofline over the traced part of the
window. Least time of the latent layers' calls made there — one call a
latent layer a step, each reading a live row's whole context ONCE at the
published row width (``benchmark/joyai_model.py``: 1,152 B a position;
operations over the 32 query heads, score and value) — over the summed
device time of the ``paged_latent_attention`` kernel's events, which the
driver reads out of the trace under that name (``ctx["latent_kernels"]``).
Rows and contexts from the client's records: every token event in the
traced part but a request's first."""
from benchmark import joyai_model, kernel_model
from benchmark.layer_metrics.paged_window_attention_roofline import traced_contexts


def read(ctx):
    model, kernels = ctx.get("model") or {}, ctx.get("latent_kernels")
    if not kernels or "latent_layers" not in model or "records" not in ctx or not ctx.get("trace_abs"):
        return None
    spent = sum(kernels["kernel_s"].values())
    if spent <= 0:
        return None
    contexts = traced_contexts(ctx)
    ops, nbytes = joyai_model.paged_latent_attention_call(sum(contexts), len(contexts), model)
    layers = model["latent_layers"]
    least, _bound = kernel_model.least_seconds(layers * ops, layers * nbytes, ctx["peaks"])
    return 100.0 * least / spent
