"""Kernels (``ops/kernels/decode_attention.py``, grouped queries): the
paged decode kernel's share of its roofline over the traced part of the
window, as ``paged_append_attention_roofline`` with operations over the
query heads and bytes over the K/V heads at the cache's width
(``benchmark/moe_model.py::paged_gqa_attention_call``), one call per
ATTENTION layer a step. The kernel keeps its names."""
from benchmark import kernel_model, moe_model


def traced_rows(ctx):
    """(context positions attended, rows) of the decode steps in the
    traced part of the window, summed from the client's records: every
    token event there but a request's first (prompt length + index)."""
    lo, hi = ctx["trace_abs"]
    context = rows = 0
    for r in ctx["records"]:
        for i, t in enumerate(r["token_times"]):
            if i >= 1 and lo <= t < hi:
                context += r["prompt_len"] + i
                rows += 1
    return context, rows


def read(ctx):
    trace = ctx.get("trace")
    model = ctx.get("model") or {}
    if not trace or "kv_heads" not in model or "records" not in ctx or not ctx.get("trace_abs"):
        return None
    spent = sum(trace["kernel_s"][k] for k in kernel_model.PAGED_KERNELS)
    if spent <= 0:
        return None
    context, rows = traced_rows(ctx)
    ops, nbytes = moe_model.paged_gqa_attention_call(
        context, rows, model["num_heads"], model["kv_heads"], model["head_dim"], model["cache_itemsize"]
    )
    layers = model["attention_layers"]
    least, _bound = kernel_model.least_seconds(layers * ops, layers * nbytes, ctx["peaks"])
    return 100.0 * least / spent
