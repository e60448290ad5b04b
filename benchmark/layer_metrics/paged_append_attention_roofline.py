"""Kernels (``ops/kernels/decode_attention.py``): the paged decode
kernel's share of its roofline over the traced part of the window.

Least time = max(ops / peak FLOP/s, bytes / peak B/s) of the calls made,
from ``benchmark/kernel_model.py``: a decode step attends, for every
token it produces, that token's whole context in each layer, so the
context positions attended are summed from the client's records (every
token event in the traced window but a request's first: prompt length +
index). Over the summed device durations of the kernel's events
(``paged_append_attention`` and ``..._split``) in the trace. Requests
still running when the trace starts or stops are counted by the tokens
that fall inside it, so the edges cost well under a step's worth."""
from benchmark import kernel_model


def read(ctx):
    trace = ctx.get("trace")
    if not trace or "records" not in ctx or not ctx.get("trace_abs"):
        return None
    spent = sum(trace["kernel_s"][k] for k in kernel_model.PAGED_KERNELS)
    if spent <= 0:
        return None
    lo, hi = ctx["trace_abs"]
    context = rows = 0
    for r in ctx["records"]:
        for i, t in enumerate(r["token_times"]):
            if i >= 1 and lo <= t < hi:
                context += r["prompt_len"] + i
                rows += 1
    m = ctx["model"]
    ops, nbytes = kernel_model.paged_attention_call(
        context, rows, m["num_heads"], m["head_dim"], m["cache_itemsize"]
    )
    least, _bound = kernel_model.least_seconds(m["num_layers"] * ops, m["num_layers"] * nbytes, ctx["peaks"])
    return 100.0 * least / spent
