"""Kernels / device: the block-step PROGRAM's share of its roofline over
the traced part of the window. Least time of the block forwards made
there — ``benchmark/sdar_model.py::block_forward``: every weight a
forward reads (of the experts, those that some row was routed to,
expected from the rows a forward had), every layer's K/V (a slot's
context read once for its ``B`` rows, the block's rows written), and the
rows' operations, through ``kernel_model.least_seconds`` — over the
device seconds the trace gives the program (``jit__block_impl``). The
forwards made there are counted from the trace itself (the paged
kernel's calls over the layers); the live slots a forward had from
``/v2/stats`` ``diffusion`` over the window; a slot's context from the
client's records (every token event in the traced part: prompt length +
index, to the end of its block)."""
from benchmark import kernel_model, sdar_model
from benchmark.layer_metrics.tokens_per_forward import growth


def traced_forwards(ctx):
    """(block forwards in the traced part, live slots a forward, mean
    context of a slot), or None where something is missing."""
    trace, model = ctx.get("trace"), ctx.get("model") or {}
    if not trace or "block_length" not in model or not ctx.get("trace_abs") or "records" not in ctx:
        return None
    calls = sum(trace["kernel_calls"].get(k, 0) for k in kernel_model.PAGED_KERNELS)
    slot_forwards = growth(ctx, "slot_forwards_total")
    a, b = ctx["engine_open"]["step_counts"], ctx["engine_close"]["step_counts"]
    steps = b.get("block_step", 0) - a.get("block_step", 0)
    if calls <= 0 or not slot_forwards or steps <= 0:
        return None
    lo, hi = ctx["trace_abs"]
    block = model["block_length"]
    contexts = [
        (r["prompt_len"] + i) // block * block + block
        for r in ctx["records"] for i, t in enumerate(r.get("token_times") or []) if lo <= t < hi
    ]
    if not contexts:
        return None
    return calls / model["num_layers"], slot_forwards / steps, sum(contexts) / len(contexts)


def read(ctx):
    found = traced_forwards(ctx)
    if found is None:
        return None
    forwards, slots, context = found
    spent = sum(s for name, s in ctx["trace"]["programs"].items() if "block_impl" in name)
    if spent <= 0:
        return None
    ops, nbytes = sdar_model.block_forward(ctx["model"], slots, slots * context)
    least, _bound = kernel_model.least_seconds(forwards * ops, forwards * nbytes, ctx["peaks"])
    return 100.0 * least / spent
