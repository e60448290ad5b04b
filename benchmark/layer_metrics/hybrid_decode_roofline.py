"""Kernels / device: the decode PROGRAM's share of its roofline over the
traced part of the window, for a configuration whose layers are state-space
mixers, attention and experts in a latent, one branch a layer
(Nemotron-3-Super), as ``shortcut_decode_roofline`` is for LongCat and
``block_step_roofline`` for SDAR. Least time of the decode steps made
there — ``benchmark/nemotron_model.py::decode_step``: every weight a step
reads (of the held experts those some row picked), the live rows' float32
state read and written in every state-space layer, the attention layer's
K/V, the head, and the rows' operations, through
``kernel_model.least_seconds`` — over the device seconds the trace gives
``jit__decode_impl``. The steps made there are counted from the trace
itself (the update kernel's calls over the state-space layers); the live
rows a step had from the client's records over the window; contexts from
the records (every token event in the traced part but a request's first)."""
from benchmark import kernel_model, nemotron_model
from benchmark.layer_metrics.paged_window_attention_roofline import traced_contexts


def read(ctx):
    trace, model, kernels = ctx.get("trace"), ctx.get("model") or {}, ctx.get("ssm_kernels")
    if not trace or not kernels or "ssm_layers" not in model or not ctx.get("trace_abs") or "records" not in ctx:
        return None
    spent = sum(s for name, s in trace["programs"].items() if "decode_impl" in name)
    steps = sum(kernels["kernel_calls"].values()) / model["ssm_layers"]
    contexts = traced_contexts(ctx)
    if spent <= 0 or steps <= 0 or not contexts:
        return None
    rows = min(len(contexts) / steps, float(ctx["slots"]))  # live rows a step had
    ops, nbytes = nemotron_model.decode_step(model, rows, sum(contexts) / steps)
    least, _bound = kernel_model.least_seconds(steps * ops, steps * nbytes, ctx["peaks"])
    return 100.0 * least / spent
