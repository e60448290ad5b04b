"""Kernels / device: the decode PROGRAM's share of its roofline over the
traced part of the window, for a configuration whose routed experts sit on
a shortcut beside dense feed-forwards (LongCat-Flash), as
``latent_decode_roofline`` is for JoyAI and ``parallel_decode_roofline``
for Command A+. Least time of the decode steps made there —
``benchmark/longcat_model.py::shortcut_decode_step``: every sub-layer's
attention and dense weights, of each routed branch's held experts those
some token picked (from the live rows a step had and the held experts'
share of the picks, which the window's counters give: the growth of
``experts.tokens_total`` over held experts x tokens routed), the latent
rows of the attended positions, the head, and the operations of the
absorbed form, the dense SwiGLUs and the experts a row picks here,
through ``kernel_model.least_seconds`` — over the device seconds the
trace gives ``jit__decode_impl``."""
from benchmark import kernel_model, longcat_model
from benchmark.layer_metrics.paged_window_attention_roofline import traced_contexts
from benchmark.layer_metrics.zero_expert_pick_share import grown


def read(ctx):
    trace = ctx.get("trace")
    model = ctx.get("model") or {}
    if not trace or "routed_branches" not in model or not ctx.get("trace_abs") or "records" not in ctx:
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
    held, routed = grown(ctx, "tokens_total"), grown(ctx, "real_experts_per_token_total")
    picked = None
    if held is not None and routed is not None and sum(routed[0]) > sum(routed[1]):
        picked = (sum(held[0]) - sum(held[1])) / (model["experts_held"] * (sum(routed[0]) - sum(routed[1])))
    ops, nbytes = longcat_model.shortcut_decode_step(model, per_step, sum(contexts) / steps, picked)
    least, _bound = kernel_model.least_seconds(steps * ops, steps * nbytes, ctx["peaks"])
    return 100.0 * least / spent
