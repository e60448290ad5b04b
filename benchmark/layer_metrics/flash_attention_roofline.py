"""Kernels (``ops/kernels/flash_attention.py``): the three flash kernels'
share of their roofline over the traced part of the window: least time
of the calls the trace shows (``benchmark/kernel_model.py``, each call
on the per-chip [batch, seq, heads, head] operands) over their summed
device durations."""
from benchmark import kernel_model


def read(ctx):
    trace, t = ctx.get("trace"), ctx.get("train")
    if not trace or not t:
        return None
    spent = least = 0.0
    for k in kernel_model.FLASH_KERNELS:
        calls = trace["kernel_calls"][k]
        if not calls:
            continue
        # calls are summed over devices; each chip's call sees its share
        # of the batch x heads (data or tensor parallel alike)
        ops, nbytes = kernel_model.flash_attention_call(
            k, t["global_batch"], t["seq"], t["num_heads"], t["hidden_size"] // t["num_heads"]
        )
        one, _bound = kernel_model.least_seconds(ops / t["chips"], nbytes / t["chips"], ctx["peaks"])
        least += calls * one
        spent += trace["kernel_s"][k]
    return None if spent <= 0 else 100.0 * least / spent
