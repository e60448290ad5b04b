"""Kernels (``ops/kernels/decode_attention.py``, the grouped paged call
at a block's rows: 4 window queries x group 8 x 4 K/V heads = 128 query
rows a sequence): its share of its roofline over the traced part of the
window. Least time of the calls made there
(``benchmark/sdar_model.py::paged_block_attention_call``: a slot's live
K/V read once for all its rows, over the K/V heads; operations over the
rows' query heads) over the kernel's device seconds, found by its name
in the trace. Calls, live slots and contexts as
``block_step_roofline`` finds them."""
from benchmark import kernel_model, sdar_model
from benchmark.layer_metrics.block_step_roofline import traced_forwards


def read(ctx):
    found = traced_forwards(ctx)
    if found is None:
        return None
    forwards, slots, context = found
    spent = sum(ctx["trace"]["kernel_s"].get(k, 0.0) for k in kernel_model.PAGED_KERNELS)
    if spent <= 0:
        return None
    ops, nbytes = sdar_model.paged_block_attention_call(slots * context, slots, ctx["model"])
    layers = ctx["model"]["num_layers"]
    least, _bound = kernel_model.least_seconds(forwards * layers * ops, forwards * layers * nbytes, ctx["peaks"])
    return 100.0 * least / spent
