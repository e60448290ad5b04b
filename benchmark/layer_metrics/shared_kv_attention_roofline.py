"""Kernels (``ops/kernels/decode_attention.py``, the grouped paged call as the
K/V layer and the cross layers of a decoder-hybrid-decoder make it): the share
of their roofline that the calls reading the ONE shared K/V reach over the
traced part of the window. Least time of those calls
(``benchmark/phi4flash_model.py::shared_kv_call``: one a decode step for the
K/V layer and one for every cross layer, each reading every live row's whole
context at the PUBLISHED width, 20 K/V heads of 64, and differential
attention's operations) over the device seconds of the paged kernel's events
that lie under the scopes ``attention.full`` and ``attention.cross`` of the
decode program (``ctx["samba_scopes"]["kernel_s"]``, the driver's reduction
of the trace by the compiled program's own scope names). The steps are
counted from the trace (``steps``); rows and contexts from the client's
records (every token event in the traced part but a request's first). A
program without those scopes is not read."""
from benchmark import kernel_model, phi4flash_model
from benchmark.layer_metrics.paged_window_attention_roofline import traced_contexts

SCOPES = ("attention.full", "attention.cross")


def read(ctx):
    model, scopes = ctx.get("model") or {}, ctx.get("samba_scopes")
    if not scopes or "cross_layers" not in model or "records" not in ctx or not ctx.get("trace_abs"):
        return None
    spent = sum(scopes["kernel_s"].get(s, 0.0) for s in SCOPES)
    contexts = traced_contexts(ctx)
    if spent <= 0 or not contexts:
        return None
    ops, nbytes = phi4flash_model.shared_kv_call(model, len(contexts), sum(contexts))
    calls = 1 + model["cross_layers"]
    least, _bound = kernel_model.least_seconds(calls * ops, calls * nbytes, ctx["peaks"])
    return 100.0 * least / spent
