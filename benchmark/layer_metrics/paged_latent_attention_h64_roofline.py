"""Kernels (``ops/kernels/decode_attention.py``, the latent call at 64
heads a row): ``paged_latent_attention_roofline``'s reading (the least
time of the latent sub-layers' calls over the traced part of the window —
one a sub-layer a step, each reading a live row's whole context ONCE at
the published row width, operations over the 64 query heads,
``benchmark/joyai_model.py::paged_latent_attention_call`` — over the
summed device time of the ``paged_latent_attention`` kernel's events),
under a name of its own: that metric's list of cells is held to one cell
by an accepted test (tests/benchmark_yardstick/test_joyai_cell.py), so a
second latent cell cannot be appended to it. The arithmetic is that
reader's, imported and not copied."""
from benchmark.layer_metrics import paged_latent_attention_roofline


def read(ctx):
    return paged_latent_attention_roofline.read(ctx)
