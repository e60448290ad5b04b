"""Engine (``generation/engine.py``): what a thousand prompt tokens cost to
prefill where the layers are latent and the prefill's attention streams
(``prefill_ms_per_ktoken``'s reading: the growth of
``engine.phase_time_s["prefill"]`` over the growth of
``prefill_attention.tokens_total``, which counts a latent configuration's
whole-prompt prefills from PR 41 on), under a name of its own: that
metric's list of cells is held to one cell by an accepted test
(tests/benchmark_yardstick/test_command_a_cell.py). With the XLA
composition as the streamed form there is no prefill kernel whose roofline
could be read: this is the latent prefill's reader. A program that does
not count latent prefills is not read."""
from benchmark.layer_metrics import prefill_ms_per_ktoken


def read(ctx):
    return prefill_ms_per_ktoken.read(ctx)
