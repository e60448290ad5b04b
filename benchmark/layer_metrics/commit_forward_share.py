"""Engine (block diffusion): the share of a slot's forwards that were
commits (a forward over a finished block, whose K/V later blocks read
and whose logits nothing reads), in percent, over the window, from
``/v2/stats`` ``diffusion``: the growth of ``commit_forwards_total``
over that of ``slot_forwards_total``. ``1 / (S + 1)`` under the static
rule: a third at 2 steps. A program without the section is not read."""
from benchmark.layer_metrics.tokens_per_forward import growth


def read(ctx):
    commits, forwards = growth(ctx, "commit_forwards_total"), growth(ctx, "slot_forwards_total")
    if commits is None or not forwards or forwards <= 0:
        return None
    return 100.0 * commits / forwards
