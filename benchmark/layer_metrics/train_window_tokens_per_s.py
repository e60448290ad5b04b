"""Trainer step: tokens of ALL the optimizer steps completed inside the
window (closed by ``block_until_ready``) over its seconds, for the whole
cell: pipeline fill, drain and every stall included. What it lacks
against ``train_tokens_per_s`` (the median slice) is time lost to
stalls that hit fewer than half of the slices."""


def read(ctx):
    t = ctx.get("train")
    return t["steps"] * t["global_batch"] * t["seq"] / t["window_s"] if t else None
