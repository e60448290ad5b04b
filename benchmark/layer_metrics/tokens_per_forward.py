"""Scheduler / engine (block diffusion): tokens fixed a forward of a
slot over the window, from ``/v2/stats`` ``diffusion``: the growth of
``tokens_fixed_total`` over the growth of ``slot_forwards_total`` (a
step is one forward of every live slot; a commit forward fixes none).
``B / (S + 1)`` under the static rule: 1.33 at 2 steps of a block of 4;
a commit fused into the next block's first forward, or a rule that fixes
more rows a forward, would move it. A program without the section is
not read."""


def growth(ctx, key):
    a, b = (ctx.get("stats_open") or {}).get("diffusion"), (ctx.get("stats_close") or {}).get("diffusion")
    if not a or not b or key not in a or key not in b:
        return None
    return b[key] - a[key]


def read(ctx):
    fixed, forwards = growth(ctx, "tokens_fixed_total"), growth(ctx, "slot_forwards_total")
    if fixed is None or not forwards or forwards <= 0:
        return None
    return fixed / forwards
