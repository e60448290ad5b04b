"""Start-up (``obs/steptrace.py``): share of ``setup_s`` that the
program's account names: the union (no second counted twice) of its
top-level ``ff.startup.*`` spans and of the jit programs' first calls
before the window opened (``spanned_s``), over ``setup_s``. The rest is
the driver's own: drawing the weights' inputs, the warm-up traffic
between compiles, ageing the cache, starting the load generator, the
lead-in. None before the program's PR 50."""
from benchmark import startup


def read(ctx):
    acct = startup.account(ctx)
    if acct is None or not ctx.get("setup_s"):
        return None
    return 100.0 * acct["spanned_s"] / ctx["setup_s"]
