"""Engine (``generation/engine.py``): share of the window's seconds the
scheduler thread spent in ``ff.engine.*.dispatch.call`` spans, the call
of the jitted step program alone (the runtime's walk over the argument
pytree and its enqueueing of the program, and every wait for a lock
inside them), as growth of ``<kind>.dispatch.call`` in ``step_phases``.
See ``dispatch_upload_share``."""
from benchmark.layer_metrics import dispatch_upload_share


def read(ctx):
    return dispatch_upload_share.read(ctx, "dispatch.call")
