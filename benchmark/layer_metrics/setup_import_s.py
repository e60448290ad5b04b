"""Start-up (``flexflow_tpu/__init__.py``, ``device.require_tpu``): seconds
from the process's start to the end of ``import flexflow_tpu`` (span
``ff.startup.import``: the interpreter, the benchmark's and JAX's
imports, the package's) plus the first listing of devices (span
``ff.startup.backend``: the TPU runtime's own start, which differs on
one chip and four). Part of ``setup_s``. A program without the account
(before its PR 50) gives None."""
from benchmark import startup


def read(ctx):
    return startup.phase_seconds(ctx, ["import", "backend"])
