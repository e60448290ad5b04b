"""Start-up (``obs/capacity.py``): seconds the host spent TRACING and
LOWERING the jit programs first called before the window opened, summed
over programs (``trace_s + lower_s`` of ``startup`` ``programs``, from
JAX's ``jaxpr_trace_duration`` and ``jaxpr_to_mlir_module_duration``
events at depth one): what a Python frame above a step, an unrolled loop
over layers or a kernel's text costs, paid in every run, warm cache or
not. Part of ``setup_s``. None before the program's PR 50."""
from benchmark import startup


def read(ctx):
    return startup.program_seconds(ctx, ["trace_s", "lower_s"])
