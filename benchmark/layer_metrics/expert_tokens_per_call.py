"""Experts: how full an expert's batch is. Growth over the window of all
experts' ``tokens_total`` / (experts x expert layers x growth of
``decode_calls_total`` + ``prefill_calls_total``): the tokens one expert
of one layer is handed in one call of a step program, decode steps and
prefills together (``/v2/stats`` section ``experts``)."""
from benchmark.layer_metrics import expert_load_imbalance


def read(ctx):
    grew = expert_load_imbalance.growth(ctx)
    if not grew:
        return None
    a, b = ctx["stats_open"]["experts"], ctx["stats_close"]["experts"]
    calls = sum(b[k] - a[k] for k in ("decode_calls_total", "prefill_calls_total"))
    if calls <= 0:
        return None
    return sum(grew) / (len(grew) * len(b["layers"]) * calls)
