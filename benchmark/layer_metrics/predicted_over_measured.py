"""Search: the cost model's step time for the strategy it chose
(``_search_result.best_cost``) over the measured median step."""
from benchmark import stats


def read(ctx):
    t = ctx.get("train")
    if not t or not t["predicted_step_s"] or not t["step_ms"]:
        return None
    return t["predicted_step_s"] * 1e3 / stats.median(t["step_ms"])
