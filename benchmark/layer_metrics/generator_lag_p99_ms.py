"""Service, seen from the load generator: how late the generator sent a
request against its schedule (99th percentile of sent - due over the
requests due inside the window), so that a starved generator is not
read as a fast server."""
from benchmark import stats


def read(ctx):
    if "records" not in ctx:
        return None
    lag = [(r["sent"] - r["due"]) * 1e3
           for r in stats.due_in_window(ctx["records"], *ctx["window"]) if r.get("sent") is not None]
    return stats.percentile(lag, 99) if lag else None
