"""Front end (``serving/server.py``): what HTTP, the handler thread and
the SSE flush add to a first token. Median over the window's requests of
(first SSE token event at the client - request sent), minus the server's
own ``ttft`` p50 (submit to first token, ``/v2/stats``; a rolling window
of the last 512 requests, so it also holds the lead-in's). Source:
host clock at the client and a program counter."""
from benchmark import stats


def read(ctx):
    due = stats.window_ok(ctx)
    if not due or "ttft" not in ctx["stats_close"]:
        return None
    client = stats.median([(r["token_times"][0] - r["sent"]) * 1e3 for r in due])
    return client - ctx["stats_close"]["ttft"]["p50_s"] * 1e3
