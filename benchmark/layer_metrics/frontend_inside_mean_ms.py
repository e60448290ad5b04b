"""Front end (``serving/server.py``), timed from inside: the handler's
two spans per streamed request, ``ff.http.ingress`` (body read ->
``submit`` returned) and ``ff.http.first_write`` (first token taken off
the handle -> its SSE event flushed), as growth of the ``http_ingress``
and ``http_first_write`` sums of ``/v2/stats`` over the requests that
came in inside the window. Socket accept, header parsing and the wake-up
of the handler thread are outside both spans: the outside-timed twin,
``frontend_overhead_p50_ms``, holds them too."""
from benchmark import inside


def read(ctx):
    return inside.mean_ms(ctx, ["http_ingress", "http_first_write"])
