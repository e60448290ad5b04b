"""Start-up (``obs/capacity.py``): compile requests that asked the
persistent cache and were not answered from it before the window opened
(``requests - hits`` of ``startup`` ``cache``, JAX's
``compile_requests_use_cache`` and ``cache_hits`` events; ``misses``
there counts the entries JAX then wrote, ``missed`` names them). Zero
in a warm run unless something evicted or re-keyed a program: the
"directory looks size-capped" question. None before the program's PR 50."""
from benchmark import startup


def read(ctx):
    acct = startup.account(ctx)
    return None if acct is None else acct["cache"]["requests"] - acct["cache"]["hits"]
