"""Closed loop: callers that each wait for a reply. ``clients`` clients
each send their next request when their last completes, so a slower
server is offered less. The list is drawn whole from the seed, long
enough for the fastest server the cell allows for."""
from __future__ import annotations

from typing import Dict

import numpy as np

from . import lengths


def schedule(seed: int, seconds: float, params: Dict, sizes: Dict) -> Dict:
    """``params``: ``clients``; ``prompt`` and ``output`` (see
    ``lengths.requests``); ``max_rate_per_s``, an upper
    bound on completions per second that sizes the list (a run that
    exhausts it fails: raise the bound)."""
    rs = np.random.RandomState(seed)
    clients = int(params["clients"])
    n = clients + int(np.ceil(float(params["max_rate_per_s"]) * seconds))
    reqs = lengths.requests(rs, n, params, sizes["vocab_size"])
    return {"mode": "closed", "clients": clients, "requests": reqs}
