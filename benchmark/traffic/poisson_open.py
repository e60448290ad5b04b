"""Open loop: independent users. Seeded arrivals of a Poisson process at
a fixed rate; every request is due at a time fixed before the run,
whatever the server does."""
from __future__ import annotations

from typing import Dict

import numpy as np

from . import lengths


def schedule(seed: int, seconds: float, params: Dict, sizes: Dict) -> Dict:
    """``params``: ``rate_per_s``; ``prompt``, ``output`` and
    ``stratified`` (see ``lengths.requests``). Exactly
    ``round(rate_per_s * seconds)`` arrivals at seeded uniform times:
    a Poisson process given its count, so that every seed offers the
    same load (a plain Poisson count moves by 1 / sqrt(count) from seed
    to seed, 13 % at 60 requests, and the tails with it)."""
    rs = np.random.RandomState(seed)
    n = int(round(float(params["rate_per_s"]) * seconds))
    due = np.sort(rs.uniform(0.0, seconds, size=n))
    reqs = lengths.requests(rs, n, params, sizes["vocab_size"])
    for r, d in zip(reqs, due):
        r["due_s"] = float(d)
    return {"mode": "open", "requests": reqs}
