"""Open loop: independent users. Seeded arrivals of a Poisson process at
a fixed rate, given its count in every block of seconds; every request
is due at a time fixed before the run, whatever the server does."""
from __future__ import annotations

from typing import Dict

import numpy as np

from . import lengths


def schedule(seed: int, seconds: float, params: Dict, sizes: Dict) -> Dict:
    """``params``: ``rate_per_s``, ``arrival_block_s``; ``prompt``,
    ``output`` and ``stratified`` (see ``lengths.requests``). Exactly
    ``round(rate_per_s * arrival_block_s)`` arrivals at seeded uniform
    times inside every block of ``arrival_block_s`` seconds: a Poisson
    process given its count in each block. ``stratified`` lengths are
    stratified within each block's requests (``stratified_block``, see
    ``lengths.draw``).

    This is VARIANCE REDUCTION, not what users do: a plain Poisson
    count moves by 1 / sqrt(count) from stretch to stretch (10 % over
    10 s at 9.5/s), and the gaps and tails follow the load. With the
    count fixed per block every block carries the same work in every
    seed, in another order, and so does any window that is a whole
    number of blocks; inside a block the arrivals are as bursty as a
    Poisson process is, but load swings slower than a block are gone.
    One block as long as the run gives the count over the whole run
    only: the share of it that falls inside the measured window then
    moves by a few per cent from seed to seed (458 to 511 of 665 at
    9.5/s; PR 26)."""
    rs = np.random.RandomState(seed)
    block_s = float(params["arrival_block_s"])
    per_block = int(round(float(params["rate_per_s"]) * block_s))
    blocks = int(np.ceil(seconds / block_s - 1e-9))
    due = np.concatenate([
        np.sort(rs.uniform(b * block_s, (b + 1) * block_s, size=per_block)) for b in range(blocks)
    ])
    due = due[due < seconds]
    if params.get("stratified"):
        params = dict(params, **{k: dict(params[k], stratified_block=per_block) for k in ("prompt", "output")})
    reqs = lengths.requests(rs, len(due), params, sizes["vocab_size"])
    for r, d in zip(reqs, due):
        r["due_s"] = float(d)
    return {"mode": "open", "requests": reqs}
