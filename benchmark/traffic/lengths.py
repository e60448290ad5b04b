"""Length distributions and request bodies shared by the request
generators: all draws come from one ``numpy.random.RandomState``."""
from __future__ import annotations

from statistics import NormalDist
from typing import Dict, List

import numpy as np


def draw(rs: np.random.RandomState, spec: Dict, n: int, stratified: bool = False) -> np.ndarray:
    """``n`` integer lengths from ``spec``: ``{"dist": "lognormal",
    "median", "sigma", "min", "max"}`` or ``{"dist": "uniform", "min",
    "max"}`` (inclusive).

    ``stratified``: one draw from each of ``n`` equal slices of the
    distribution, in seeded random order, instead of ``n`` independent
    draws. Every run then carries the same amount of work whatever its
    seed (the lengths' sum hardly moves), which is what lets two runs of
    one cell be compared; any one request's length is still random.

    ``spec["stratified_block"]``: the same within every ``m``
    consecutive requests, for a list of which a run uses only as much
    as the server gets through (a closed loop): any stretch of it then
    sums alike, wherever the run stops."""
    block = int(spec.get("stratified_block", n if stratified else 0))
    if block and n:
        q = np.concatenate([
            rs.permutation((np.arange(block) + rs.uniform(size=block)) / block) for _ in range(-(-n // block))
        ])[:n]
    else:
        q = rs.uniform(size=n)
    dist = spec["dist"]
    if dist == "uniform":
        lo, hi = int(spec["min"]), int(spec["max"])
        return np.minimum(lo + np.floor(q * (hi - lo + 1)), hi).astype(np.int64)
    if dist == "lognormal":
        z = np.asarray([NormalDist().inv_cdf(min(max(p, 1e-12), 1 - 1e-12)) for p in q])
        x = float(spec["median"]) * np.exp(float(spec["sigma"]) * z)
        return np.clip(np.rint(x), int(spec["min"]), int(spec["max"])).astype(np.int64)
    raise ValueError(f"unknown length distribution {dist!r}")


def requests(rs: np.random.RandomState, n: int, params: Dict, vocab_size: int) -> List[Dict]:
    """``n`` greedy request bodies: prompt lengths from
    ``params["prompt"]``, reply lengths from ``params["output"]``,
    stratified if ``params["stratified"]`` (see ``draw``). Tokens are
    uniform over the vocabulary, so no two prompts share a prefix."""
    strat = bool(params.get("stratified", False))
    prompt_lens = draw(rs, params["prompt"], n, strat)
    output_lens = draw(rs, params["output"], n, strat)
    return [
        {
            "id": i,
            "prompt": rs.randint(0, vocab_size, size=int(prompt_lens[i])).tolist(),
            "max_new_tokens": int(output_lens[i]),
        }
        for i in range(n)
    ]
