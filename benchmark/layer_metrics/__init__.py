"""Metric readers, found by the name ``BENCHMARK.json`` gives a metric.

A METRIC, end-to-end or per-layer, is ``layer_metrics/<reader>.py``: a
docstring (layer, source, arithmetic) and ``read(ctx)`` over what the
driver gathered. ``<reader>`` is the metric's name up to its first ``.``
(``device_idle_share.itl`` and ``device_idle_share.train`` share a
reader and differ in what they ``move``). A reader that finds nothing to
read returns None. The result line, the drivers' log lines and the knee
sweep all take their numbers here, so each is worked out in one place.
"""
from __future__ import annotations

import importlib
from typing import Callable, Dict, List, Optional


def read(name: str, ctx: Dict) -> Optional[float]:
    reader = name.split(".", 1)[0]
    return importlib.import_module(f"benchmark.layer_metrics.{reader}").read(ctx)


def read_all(entries: List[Dict], ctx: Dict, log: Callable[[str], None]) -> Dict:
    """``{name: {"value", "unit"}}`` for the ``BENCHMARK.json`` entries
    whose reader found something; the others are left out, and logged."""
    out = {}
    for m in entries:
        value = read(m["name"], ctx)
        if value is None:
            log(f"metric {m['name']}: nothing to read")
            continue
        out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out
