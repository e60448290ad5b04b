"""Traffic generators, found by the name a traffic file gives.

A traffic MIX is a data file, ``traffic/<traffic>.json``: ``{"generator":
<name>, "params": {...}}``. A GENERATOR is ``traffic/<name>.py`` with
``schedule(seed, seconds, params, sizes) -> dict``: everything the run
will send, drawn from the seed before the clock starts. A new mix is a
new data file; a new generator is a new module; neither edits a file.
"""
from __future__ import annotations

import importlib
from typing import Dict


def schedule(generator: str, seed: int, seconds: float, params: Dict, sizes: Dict) -> Dict:
    mod = importlib.import_module(f"benchmark.traffic.{generator}")
    return mod.schedule(seed, seconds, params, sizes)
