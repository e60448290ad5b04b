"""Which files make a cell, found by the names in ``BENCHMARK.json``.

A cell ``<config>.<traffic>`` is ``workloads/<cell>.json`` (chips,
driver, deployment settings, limits, why) over ``configs/<config>.json``
(the sizes, as run) under ``traffic/<traffic>.json`` (generator and
parameters; the cell's ``traffic_params`` fill what the mix leaves to
the cell, such as the rate). The metrics a cell reports are those of
``BENCHMARK.json`` that list it, or list no cell at all.
"""
from __future__ import annotations

import copy
import dataclasses
import json
import pathlib
from typing import Dict, List

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parent


def _load(path: pathlib.Path) -> Dict:
    with open(path) as f:
        return json.load(f)


def _merge(base: Dict, over: Dict) -> Dict:
    out = copy.deepcopy(base)
    for k, v in over.items():
        out[k] = _merge(out[k], v) if isinstance(v, dict) and isinstance(out.get(k), dict) else copy.deepcopy(v)
    return out


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: Dict
    traffic: Dict  # {"generator", "params"}
    workload: Dict
    end_to_end: List[Dict]
    per_layer: List[Dict]

    @property
    def driver(self) -> str:
        return self.workload["driver"]


def benchmark_json() -> Dict:
    return _load(ROOT / "BENCHMARK.json")


def metrics_for(entries: List[Dict], cell: str) -> List[Dict]:
    return [m for m in entries if "workloads" not in m or cell in m["workloads"]]


def load_cell(name: str, rehearsal: bool = False) -> Cell:
    bench = benchmark_json()
    entry = next((w for w in bench["workloads"] if w["name"] == name), None)
    if entry is None:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json (has {[w['name'] for w in bench['workloads']]})")
    workload = _load(HERE / "workloads" / f"{name}.json")
    cfg_entry = next(c for c in bench["configs"] if c["name"] == entry["config"])
    config = _load(ROOT / cfg_entry["file"])
    traffic = _load(HERE / "traffic" / f"{entry['traffic']}.json")
    if rehearsal:
        config = _merge(config, config.get("rehearsal", {}))
        workload = _merge(workload, workload.get("rehearsal", {}))
    traffic = {
        "generator": traffic["generator"],
        "params": _merge(traffic["params"], workload.get("traffic_params", {})),
    }
    missing = [k for k, v in traffic["params"].items() if v is None]
    if missing:
        raise ValueError(f"{name}: traffic parameters {missing} are set neither by the mix nor by the cell")
    e2e = metrics_for(bench["end_to_end"], name)
    names = {m["name"] for m in e2e}
    per_layer = [m for m in metrics_for(bench["per_layer"], name) if m["moves"] in names]
    return Cell(name, int(entry["chips"]), config, traffic, workload, e2e, per_layer)
