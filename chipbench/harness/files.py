"""Find a cell's files by the names in ``BENCHMARK.json``.

Layout (everything under ``chipbench/``)::

    configs/<config>.json     model configuration as run, and its source
    traffic/<traffic>.json    fleet, strategy, knobs, data, warm-up
    limits/<cell>.json        the limits of the numbers the check compares
    metrics/<metric>.py       one reader per per-layer metric
    reference/<name>.py       the plain reference a configuration names
    peaks.json                the chips' published peaks, by device kind
"""
from __future__ import annotations

import json
import os
from dataclasses import dataclass
from typing import Dict, List

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CHECKOUT = os.path.dirname(BENCH_DIR)
#: where cells are looked up; tests point these at files of their own
BENCH_PATH = os.path.join(CHECKOUT, "BENCHMARK.json")
DATA_DIR = BENCH_DIR


def _load(path: str) -> Dict:
    with open(path) as f:
        return json.load(f)


@dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: Dict          # configs/<config>.json
    traffic: Dict         # traffic/<traffic>.json
    limits: Dict          # limits/<cell>.json
    end_to_end: List[Dict]
    per_layer: List[Dict]

    @property
    def model(self) -> Dict:
        return self.config["model"]


def bench_file() -> Dict:
    return _load(BENCH_PATH)


def cell(name: str) -> Cell:
    bench = bench_file()
    work = {w["name"]: w for w in bench["workloads"]}
    if name not in work:
        raise SystemExit(f"chipbench: no workload {name!r} in BENCHMARK.json; "
                         f"known: {sorted(work)}")
    w = work[name]
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]
    in_cell = lambda m: name in m.get("workloads", [name])
    return Cell(
        name=name, chips=int(w["chips"]),
        config=_load(os.path.join(os.path.dirname(BENCH_PATH), conf["file"])),
        traffic=_load(os.path.join(DATA_DIR, "traffic", w["traffic"] + ".json")),
        limits=_load(os.path.join(DATA_DIR, "limits", name + ".json")),
        end_to_end=[m for m in bench["end_to_end"] if in_cell(m)],
        per_layer=[m for m in bench["per_layer"] if in_cell(m)])


def peaks(device_kind: str) -> Dict:
    table = _load(os.path.join(BENCH_DIR, "peaks.json"))
    if device_kind not in table["devices"]:
        raise SystemExit(f"chipbench: device kind {device_kind!r} has no row "
                         f"in peaks.json; known: {sorted(table['devices'])}")
    return table["devices"][device_kind]
