"""Resolve a cell of ``BENCHMARK.json`` into the files it names."""
from __future__ import annotations

import dataclasses
import json
from pathlib import Path
from typing import Dict, List, Optional

BENCH_DIR = Path(__file__).resolve().parents[1]          # benchmarks/chip
ROOT = BENCH_DIR.parents[1]                              # the checkout


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: Dict
    traffic: Dict
    limits: Dict
    end_to_end: List[Dict]
    per_layer: List[Dict]

    @property
    def kind(self) -> str:
        """``batch`` for a backlog cell, ``online`` for an open loop."""
        return "batch" if self.traffic["arrivals"] == "backlog" else "online"


def read_json(path: Path) -> Dict:
    with open(path) as f:
        return json.load(f)


def _applies(metric: Dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def resolve(name: str, root: Path = ROOT,
            bench: Optional[Dict] = None) -> Cell:
    """The cell ``name`` with its configuration, traffic and limits read,
    and the metrics that it reports."""
    bench = bench if bench is not None else read_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; known: "
                       f"{sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    config = read_json(root / configs[w["config"]]["file"])
    traffic = read_json(BENCH_DIR / "traffic" / f"{w['traffic']}.json")
    limits = read_json(BENCH_DIR / "limits" / f"{name}.json")
    return Cell(name=name, chips=int(w["chips"]), config=config,
                traffic=traffic, limits=limits,
                end_to_end=[m for m in bench["end_to_end"]
                            if _applies(m, name)],
                per_layer=[m for m in bench["per_layer"]
                           if _applies(m, name)])


def peak_for(kind: str) -> Dict:
    """The published peaks of one chip of ``kind`` (``peaks.json``); a
    device that is not in the table is an error, not a default."""
    table = read_json(BENCH_DIR / "peaks.json")
    if kind not in table["devices"]:
        raise KeyError(f"device kind {kind!r} is not in peaks.json")
    return table["devices"][kind]
