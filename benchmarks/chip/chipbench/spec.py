"""Find a cell's files by the names in ``BENCHMARK.json``.

A workload names a configuration and a traffic mix. Each is a data file of
its own under this benchmark's directory:

    configs/<config>.json     sizes, serving settings, reference module
    traffic/<traffic>.json    arrival process and request fields
    limits/<workload>.json    the correctness limits and their readings

and each per-layer metric is a reader module ``chipbench/metrics/<name>.py``
(dots in a metric's name become underscores). A later change adds a cell,
a mix or a metric by adding such files and entries, never by editing code.
"""
from __future__ import annotations

import dataclasses
import importlib
import json
from pathlib import Path
from typing import Dict, List, Optional

BENCH_DIR = Path(__file__).resolve().parents[1]
REPO_ROOT = BENCH_DIR.parents[1]


@dataclasses.dataclass(frozen=True)
class Metric:
    name: str
    unit: str
    workloads: Optional[tuple] = None

    def applies_to(self, workload: str) -> bool:
        return self.workloads is None or workload in self.workloads


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config_name: str
    traffic_name: str
    config: dict
    traffic: dict
    limits: dict
    end_to_end: tuple
    per_layer: tuple


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def _metrics(entries: List[dict]) -> tuple:
    out = []
    for e in entries:
        wl = e.get("workloads")
        out.append(Metric(name=e["name"], unit=e["unit"],
                          workloads=tuple(wl) if wl is not None else None))
    return tuple(out)


def load_cell(workload: str, bench_json: Optional[Path] = None,
              bench_dir: Path = BENCH_DIR) -> Cell:
    """The cell ``workload`` of ``BENCHMARK.json`` with its files loaded."""
    bench = load_json(bench_json or REPO_ROOT / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"unknown workload {workload!r}; known: "
                       f"{sorted(cells)}")
    w = cells[workload]
    limits_path = bench_dir / "limits" / f"{workload}.json"
    return Cell(
        name=workload, chips=int(w["chips"]),
        config_name=w["config"], traffic_name=w["traffic"],
        config=load_json(bench_dir / "configs" / f"{w['config']}.json"),
        traffic=load_json(bench_dir / "traffic" / f"{w['traffic']}.json"),
        limits=load_json(limits_path) if limits_path.exists() else {},
        end_to_end=tuple(m for m in _metrics(bench["end_to_end"])
                         if m.applies_to(workload)),
        per_layer=tuple(m for m in _metrics(bench["per_layer"])
                        if m.applies_to(workload)))


def metric_reader(name: str):
    """The ``read(run)`` function of a per-layer metric's own module."""
    mod = importlib.import_module(
        f"chipbench.metrics.{name.replace('.', '_').replace('-', '_')}")
    return mod.read


def reference_module(config: dict):
    """The plain reference a configuration names (``"reference"`` key)."""
    return importlib.import_module(f"chipbench.{config['reference']}")


def readings(limits: dict) -> Dict[str, float]:
    """{number name: limit} of a cell's limits file."""
    return {k: float(v["limit"]) for k, v in limits.get("numbers", {}).items()}
