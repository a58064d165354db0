"""``BENCHMARK.json`` and the data files it names.

A cell is found by its name. Its configuration file is the one the
configuration entry names; its traffic file is ``traffic/<traffic>.json``
and each per-layer metric's reader is ``metrics/<metric>.py``, all under
the benchmark's directory. Adding a configuration, a traffic mix or a
metric therefore means adding files and entries, never editing code.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path
from typing import Callable, Dict, List, Optional

BENCH_DIR = Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parent


class SpecError(Exception):
    """The benchmark's files do not describe the cell asked for."""


@dataclasses.dataclass(frozen=True)
class Metric:
    name: str
    unit: str
    workloads: Optional[tuple]
    moves: Optional[str] = None

    def applies_to(self, workload: str) -> bool:
        return self.workloads is None or workload in self.workloads


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: List[Metric]
    per_layer: List[Metric]


def _metric(entry: dict) -> Metric:
    w = entry.get("workloads")
    return Metric(name=entry["name"], unit=entry["unit"],
                  workloads=tuple(w) if w is not None else None,
                  moves=entry.get("moves"))


def _read_json(path: Path) -> dict:
    try:
        with open(path) as f:
            return json.load(f)
    except FileNotFoundError:
        raise SpecError(f"missing benchmark file {path}") from None


def load_cell(workload: str, root: Path = ROOT,
              bench_dir: Path = BENCH_DIR) -> Cell:
    spec = _read_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise SpecError(f"no workload {workload!r} in BENCHMARK.json "
                        f"(have {sorted(cells)})")
    w = cells[workload]
    configs = {c["name"]: c for c in spec["configs"]}
    if w["config"] not in configs:
        raise SpecError(f"workload {workload!r} names unknown config "
                        f"{w['config']!r}")
    config = _read_json(root / configs[w["config"]]["file"])
    traffic = _read_json(bench_dir / "traffic" / f"{w['traffic']}.json")
    e2e = [m for m in map(_metric, spec["end_to_end"])
           if m.applies_to(workload)]
    layer = [m for m in map(_metric, spec["per_layer"])
             if m.applies_to(workload)]
    return Cell(name=workload, chips=int(w["chips"]), config=config,
                traffic=traffic, end_to_end=e2e, per_layer=layer)


def load_reader(metric: str, bench_dir: Path = BENCH_DIR) -> Callable:
    """The ``read(run)`` function of ``metrics/<metric>.py``."""
    path = bench_dir / "metrics" / f"{metric}.py"
    if not path.is_file():
        raise SpecError(f"no reader {path} for per-layer metric {metric!r}")
    mod_spec = importlib.util.spec_from_file_location(
        f"bench_metric_{metric.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod.read


def load_net(name: str, bench_dir: Path = BENCH_DIR) -> Dict:
    return _read_json(bench_dir / "nets" / f"{name}.json")
