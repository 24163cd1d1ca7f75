"""Find a cell's files by the names in ``BENCHMARK.json``.

A cell names a configuration and a traffic mix. The configuration is
``bench/configs/<config>.json``, the traffic mix ``bench/traffic/<traffic>.json``
and each metric ``bench/metrics/<metric>.py``; adding any of them is adding
files, never editing one.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path
from types import ModuleType
from typing import Dict, List, Optional

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent


class SpecError(ValueError):
    """A cell, configuration, traffic mix or metric that cannot be found
    or read."""


@dataclasses.dataclass(frozen=True)
class Metric:
    name: str
    unit: str
    better: str
    kind: str                      # "end_to_end" or "per_layer"
    workloads: Optional[List[str]]
    reader: ModuleType

    def applies_to(self, cell: str) -> bool:
        return self.workloads is None or cell in self.workloads


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    metrics: List[Metric]          # every metric this cell reports

    def metrics_of(self, kind: str) -> List[Metric]:
        return [m for m in self.metrics if m.kind == kind]


def _read_json(path: Path) -> dict:
    try:
        return json.loads(path.read_text())
    except (OSError, json.JSONDecodeError) as exc:
        raise SpecError(f"cannot read {path}: {exc}") from exc


def load_config(name: str, bench: Path = BENCH) -> dict:
    """The configuration file, as it is run."""
    cfg = _read_json(bench / "configs" / f"{name}.json")
    cfg["name"] = name
    return cfg


def load_traffic(name: str, bench: Path = BENCH) -> dict:
    """The traffic mix. A mix may name another under ``extends``: its
    parameters are taken first and this file's replace them key by key."""
    spec = _read_json(bench / "traffic" / f"{name}.json")
    base = spec.pop("extends", None)
    if base is not None:
        spec = {**load_traffic(base, bench), **spec}
    spec["name"] = name
    return spec


def load_reader(name: str, bench: Path = BENCH) -> ModuleType:
    """The metric's reader module, ``bench/metrics/<name>.py``, which
    defines ``read(run) -> float | None``."""
    path = bench / "metrics" / f"{name}.py"
    if not path.is_file():
        raise SpecError(f"no reader for metric {name!r} at {path}")
    mod_spec = importlib.util.spec_from_file_location(
        f"sartbench_metric_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    if not callable(getattr(mod, "read", None)):
        raise SpecError(f"{path} defines no read(run)")
    return mod


def load_benchmark(root: Path = ROOT) -> dict:
    return _read_json(root / "BENCHMARK.json")


def load_cell(workload: str, root: Path = ROOT) -> Cell:
    bench_json = load_benchmark(root)
    entries: Dict[str, dict] = {w["name"]: w for w in bench_json["workloads"]}
    if workload not in entries:
        raise SpecError(f"unknown workload {workload!r}; known: "
                        f"{sorted(entries)}")
    w = entries[workload]
    bench = root / "bench"
    metrics = []
    for kind in ("end_to_end", "per_layer"):
        for m in bench_json[kind]:
            metric = Metric(m["name"], m["unit"], m["better"], kind,
                            m.get("workloads"), load_reader(m["name"], bench))
            if metric.applies_to(workload):
                metrics.append(metric)
    return Cell(workload, int(w["chips"]), load_config(w["config"], bench),
                load_traffic(w["traffic"], bench), metrics)
