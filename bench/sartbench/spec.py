"""Find a cell's files by the names in ``BENCHMARK.json``.

A cell names a configuration and a traffic mix. The configuration is
``bench/configs/<config>.json``, the traffic mix ``bench/traffic/<traffic>.json``
and each metric ``bench/metrics/<metric>.py``; adding any of them is adding
files, never editing one.
"""
from __future__ import annotations

import dataclasses
import functools
import importlib.util
import json
from pathlib import Path
from types import ModuleType
from typing import Dict, List, Optional, Tuple

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

    @functools.cached_property
    def work(self) -> "Work":
        """What each layer of the configuration holds, by kind."""
        return describe(self.config)


# the widths each kind of layer group states, besides ``kind``/``layers``
KINDS = {
    "attention": ("heads", "kv_heads", "head_dim"),
    "mlp": ("width", "gated"),
    "moe": ("experts", "held", "per_token", "width", "shared_width",
            "gated"),
    "mamba2": ("heads", "head_dim", "state", "groups", "conv"),
}


@dataclasses.dataclass(frozen=True)
class Work:
    """What the layers of a configuration hold, by kind."""
    d_model: int
    vocab_size: int
    tie_embeddings: bool
    groups: Tuple[dict, ...]

    def of(self, kind: str) -> List[dict]:
        return [g for g in self.groups if g["kind"] == kind]


def attention_widths(model: dict) -> tuple:
    """(heads, kv_heads, head_dim) that ``model`` builds its K/V pool
    from."""
    h = model.get("num_heads")
    return (h, model.get("num_kv_heads"),
            model.get("head_dim") or (h and model["d_model"] // h))


def uniform(model: dict) -> List[dict]:
    """The groups of a model whose every layer is attention plus an MLP."""
    L = model["num_layers"]
    h, kv, hd = attention_widths(model)
    return [{"kind": "attention", "layers": L, "heads": h, "kv_heads": kv,
             "head_dim": hd},
            {"kind": "mlp", "layers": L, "width": model["d_ff"],
             "gated": bool(model.get("mlp_gated", True))}]


def _check_group(i: int, g) -> dict:
    if not isinstance(g, dict) or g.get("kind") not in KINDS:
        kind = g.get("kind") if isinstance(g, dict) else g
        raise SpecError(f"work[{i}]: unknown kind {kind!r}; known: "
                        f"{sorted(KINDS)}")
    need = ("kind", "layers") + KINDS[g["kind"]]
    missing = [k for k in need if k not in g]
    extra = sorted(set(g) - set(need))
    if missing or extra:
        raise SpecError(f"work[{i}] ({g['kind']}): missing {missing}, "
                        f"not of this kind {extra}")
    for k in need[1:]:
        v = g[k]
        if k == "gated":
            ok = isinstance(v, bool)
        else:
            least = 0 if k == "shared_width" else 1
            ok = isinstance(v, int) and not isinstance(v, bool) \
                and v >= least
        if not ok:
            raise SpecError(f"work[{i}] ({g['kind']}): {k} = {v!r}")
    if g["kind"] == "moe" and not (g["per_token"] <= g["experts"]
                                   and g["held"] <= g["experts"]):
        raise SpecError(f"work[{i}] (moe): per_token and held are at "
                        f"most experts ({g['experts']})")
    return dict(g)


def _check_attention(i: int, g: dict, model: dict) -> None:
    """An attention group states the widths of ``model``'s K/V pool, so
    that the counts are of the pool that is timed."""
    got = (g["heads"], g["kv_heads"], g["head_dim"])
    want = attention_widths(model)
    if got != want:
        raise SpecError(f"work[{i}] (attention): heads, kv_heads, head_dim "
                        f"{got} differ from model's {want}")


def describe(config: dict) -> Work:
    """The layer groups of a configuration file (module docstring of
    ``roofline``): its ``work``, or the uniform reading of its ``model``
    where it has none."""
    model = config["model"]
    groups = config.get("work")
    if groups is None:
        groups = uniform(model)
    if not isinstance(groups, list) or not groups:
        raise SpecError("work is a non-empty list of layer groups")
    checked = tuple(_check_group(i, g) for i, g in enumerate(groups))
    for i, g in enumerate(checked):
        if g["kind"] == "attention":
            _check_attention(i, g, model)
    return Work(int(model["d_model"]), int(model["vocab_size"]),
                bool(model.get("tie_embeddings", False)), checked)


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
    cell = Cell(workload, int(w["chips"]), load_config(w["config"], bench),
                load_traffic(w["traffic"], bench), metrics)
    cell.work                      # a bad ``work`` fails before any run
    return cell
