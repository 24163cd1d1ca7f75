"""Reduce a profiler trace to the numbers the per-layer metrics read.

``load`` turns the JAX profiler's ``.xplane.pb`` into a compact form: the
device operations of each TPU plane (name, start, duration, in ns) and the
benchmark's own host spans (``HOST_SPANS``). Everything else here works on
that form, so the reduction is checked on small traces in that form
(``bench/tests/test_sartbench_trace.py``).
"""
from __future__ import annotations

import glob
import json
from collections import defaultdict
from pathlib import Path
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

import numpy as np

# host spans the benchmark writes (proxy and load generator)
HOST_SPANS = ("scheduler", "engine.decode_step", "prm.score",
              "idle.wait_arrival")
OPS_LINE = "XLA Ops"


def load(trace_dir: str) -> dict:
    """Compact form of the newest trace under ``trace_dir``."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = ProfileData.from_file(paths[-1])
    devices, host = [], []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            ops = []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops += [(ev.name, int(ev.start_ns), int(ev.duration_ns))
                            for ev in line.events]
            devices.append(sorted(ops, key=lambda e: e[1]))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host += [(ev.name, int(ev.start_ns), int(ev.duration_ns))
                         for ev in line.events if ev.name in HOST_SPANS]
    return {"devices": devices, "host": sorted(host, key=lambda e: e[1])}


def save(compact: dict, path: Path) -> None:
    Path(path).write_text(json.dumps(compact))


def clip(events: Iterable[Sequence], lo: int, hi: int) -> List[Tuple]:
    """Events cut to [lo, hi] ns; those outside dropped."""
    out = []
    for name, s, d in events:
        a, b = max(s, lo), min(s + d, hi)
        if b > a:
            out.append((name, a, b - a))
    return out


def union_ns(events: Iterable[Sequence]) -> int:
    """Length of the union of the events' intervals."""
    iv = sorted((s, s + d) for _, s, d in events)
    total, cur_s, cur_e = 0, None, None
    for s, e in iv:
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def idle_gaps(events: Iterable[Sequence], lo: int,
              hi: int) -> List[Tuple[int, int]]:
    """(start, length) of each stretch in [lo, hi] with no event."""
    gaps, t = [], lo
    for _, s, d in sorted(events, key=lambda e: e[1]):
        if s > t:
            gaps.append((t, s - t))
        t = max(t, s + d)
    if hi > t:
        gaps.append((t, hi - t))
    return gaps


def window(compact: dict) -> Tuple[int, int]:
    """The traced stretch: first to last ``engine.decode_step`` span."""
    steps = [e for e in compact["host"] if e[0] == "engine.decode_step"]
    if not steps:
        raise ValueError("the trace holds no engine.decode_step span")
    return steps[0][1], steps[-1][1] + steps[-1][2]


def has_device_ops(compact: dict) -> bool:
    """Whether any chip's trace holds a device operation."""
    return any(compact["devices"])


def busy_ns(compact: dict, lo: int, hi: int) -> float:
    """Device busy time in [lo, hi], averaged over the chips."""
    devs = compact["devices"]
    if not devs:
        return 0.0
    return float(np.mean([union_ns(clip(ops, lo, hi)) for ops in devs]))


def kernel_ns(compact: dict, names: Sequence[str], lo: int, hi: int) -> int:
    """Device time of the operations whose name holds any of ``names``."""
    return sum(d for ops in compact["devices"]
               for n, _, d in clip(ops, lo, hi)
               if any(k in n for k in names))


def top_ops(compact: dict, lo: int, hi: int, k: int = 10) -> List[list]:
    """The ``k`` device operations (by name) that took most time, in
    seconds, averaged over the chips."""
    acc: Dict[str, float] = defaultdict(float)
    n = max(len(compact["devices"]), 1)
    for ops in compact["devices"]:
        for name, _, d in clip(ops, lo, hi):
            acc[name] += d / 1e9 / n
    return [[name, s] for name, s in
            sorted(acc.items(), key=lambda kv: -kv[1])[:k]]


def _innermost(host: List[Tuple], t: int) -> str:
    best: Optional[Tuple] = None
    for name, s, d in host:
        if s <= t < s + d and (best is None or d < best[2]):
            best = (name, s, d)
    return best[0] if best else "outside_spans"


def idle_by_host(compact: dict, lo: int, hi: int,
                 k: int = 10) -> List[list]:
    """Device idle time in [lo, hi] by what the host was doing: each gap
    of the first chip goes to the innermost host span at its midpoint."""
    if not compact["devices"]:
        return []
    host = clip(compact["host"], lo, hi)
    acc: Dict[str, float] = defaultdict(float)
    for s, d in idle_gaps(clip(compact["devices"][0], lo, hi), lo, hi):
        acc[_innermost(host, s + d // 2)] += d / 1e9
    return [[name, sec] for name, sec in
            sorted(acc.items(), key=lambda kv: -kv[1])[:k]]


def span_ns(compact: dict, name: str, lo: int, hi: int) -> int:
    return sum(d for n, _, d in clip(compact["host"], lo, hi) if n == name)


def device_ns_within(compact: dict, spans: List[Tuple]) -> int:
    """Device busy time (first chip) inside the given host spans."""
    if not compact["devices"]:
        return 0
    ops = compact["devices"][0]
    return sum(union_ns(clip(ops, s, s + d)) for _, s, d in spans)
