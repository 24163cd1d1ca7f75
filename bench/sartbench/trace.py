"""Reduce a profiler trace to the numbers the per-layer metrics read.

``load`` turns the JAX profiler's ``.xplane.pb`` into a compact form: the
device operations of each TPU plane (name, start, duration, in ns) under
``devices``, the benchmark's own host spans (``HOST_SPANS``) under
``host``, and under ``program`` every span the program wrote through
``repro.tracing.span``, as ``[name, start, duration, {stat: value}]``.
Everything else here works on that form, so the reduction is checked on
small traces in that form (``bench/tests/test_sartbench_trace.py``).

The program's spans are found by name, and the names by reading the
program's source (``program_span_names``): each string literal passed as
the first argument to ``repro.tracing.span``. A span the program adds is
in the compact form with no edit here; a reader file takes it from
``program``. A compact trace without ``program`` loads as before, and
``program`` returns None for it.

Where the installation holds the profiler's XPlane protobuf module
(``xplane_pb2``, which TensorFlow ships), ``device_scopes`` holds the same
operations as ``devices`` with the scope path XLA gives each in its
metadata (``SCOPE_STAT``: ``jit(...)/.../attn_decode/...``), as
``[name, start, duration, {"tf_op": path}]``; ``scope_ns`` reads device
time by a ``jax.named_scope`` from it, and None where the trace has none.
"""
from __future__ import annotations

import ast
import functools
import glob
import importlib.util
import json
from collections import defaultdict
from pathlib import Path
from typing import (Dict, FrozenSet, Iterable, Iterator, List, Optional,
                    Sequence, Tuple)

import numpy as np

# host spans the benchmark writes (proxy and load generator)
HOST_SPANS = ("scheduler", "engine.decode_step", "prm.score",
              "idle.wait_arrival")
OPS_LINE = "XLA Ops"
# the stat of a device operation's metadata that holds its scope path
SCOPE_STAT = "tf_op"
XPLANE_PB2 = ("tensorflow", "tsl", "profiler", "protobuf", "xplane_pb2")


def _span_names(tree: ast.AST) -> Iterator[str]:
    """First arguments, where string literals, of the calls of
    ``repro.tracing.span`` in one module: ``span(...)`` after ``from
    <...>tracing import span``, ``<module>.span(...)`` after an import of
    the ``tracing`` module."""
    fns, mods = set(), set()
    for node in ast.walk(tree):
        if isinstance(node, ast.ImportFrom):
            if (node.module or "").split(".")[-1] == "tracing":
                fns |= {a.asname or a.name for a in node.names
                        if a.name == "span"}
            else:
                mods |= {a.asname or a.name for a in node.names
                         if a.name == "tracing"}
        elif isinstance(node, ast.Import):
            mods |= {a.asname or a.name for a in node.names
                     if a.name.split(".")[-1] == "tracing"}
    for node in ast.walk(tree):
        if not (isinstance(node, ast.Call) and node.args
                and isinstance(node.args[0], ast.Constant)
                and isinstance(node.args[0].value, str)):
            continue
        f = node.func
        if (isinstance(f, ast.Name) and f.id in fns) or (
                isinstance(f, ast.Attribute) and f.attr == "span"
                and ast.unparse(f.value) in mods):
            yield node.args[0].value


@functools.lru_cache(maxsize=None)
def _names_under(roots: Tuple[Path, ...]) -> FrozenSet[str]:
    names = set()
    for root in roots:
        for path in sorted(root.rglob("*.py")):
            names.update(_span_names(ast.parse(path.read_text(),
                                               str(path))))
    return frozenset(names)


def program_span_names() -> FrozenSet[str]:
    """The names the program gives ``repro.tracing.span``, read from the
    ``.py`` files of its package, ``repro`` (every directory of it)."""
    import repro
    return _names_under(tuple(Path(p).resolve() for p in repro.__path__))


def _stat(value):
    return value if isinstance(value, (int, float, str)) else str(value)


@functools.lru_cache(maxsize=None)
def _xplane_pb2():
    """The generated XPlane protobuf module, loaded from its file in the
    installed package (``XPLANE_PB2``) without importing that package;
    None where no package holds it."""
    try:
        spec = importlib.util.find_spec(XPLANE_PB2[0])
    except (ImportError, ValueError):
        return None
    for loc in (spec.submodule_search_locations or ()) if spec else ():
        path = Path(loc).joinpath(*XPLANE_PB2[1:-1], XPLANE_PB2[-1] + ".py")
        if not path.is_file():
            continue
        mod_spec = importlib.util.spec_from_file_location(
            "sartbench_xplane_pb2", path)
        mod = importlib.util.module_from_spec(mod_spec)
        try:
            mod_spec.loader.exec_module(mod)
        except Exception:          # a protobuf runtime it does not take
            return None
        return mod
    return None


def _op_scopes(path: str) -> Dict[str, List[Optional[str]]]:
    """Per TPU plane, the scope path of each operation of its ops line, in
    the order the trace stores them; empty without ``xplane_pb2``."""
    pb2 = _xplane_pb2()
    if pb2 is None:
        return {}
    space = pb2.XSpace()
    space.ParseFromString(Path(path).read_bytes())
    out = {}
    for plane in space.planes:
        if not plane.name.startswith("/device:TPU:"):
            continue
        names = {k: m.name for k, m in plane.stat_metadata.items()}

        def scope(meta_id):
            for st in plane.event_metadata[meta_id].stats:
                if names.get(st.metadata_id) == SCOPE_STAT:
                    kind = st.WhichOneof("value")
                    return (names.get(st.ref_value) if kind == "ref_value"
                            else str(getattr(st, kind)))
            return None
        out[plane.name] = [scope(ev.metadata_id) for line in plane.lines
                           if line.name == OPS_LINE for ev in line.events]
    return out


def load(trace_dir: str) -> dict:
    """Compact form of the newest trace under ``trace_dir``."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = ProfileData.from_file(paths[-1])
    spans = program_span_names()
    scopes = _op_scopes(paths[-1])
    devices, host, program, scoped = [], [], [], []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            ops = []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops += [(ev.name, int(ev.start_ns), int(ev.duration_ns))
                            for ev in line.events]
            devices.append(sorted(ops, key=lambda e: e[1]))
            op_paths = scopes.get(plane.name)
            if op_paths is not None and len(op_paths) == len(ops):
                scoped.append(sorted(
                    ((*op, {SCOPE_STAT: p} if p else {})
                     for op, p in zip(ops, op_paths)), key=lambda e: e[1]))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in HOST_SPANS:
                        host.append((ev.name, int(ev.start_ns),
                                     int(ev.duration_ns)))
                    if ev.name in spans:
                        program.append((ev.name, int(ev.start_ns),
                                        int(ev.duration_ns),
                                        {k: _stat(v) for k, v in ev.stats}))
    compact = {"devices": devices, "host": sorted(host, key=lambda e: e[1]),
               "program": sorted(program, key=lambda e: e[1])}
    if scoped and len(scoped) == len(devices) and any(
            e[3] for ops in scoped for e in ops):
        compact["device_scopes"] = scoped
    return compact


def save(compact: dict, path: Path) -> None:
    Path(path).write_text(json.dumps(compact))


def clip(events: Iterable[Sequence], lo: int, hi: int) -> List[Tuple]:
    """Events cut to [lo, hi] ns, with what follows their duration (a
    program span's stats); those outside dropped."""
    out = []
    for name, s, d, *rest in events:
        a, b = max(s, lo), min(s + d, hi)
        if b > a:
            out.append((name, a, b - a, *rest))
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


def program(compact: dict, lo: int, hi: int) -> Optional[List[Tuple]]:
    """The program's spans cut to [lo, hi], as (name, start, duration,
    stats); None for a compact trace that does not hold them."""
    if "program" not in compact:
        return None
    return clip(compact["program"], lo, hi)


def scope_ns(compact: dict, scope: str, lo: int,
             hi: int) -> Optional[int]:
    """Device time in [lo, hi] of the operations whose scope path holds
    ``scope`` as one of its parts (``jax.named_scope``), the union of
    their intervals on each chip, summed over the chips; None for a
    compact trace without ``device_scopes``."""
    if "device_scopes" not in compact:
        return None
    return sum(union_ns(clip(
        [e[:3] for e in ops if scope in e[3].get(SCOPE_STAT, "").split("/")],
        lo, hi)) for ops in compact["device_scopes"])


def span_ns(compact: dict, name: str, lo: int, hi: int) -> int:
    return sum(d for n, _, d in clip(compact["host"], lo, hi) if n == name)


def device_ns_within(compact: dict, spans: List[Tuple]) -> int:
    """Device busy time (first chip) inside the given host spans."""
    if not compact["devices"]:
        return 0
    ops = compact["devices"][0]
    return sum(union_ns(clip(ops, s, s + d)) for _, s, d in spans)
