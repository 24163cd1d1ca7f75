"""jaxpr plumbing shared by the analyzers: recursive equation walks,
source attribution, aval/signature rendering.

Everything here operates on the ``ClosedJaxpr`` objects the harness
produced — pure data, no device, no re-tracing.
"""
from __future__ import annotations

import hashlib
from typing import Iterator, List, Optional, Tuple

import jax
import numpy as np

from jax._src import source_info_util as _siu


def iter_eqns(jaxpr) -> Iterator[object]:
    """Every equation in ``jaxpr`` (a ``Jaxpr``), recursing into the
    sub-jaxprs carried by pjit/scan/cond/while/remat params."""
    for eqn in jaxpr.eqns:
        yield eqn
        for value in eqn.params.values():
            values = value if isinstance(value, (list, tuple)) else [value]
            for sub in values:
                inner = getattr(sub, "jaxpr", None)  # ClosedJaxpr -> Jaxpr
                if inner is not None and hasattr(inner, "eqns"):
                    yield from iter_eqns(inner)
                elif hasattr(sub, "eqns"):           # bare Jaxpr
                    yield from iter_eqns(sub)


def source_symbol(eqn) -> str:
    """``file:function`` of the innermost repo frame that emitted ``eqn``
    (paths shortened to be src-relative), or ``<jax>:fn`` when every
    frame is library code. Line-number-free on purpose: the string is a
    baseline key and must survive unrelated edits."""
    frames = list(_siu.user_frames(eqn.source_info.traceback))
    for fr in frames:
        file_name = fr.file_name or ""
        if "/repro/" in file_name or file_name.startswith("repro/"):
            short = (file_name.split("/src/", 1)[-1]
                     if "/src/" in file_name else file_name)
            return f"{short}:{fr.function_name}"
    if frames:
        return f"<jax>:{frames[0].function_name}"
    return "<unknown>"


def aval_str(aval) -> str:
    """Canonical short form, e.g. ``f32[4,64]`` / ``bf16[2,8,128]``."""
    dtype = np.dtype(aval.dtype) if hasattr(aval, "dtype") else None
    name = {"float32": "f32", "float64": "f64", "float16": "f16",
            "bfloat16": "bf16", "int32": "i32", "int64": "i64",
            "uint32": "u32", "bool": "b1"}.get(
        str(aval.dtype) if dtype is not None else "?",
        str(getattr(aval, "dtype", "?")))
    shape = ",".join(str(d) for d in getattr(aval, "shape", ()))
    return f"{name}[{shape}]"


def variant_signature(closed_jaxpr) -> Tuple[str, List[str], List[str]]:
    """(sha256-16 digest, in-aval strings, out-aval strings) of a traced
    variant. The digest covers the full in/out aval lists — any retrace
    with different shapes or dtypes changes it."""
    in_avals = [aval_str(a) for a in closed_jaxpr.in_avals]
    out_avals = [aval_str(a) for a in closed_jaxpr.out_avals]
    payload = "|".join(in_avals) + "->" + "|".join(out_avals)
    digest = hashlib.sha256(payload.encode("utf-8")).hexdigest()[:16]
    return digest, in_avals, out_avals


def is_float_dtype(dtype) -> bool:
    """True for any floating dtype including the ml_dtypes extended ones
    (``np.issubdtype`` does not recognize bfloat16)."""
    return jax.numpy.issubdtype(dtype, jax.numpy.floating)


def float_width(dtype) -> int:
    return np.dtype(dtype).itemsize


def leaf_groups(engine, variant) -> List[Tuple[str, int]]:
    """(top-level argument name, number of flat leaves) in the exact
    order ``jax.make_jaxpr`` flattens ``(params, state, *variant.args)``
    — used to map jaxpr invars back to step arguments."""
    names = ["params", "state", "tokens", "positions", "block_tables",
             "lengths", "rng", "chunk_state", "chunk_lens", "slot_valid",
             "cow_src", "cow_dst", "tree"]
    values = (engine.params, engine.state) + tuple(variant.args)
    assert len(names) == len(values), (len(names), len(values))
    return [(name, len(jax.tree_util.tree_leaves(value)))
            for name, value in zip(names, values)]


def param_leaf_paths(params) -> List[str]:
    """Human-readable path per flat params leaf (for STEP006 messages)."""
    flat, _ = jax.tree_util.tree_flatten_with_path(params)
    return [jax.tree_util.keystr(path) for path, _leaf in flat]
