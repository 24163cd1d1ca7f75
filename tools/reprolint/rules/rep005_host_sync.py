"""REP005 — host synchronization on the engine hot path.

``Engine.decode_step`` is the serving clock: everything between two
jit'd step dispatches is host-side critical path. Forcing a device
value back to the host there (``np.asarray``, ``.item()``, ``float()``,
``int()``) blocks on the device and serializes dispatch — the class of
regression the single-dispatch-per-step work (PR 5) exists to prevent.
One sync per step is load-bearing (the sampled tokens drive branch
bookkeeping); it carries an inline suppression with its justification.
Everything else should stay on device or ride that one sync.

Detection is a per-function taint walk, scoped to ``serving/``:

  * **sources** — names assigned (incl. tuple unpacking) from a call
    whose callee ends in ``_jit`` or is ``_run_step`` /
    ``decode_step`` (the step dispatchers);
  * **propagation** — subscripts/slices of tainted names stay device
    values;
  * **sinks** — ``np.asarray(t)`` / ``np.array(t)`` / ``float(t)`` /
    ``int(t)`` / ``t.item()`` / ``t.tolist()`` on a tainted value.
"""
from __future__ import annotations

import ast
from typing import Iterator, Set

from ..framework import (FileContext, Finding, ProjectContext, Rule,
                         dotted_name, register)

_STEP_CALLEES = ("_run_step", "decode_step")
_SINK_CALLS = ("np.asarray", "np.array", "numpy.asarray", "numpy.array",
               "float", "int", "bool")
_SINK_METHODS = ("item", "tolist", "block_until_ready")


def _is_step_call(node: ast.expr) -> bool:
    if not isinstance(node, ast.Call):
        return False
    name = dotted_name(node.func).rsplit(".", 1)[-1]
    return name.endswith("_jit") or name in _STEP_CALLEES


def _tainted_names(fn: ast.FunctionDef) -> Set[str]:
    tainted: Set[str] = set()
    for node in ast.walk(fn):
        if not isinstance(node, ast.Assign) or not _is_step_call(node.value):
            continue
        for tgt in node.targets:
            elts = tgt.elts if isinstance(tgt, ast.Tuple) else [tgt]
            for el in elts:
                if isinstance(el, ast.Name):
                    tainted.add(el.id)
    return tainted


def _is_tainted_expr(node: ast.expr, tainted: Set[str]) -> bool:
    if isinstance(node, ast.Name):
        return node.id in tainted
    if isinstance(node, ast.Subscript):
        return _is_tainted_expr(node.value, tainted)
    return False


@register
class HostSyncRule(Rule):
    code = "REP005"
    name = "hot-path-host-sync"
    summary = ("np.asarray/.item()/float() on a jit-step result inside "
               "serving loop bodies — blocks the device between steps")
    path_filter = ("serving/",)

    def check(self, ctx: FileContext,
              project: ProjectContext) -> Iterator[Finding]:
        for fn in ast.walk(ctx.tree):
            if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            tainted = _tainted_names(fn)
            if not tainted:
                continue
            for node in ast.walk(fn):
                if not isinstance(node, ast.Call):
                    continue
                if ctx.enclosing_function(node) is not fn:
                    continue
                callee = dotted_name(node.func)
                if callee in _SINK_CALLS and node.args and \
                        _is_tainted_expr(node.args[0], tainted):
                    yield ctx.finding(
                        node, self.code,
                        f"`{callee}(...)` forces the jit-step result "
                        f"`{ast.unparse(node.args[0])}` to host inside "
                        f"`{fn.name}` — a device sync on the decode hot "
                        "path; keep it on device or justify with an "
                        "inline suppression")
                elif isinstance(node.func, ast.Attribute) and \
                        node.func.attr in _SINK_METHODS and \
                        _is_tainted_expr(node.func.value, tainted):
                    yield ctx.finding(
                        node, self.code,
                        f"`.{node.func.attr}()` on the jit-step result "
                        f"`{ast.unparse(node.func.value)}` in "
                        f"`{fn.name}` — a device sync on the decode hot "
                        "path")
