"""STEP007: concrete bounds proof for Pallas BlockSpec index maps.

Each kernel exports its launch geometry as a ``KernelGrid``
(``repro.kernels.introspect``) whose index maps are the exact callables
handed to Pallas. This module evaluates every index map at every grid
point — with concrete integers and numpy scalar-prefetch arrays — and
checks the block containment invariant for each operand dimension ``d``:

    0 <= idx[d] * block[d]  and  idx[d] * block[d] + block[d] <= array[d]

over a lattice of representative shapes: ragged lengths,
page-straddling resumed chunks, sentinel-laden block tables, GQA / MQA /
MHA head counts. REP003's syntactic clamp check made semantic: an
un-clamped sentinel chase fails here on the exact grid point that would
address HBM out of bounds on TPU (the negative self-test seeds one).

``verify_kernel_grid`` is a reusable harness — tests feed it deliberately
broken grids; the engine lattice below is what ``python -m
tools.stepcheck`` runs.
"""
from __future__ import annotations

import dataclasses
import itertools
from typing import Callable, Iterable, List, Optional, Sequence, Tuple

import numpy as np

from tools.reprolint.framework import Finding


@dataclasses.dataclass(frozen=True)
class ScalarCase:
    """One scalar-prefetch configuration to sweep a grid under."""

    name: str
    args: Tuple[object, ...] = ()


def verify_kernel_grid(kg, cases: Sequence[ScalarCase] = (ScalarCase("-"),),
                       max_findings_per_mapping: int = 3) -> List[Finding]:
    """Evaluate every index map of ``kg`` over the full grid × cases, and
    over the kernel's own loop for a mapping that has one.

    Returns one STEP007 finding per violating (mapping, case), capped at
    ``max_findings_per_mapping`` grid points each (one out-of-bounds
    access is already a proof failure; thousands are noise).
    """
    findings: List[Finding] = []
    for mapping in kg.mappings:
        reported = 0
        extents = kg.grid + mapping.loop
        for case in cases:
            for point in itertools.product(*(range(g) for g in extents)):
                if reported >= max_findings_per_mapping:
                    break
                try:
                    idx = mapping.index_map(*point, *case.args)
                except Exception as exc:  # evaluation itself is a failure
                    findings.append(Finding(
                        path=kg.kernel, line=0, rule="STEP007",
                        symbol=mapping.name,
                        message=(f"index map of `{mapping.name}` raised "
                                 f"at grid point {point} "
                                 f"(case {case.name}): {exc!r}")))
                    reported += 1
                    continue
                problem = _containment_violation(
                    tuple(int(i) for i in idx), mapping.block_shape,
                    mapping.array_shape)
                if problem is not None:
                    findings.append(Finding(
                        path=kg.kernel, line=0, rule="STEP007",
                        symbol=mapping.name,
                        message=(f"`{mapping.name}` block access out of "
                                 f"bounds at grid point {point} "
                                 f"(case {case.name}): {problem}")))
                    reported += 1
    return findings


def _containment_violation(idx: Tuple[int, ...], block: Tuple[int, ...],
                           array: Tuple[int, ...]) -> Optional[str]:
    if len(idx) != len(block) or len(block) != len(array):
        return (f"rank mismatch: index {idx}, block {block}, "
                f"array {array}")
    for d, (i, b, a) in enumerate(zip(idx, block, array)):
        start = i * b
        if start < 0 or start + b > a:
            return (f"dim {d}: block index {i} covers elements "
                    f"[{start}, {start + b}) of an axis of size {a}")
    return None


def grid_exhaustive_points(kg) -> int:
    """Number of points a full sweep visits for the mapping with the most:
    the grid times that mapping's own loop (tests pin this so the lattice
    cannot silently stop being exhaustive)."""
    def points(extents):
        n = 1
        for g in extents:
            n *= g
        return n
    return max((points(kg.grid + m.loop) for m in kg.mappings),
               default=points(kg.grid))


# --------------------------------------------------------------- lattice
def _bt(pages: Sequence[int], width: int, sentinel: int) -> np.ndarray:
    row = np.full((width,), sentinel, np.int32)
    row[:len(pages)] = np.asarray(pages, np.int32)
    return row


def paged_prefill_cases(num_pages: int, page_size: int,
                        pages_per_seq: int, t: int) -> List[ScalarCase]:
    """(block_table, info=(pos0, valid_len)) lattice for the fused
    prefill kernel: cold full chunks, page-straddling resumed chunks,
    single-token ragged tails, sentinel-heavy tables."""
    live = _bt(range(pages_per_seq), pages_per_seq, num_pages)
    partial = _bt([3, 1, 4], pages_per_seq, num_pages)
    one = _bt([7], pages_per_seq, num_pages)
    info = lambda p0, vl: np.asarray([p0, vl], np.int32)  # noqa: E731
    return [
        ScalarCase("cold-full", (live, info(0, t))),
        # resumed chunk starting mid-page: rows straddle a page boundary
        ScalarCase("straddle", (partial, info(page_size + 1,
                                              min(t, page_size)))),
        ScalarCase("ragged-1", (one, info(0, 1))),
        # deep context: pos0 near the table's token capacity
        ScalarCase("deep", (live, info((pages_per_seq - 2) * page_size,
                                       t))),
        # sentinel chase: the clamped horizon itself lands on a sentinel
        # entry — only the num_pages-1 clamp keeps the fetch in-bounds
        ScalarCase("all-sentinel", (_bt([], pages_per_seq, num_pages),
                                    info(0, 1))),
    ]


def paged_attention_cases(num_pages: int, page_size: int,
                          pages_per_seq: int,
                          batch: int) -> List[ScalarCase]:
    """(block_tables, lengths) lattice for flash-decode: ragged lengths
    (incl. an empty slot), sentinel-padded and all-sentinel tables."""
    tables = np.stack([
        _bt(range(pages_per_seq), pages_per_seq, num_pages),   # full
        _bt([5, 2], pages_per_seq, num_pages),                 # short
        _bt([], pages_per_seq, num_pages),                     # empty slot
    ][:batch])
    lengths = np.asarray(
        [pages_per_seq * page_size, page_size + 3, 0][:batch], np.int32)
    return [ScalarCase("ragged", (tables, lengths))]


def tree_shared_cases(num_pages: int, page_size: int, pages_per_seq: int,
                      num_groups: int) -> List[ScalarCase]:
    """(shared_bt, shared_lens) lattice for the tree shared-ancestor
    pass: live groups with ragged shared depths, a zero-span group, and
    a fully sentinel (no fork groups this step) table."""
    live = np.stack([
        _bt(range(3), pages_per_seq, num_pages),       # 3 shared pages
        _bt([9], pages_per_seq, num_pages),            # 1 shared page
        _bt([], pages_per_seq, num_pages),             # unused group
    ][:num_groups])
    lens = np.asarray([3 * page_size, page_size, 0][:num_groups],
                      np.int32)
    empty = np.stack([_bt([], pages_per_seq, num_pages)] * num_groups)
    return [
        ScalarCase("ragged-depths", (live, lens)),
        # all-sentinel, zero spans: every iteration parks on entry 0 and
        # clamps the sentinel — the degenerate no-groups step
        ScalarCase("all-sentinel", (empty,
                                    np.zeros((num_groups,), np.int32))),
    ]


def tree_branch_cases(num_pages: int, page_size: int, pages_per_seq: int,
                      batch: int) -> List[ScalarCase]:
    """(branch_bt, branch_lens) lattice for the tree suffix pass:
    ragged suffixes incl. a row fully covered by the shared pass (span
    0, all-sentinel suffix table)."""
    tables = np.stack([
        _bt(range(pages_per_seq), pages_per_seq, num_pages),   # full
        _bt([11, 6], pages_per_seq, num_pages),                # short
        _bt([], pages_per_seq, num_pages),                     # covered
    ][:batch])
    lens = np.asarray(
        [pages_per_seq * page_size, page_size + 2, 0][:batch], np.int32)
    return [ScalarCase("ragged", (tables, lens))]


def engine_lattice() -> List[Tuple[object, List[ScalarCase]]]:
    """The (KernelGrid, scalar cases) pairs ``python -m tools.stepcheck``
    proves in-bounds: all six kernels, swept over GQA (kv < heads), MQA
    (kv = 1) and MHA (kv = heads) head counts plus block-size variations
    that exercise internal padding and a decode block that does not
    divide the table."""
    from repro.kernels import (flash_prefill_grid, paged_attention_grid,
                               paged_prefill_grid, paged_tree_branch_grid,
                               paged_tree_shared_grid, ssd_scan_grid)

    out: List[Tuple[object, List[ScalarCase]]] = []
    num_pages, page_size, pps = 16, 4, 6
    for kv_heads in (1, 2, 4):          # MQA / GQA / MHA over 4 q heads
        for block_q in (4, 128):        # multi-q-block and single-block
            kg = paged_prefill_grid(8, 4, 8, kv_heads, num_pages,
                                    page_size, pps, block_q=block_q)
            out.append((kg, paged_prefill_cases(num_pages, page_size,
                                                pps, 8)))
        kg = paged_attention_grid(3, 4, 8, kv_heads, num_pages,
                                  page_size, pps)
        out.append((kg, paged_attention_cases(num_pages, page_size,
                                              pps, 3)))
        kg = paged_tree_shared_grid(3, 4, 8, kv_heads, num_pages,
                                    page_size, 3, pps)
        out.append((kg, tree_shared_cases(num_pages, page_size, pps, 3)))
        kg = paged_tree_branch_grid(3, 4, 8, kv_heads, num_pages,
                                    page_size, pps)
        out.append((kg, tree_branch_cases(num_pages, page_size, pps, 3)))
        for s in (12, 16):              # 12 exercises internal padding
            kg = flash_prefill_grid(2, s, 4, 8, kv_heads,
                                    block_q=8, block_k=8)
            out.append((kg, [ScalarCase("-")]))
    # decode blocks of 4 pages of 128 tokens over a 10-entry table: the
    # last block reaches past the table and re-reads its last entry
    kg = paged_attention_grid(3, 4, 8, 2, num_pages, 128, 10)
    out.append((kg, paged_attention_cases(num_pages, 128, 10, 3)))
    out.append((ssd_scan_grid(2, 16, 2, 8, 4, 8), [ScalarCase("-")]))
    return out


def run_bounds_lattice() -> List[Finding]:
    """STEP007 over the full engine lattice."""
    findings: List[Finding] = []
    for kg, cases in engine_lattice():
        findings.extend(verify_kernel_grid(kg, cases))
    return findings
