"""Grid/BlockSpec introspection surface for the Pallas kernels.

Every kernel in this package describes its launch geometry — the grid, and
per-operand (array shape, block shape, index map) triples — as data before
lowering it to ``pl.pallas_call``. The kernel builds its ``BlockSpec``s
*from* this description (``block_specs``), and static analysis consumes the
same description (``tools/stepcheck`` evaluates every index map over the
full grid and proves each block access in-bounds). One source of truth:
the geometry the analyzer checks is the geometry the kernel launches.

The index maps stored here are the exact callables handed to Pallas. For a
kernel using ``PrefetchScalarGridSpec`` they take ``(*grid_indices,
*scalar_prefetch_refs)``; evaluating them with concrete integers and numpy
arrays (as stepcheck does) exercises the same arithmetic — including the
OOB-sentinel clamps — that runs on device. An operand the kernel fetches
itself (it stays in HBM and the kernel's own loop issues async copies)
declares that loop's extents as ``loop``; its index map takes the loop
indices after the grid's and is the function the kernel's copies call.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, List, Tuple

from jax.experimental import pallas as pl

IndexMap = Callable[..., Tuple[Any, ...]]


@dataclasses.dataclass(frozen=True)
class BlockMapping:
    """One operand's blocking: full array shape, block shape, index map.

    ``index_map`` returns *block* indices: element range covered along
    dim d is ``idx[d] * block_shape[d] : (idx[d] + 1) * block_shape[d]``,
    which the bounds verifier checks against ``array_shape[d]``.

    ``loop`` is empty for a Pallas-pipelined operand. Non-empty, the
    operand stays in HBM (``memory_space=pl.ANY``) and the kernel copies
    block ``index_map(*grid_indices, *loop_indices, *scalar_refs)`` for
    every point of its own loop over ``loop``'s extents.
    """

    name: str
    array_shape: Tuple[int, ...]
    block_shape: Tuple[int, ...]
    index_map: IndexMap
    loop: Tuple[int, ...] = ()


@dataclasses.dataclass(frozen=True)
class KernelGrid:
    """A kernel's full launch geometry, as data.

    ``grid`` iterates row-major with the last axis minor/sequential (the
    Pallas TPU convention all kernels here rely on for VMEM-carried
    accumulators). ``num_scalar_prefetch`` scalar operands are passed to
    every index map after the grid indices. ``in_mappings`` follow the
    kernel's operand order; ``out_mappings`` the result order.
    """

    kernel: str
    grid: Tuple[int, ...]
    in_mappings: Tuple[BlockMapping, ...]
    out_mappings: Tuple[BlockMapping, ...]
    num_scalar_prefetch: int = 0

    @property
    def mappings(self) -> Tuple[BlockMapping, ...]:
        """All mappings, inputs then outputs."""
        return self.in_mappings + self.out_mappings


def block_specs(mappings: Tuple[BlockMapping, ...]) -> List[pl.BlockSpec]:
    """Materialize ``pl.BlockSpec``s from mapping descriptors.

    This is the only path from a :class:`KernelGrid` to Pallas — kernels
    must not hand-build specs next to it, or the analyzed geometry and the
    launched geometry can drift. An operand with a ``loop`` stays in HBM.
    """
    return [pl.BlockSpec(memory_space=pl.ANY) if m.loop
            else pl.BlockSpec(m.block_shape, m.index_map) for m in mappings]
