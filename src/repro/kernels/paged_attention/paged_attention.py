"""Pallas TPU flash-decode attention over a paged KV cache.

This is the TPU-native re-think of vLLM's PagedAttention CUDA kernel, the
compute hot-spot of SART's decode phase (the paper's serving substrate):

  * KV pages live in HBM as ``[kv_heads, num_pages, page_size, head_dim]``;
    the per-branch block table indexes them. Sibling branches of one request
    share prefix pages (ref-counted by ``repro.kv``) — the kernel is
    oblivious: shared pages are simply referenced by several block tables.
  * Grid = (batch,): one grid step per row, all of its kv heads. Inside, a
    loop walks only the row's own pages, ``pages_per_block`` table entries
    per compute block, up to the block holding the row's last token. K and
    V stay in HBM; each block's pages arrive by async copies (one per page
    and operand, every kv head at once) into a double-buffered VMEM
    scratch, block j+1's copies in flight while block j is computed. On
    the TPU a head_dim under 128 is copied as its whole 128-lane tile rows
    (the pool's layout in HBM) and the pad lanes dropped in VMEM. An
    online-softmax (m, l, acc) accumulator per kv head in VMEM scratch
    merges blocks flash-decode style, so a 500k-token context never
    materializes a full attention row.
  * Block tables and context lengths are scalar-prefetched
    (``PrefetchScalarGridSpec``): the copies chase pages through them, the
    TPU analogue of the CUDA kernel's pointer chasing. One function names
    the page every copy reads; the launch geometry
    (``paged_attention_grid``) exposes it as the K/V mappings.
  * The last block may reach past the row's length, and past the table
    when the block does not divide its width: those entries are clamped
    into range, their positions masked out of the scores, and their V rows
    zeroed, so a stale or non-finite page cannot reach the output.
  * Layout: the kernel sees q and out as ``[batch, kv_heads, group,
    head_dim]``, one block per row. The group is then the whole
    second-last block dim, which Mosaic accepts at any group size; a
    ``(1, group, head_dim)`` block over ``[batch, q_heads, head_dim]`` is
    refused unless the group is a multiple of 8 (qwen2-0.5b's is 7). The
    wrapper keeps the caller's ``[batch, q_heads, head_dim]`` contract.

Validated in ``interpret=True`` mode on CPU against ``ref.py``.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..introspect import BlockMapping, KernelGrid, block_specs

NEG_INF = -1e30
BLOCK_TOKENS = 512          # tokens one compute block covers (PERF.md)
BUFFER_BYTES = 8 << 20      # VMEM the double-buffered K and V blocks may take


def copy_lanes(head_dim: int, interpret: bool = False) -> int:
    """Lanes a page copy moves per token. On the TPU, Mosaic lays a
    kernel's HBM operand out in (8, 128) tiles, so a head_dim under 128
    sits in a 128-lane tile row whose pad lanes the copy moves too: Mosaic
    (JAX 0.9.0) refuses a DMA slice narrower than the tile. Interpret mode
    has no tiles and copies head_dim."""
    return head_dim if interpret else pl.cdiv(head_dim, 128) * 128


def pages_per_block(page_size: int, head_dim: int, kv_heads: int,
                    itemsize: int, pages_per_seq: int) -> int:
    """Table entries one compute block covers: ``BLOCK_TOKENS`` tokens,
    fewer where K's and V's double buffers over every kv head would pass
    ``BUFFER_BYTES``, never more than the table holds. Pages of 16 tokens
    give 32 for qwen2-0.5b and r1-distill-14b alike."""
    page_bytes = kv_heads * page_size * copy_lanes(head_dim) * itemsize
    fit = BUFFER_BYTES // (4 * page_bytes)
    return max(min(BLOCK_TOKENS // page_size, fit, pages_per_seq), 1)


def block_walk(page_size: int, head_dim: int, kv_heads: int, itemsize: int,
               pages_per_seq: int) -> tuple:
    """``(block tokens, table blocks)``: the tokens one compute block
    covers and the blocks a whole table holds."""
    ppb = pages_per_block(page_size, head_dim, kv_heads, itemsize,
                          pages_per_seq)
    return ppb * page_size, pl.cdiv(pages_per_seq, ppb)


def blocks_run(lengths, block_tokens: int, table_blocks: int):
    """Compute blocks the kernel runs for rows attending ``lengths``
    tokens: none at a length of 0 or less, never more than the table
    holds. ``lengths`` is a numpy array on the host or the kernel's traced
    scalar, so the host's count and the kernel's loop share this rule."""
    return ((lengths + block_tokens - 1) // block_tokens).clip(
        0, table_blocks)


def paged_attention_grid(
    batch: int,
    q_heads: int,
    head_dim: int,
    kv_heads: int,
    num_pages: int,
    page_size: int,
    pages_per_seq: int,
    itemsize: int = 2,
    interpret: bool = False,
) -> KernelGrid:
    """Launch geometry for :func:`paged_attention_decode`.

    Scalar-prefetch operands: ``bt`` — [batch, pages_per_seq] int32 block
    tables, ``ln`` — [batch] int32 context lengths. q and out are
    [batch, kv_heads, group, head_dim]; a block is one row's heads. K and V
    are read by the kernel's own loop over table entries ``i``, a page of
    every kv head per copy, ``pages_per_block`` entries per compute block:
    their mappings' loop spans every entry a row's last block can reach.
    Their extent and block are the pool as the copies see it, each token
    :func:`copy_lanes` wide: on the TPU the pool's tile-padded layout.
    """
    assert q_heads % kv_heads == 0, (q_heads, kv_heads)
    group = q_heads // kv_heads
    block, table_blocks = block_walk(page_size, head_dim, kv_heads,
                                     itemsize, pages_per_seq)
    walk = table_blocks * (block // page_size)
    lanes = copy_lanes(head_dim, interpret)

    def q_index(b, bt, ln):
        return (b, 0, 0, 0)

    def page_index(b, i, bt, ln):
        # a last block reaching past the table re-reads its last entry;
        # sentinel entries (the engine pads tables with num_pages) are
        # clamped into range. Both sit past `lengths`, so the masks
        # discard whatever the clamped copy returns — without the clamps
        # the copy would address HBM out of bounds on TPU
        entry = bt[b, jnp.minimum(i, pages_per_seq - 1)]
        return (0, jnp.minimum(entry, num_pages - 1), 0, 0)

    q_map = BlockMapping("q", (batch, kv_heads, group, head_dim),
                         (1, kv_heads, group, head_dim), q_index)
    kv_shape = (kv_heads, num_pages, page_size, lanes)
    kv_block = (kv_heads, 1, page_size, lanes)
    return KernelGrid(
        kernel="paged_attention",
        grid=(batch,),
        in_mappings=(
            q_map,
            BlockMapping("k_pages", kv_shape, kv_block, page_index, (walk,)),
            BlockMapping("v_pages", kv_shape, kv_block, page_index, (walk,)),
        ),
        out_mappings=(dataclasses.replace(q_map, name="out"),),
        num_scalar_prefetch=2,
    )


def _decode_kernel(
    # scalar-prefetch refs
    block_tables_ref,    # [B, pages_per_seq] int32
    lengths_ref,         # [B] int32
    # inputs
    q_ref,               # [1, kv_heads, group, head_dim]
    k_hbm,               # [kv_heads, num_pages, page_size, head_dim] (HBM)
    v_hbm,               # [kv_heads, num_pages, page_size, head_dim] (HBM)
    # outputs
    out_ref,             # [1, kv_heads, group, head_dim]
    # scratch
    k_buf,               # [2, ppb, kv_heads, page_size, lanes >= head_dim]
    v_buf,               # [2, ppb, kv_heads, page_size, lanes >= head_dim]
    sems,                # DMA semaphores [2 (k, v), 2 (buffer)]
    m_ref,               # [kv_heads, group, 1] f32
    l_ref,               # [kv_heads, group, 1] f32
    acc_ref,             # [kv_heads, group, head_dim] f32
    *,
    page_index,
    walk: tuple,
    scale: float,
):
    b = pl.program_id(0)
    length = lengths_ref[b]
    _, ppb, kv_heads, page_size, lanes = k_buf.shape
    head_dim = acc_ref.shape[-1]
    block = ppb * page_size
    num_blocks = blocks_run(length, *walk)

    def copies(j, slot):
        """The async copies of block ``j``'s pages into buffer ``slot``:
        K's, then V's."""
        out = []
        for kind, (hbm, buf) in enumerate(((k_hbm, k_buf), (v_hbm, v_buf))):
            for p in range(ppb):
                _, page, _, _ = page_index(b, j * ppb + p, block_tables_ref,
                                           lengths_ref)
                out.append(pltpu.make_async_copy(
                    hbm.at[:, page, :, pl.ds(0, lanes)], buf.at[slot, p],
                    sems.at[kind, slot]))
        return out

    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(num_blocks > 0)
    def _first():
        for c in copies(0, 0):
            c.start()

    def body(j, carry):
        slot = j % 2

        @pl.when(j + 1 < num_blocks)
        def _prefetch():
            for c in copies(j + 1, 1 - slot):
                c.start()

        for c in copies(j, slot):
            c.wait()
        for h in range(kv_heads):
            q = q_ref[0, h].astype(jnp.float32) * scale        # [G, hd]
            k = k_buf[slot, :, h].reshape(block, lanes)[:, :head_dim]
            v = v_buf[slot, :, h].reshape(block, lanes)[:, :head_dim]
            k, v = k.astype(jnp.float32), v.astype(jnp.float32)  # [T, hd]
            s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))  # [G, T]
            pos = j * block + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(pos < length, s, NEG_INF)
            # zero V past the length too: 0 x NaN would reach the output
            v_pos = j * block + jax.lax.broadcasted_iota(jnp.int32, v.shape,
                                                         0)
            v = jnp.where(v_pos < length, v, 0.0)

            m_prev = m_ref[h]                                  # [G, 1]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            p = jnp.exp(s - m_new)                             # [G, T]
            alpha = jnp.exp(m_prev - m_new)                    # [G, 1]
            l_ref[h] = alpha * l_ref[h] + jnp.sum(p, -1, keepdims=True)
            acc_ref[h] = acc_ref[h] * alpha + jax.lax.dot_general(
                p, v, (((1,), (0,)), ((), ())))
            m_ref[h] = m_new
        return carry

    jax.lax.fori_loop(0, num_blocks, body, 0)
    denom = jnp.maximum(l_ref[...], 1e-30)
    out_ref[0] = (acc_ref[...] / denom).astype(out_ref.dtype)


def paged_attention_decode(
    q: jax.Array,             # [B, q_heads, head_dim]
    k_pages: jax.Array,       # [kv_heads, num_pages, page_size, head_dim]
    v_pages: jax.Array,       # [kv_heads, num_pages, page_size, head_dim]
    block_tables: jax.Array,  # [B, pages_per_seq] int32
    lengths: jax.Array,       # [B] int32 (valid tokens per sequence)
    *,
    interpret: bool = False,
) -> jax.Array:
    """Flash-decode over paged KV. Returns [B, q_heads, head_dim]."""
    batch, q_heads, head_dim = q.shape
    kv_heads, num_pages, page_size, _ = k_pages.shape
    group = q_heads // kv_heads
    pages_per_seq = block_tables.shape[1]
    itemsize = k_pages.dtype.itemsize

    kg = paged_attention_grid(batch, q_heads, head_dim, kv_heads,
                              num_pages, page_size, pages_per_seq, itemsize,
                              interpret)
    # each copy moves one page of every kv head, as the K mapping's block
    # says: on the TPU whole tile rows, whose pad lanes the kernel drops
    lanes = kg.in_mappings[1].block_shape[-1]
    walk = block_walk(page_size, head_dim, kv_heads, itemsize, pages_per_seq)
    ppb = walk[0] // page_size
    buf = pltpu.VMEM((2, ppb, kv_heads, page_size, lanes), k_pages.dtype)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=kg.num_scalar_prefetch,
        grid=kg.grid,
        in_specs=block_specs(kg.in_mappings),
        out_specs=block_specs(kg.out_mappings)[0],
        scratch_shapes=[
            buf, buf,
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.VMEM((kv_heads, group, 1), jnp.float32),
            pltpu.VMEM((kv_heads, group, 1), jnp.float32),
            pltpu.VMEM((kv_heads, group, head_dim), jnp.float32),
        ],
    )

    kernel = pl.pallas_call(
        functools.partial(_decode_kernel,
                          page_index=kg.in_mappings[1].index_map,
                          walk=walk,
                          scale=1.0 / (head_dim ** 0.5)),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(
            kg.out_mappings[0].array_shape, q.dtype),
        interpret=interpret,
        name="paged_attention",
    )
    out = kernel(block_tables.astype(jnp.int32), lengths.astype(jnp.int32),
                 q.reshape(batch, kv_heads, group, head_dim), k_pages,
                 v_pages)
    return out.reshape(batch, q_heads, head_dim)
