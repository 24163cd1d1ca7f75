"""Pallas TPU flash-decode attention over a paged KV cache.

This is the TPU-native re-think of vLLM's PagedAttention CUDA kernel, the
compute hot-spot of SART's decode phase (the paper's serving substrate):

  * KV pages live in HBM as ``[kv_heads, num_pages, page_size, head_dim]``;
    the per-branch block table indexes them. Sibling branches of one request
    share prefix pages (ref-counted by ``repro.kv``) — the kernel is
    oblivious: shared pages are simply referenced by several block tables.
  * Grid = (batch, kv_head, pages_per_seq). The page axis is the minor,
    sequential grid dimension; an online-softmax (m, l, acc) accumulator in
    VMEM scratch merges pages flash-decode style, so a 500k-token context
    never materializes a full attention row.
  * Block tables and context lengths are scalar-prefetched
    (``PrefetchScalarGridSpec``) so the page index_map can consume them —
    the TPU analogue of the CUDA kernel's pointer chasing.
  * Layout: the kernel sees q and out as ``[batch, kv_heads, group,
    head_dim]``, one block per (batch, kv_head) GQA group. The group is then
    the whole second-last block dim, which Mosaic accepts at any group size;
    a ``(1, group, head_dim)`` block over ``[batch, q_heads, head_dim]`` is
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


def paged_attention_grid(
    batch: int,
    q_heads: int,
    head_dim: int,
    kv_heads: int,
    num_pages: int,
    page_size: int,
    pages_per_seq: int,
) -> KernelGrid:
    """Launch geometry for :func:`paged_attention_decode`.

    Scalar-prefetch operands: ``bt`` — [batch, pages_per_seq] int32 block
    tables, ``ln`` — [batch] int32 context lengths. q and out are
    [batch, kv_heads, group, head_dim]; a block is one (batch, kv_head) GQA
    group.
    """
    assert q_heads % kv_heads == 0, (q_heads, kv_heads)
    group = q_heads // kv_heads

    def q_index(b, h, i, bt, ln):
        return (b, h, 0, 0)

    def kv_index(b, h, i, bt, ln):
        # sentinel block-table entries (the engine pads tables with
        # num_pages) are clamped into range: their pages sit past
        # `lengths`, so the length mask discards whatever the clamped
        # fetch returns — without the clamp the index map would address
        # HBM out of bounds on TPU
        return (h, jnp.minimum(bt[b, i], num_pages - 1), 0, 0)

    q_map = BlockMapping("q", (batch, kv_heads, group, head_dim),
                         (1, 1, group, head_dim), q_index)
    kv_shape = (kv_heads, num_pages, page_size, head_dim)
    kv_block = (1, 1, page_size, head_dim)
    return KernelGrid(
        kernel="paged_attention",
        grid=(batch, kv_heads, pages_per_seq),
        in_mappings=(
            q_map,
            BlockMapping("k_pages", kv_shape, kv_block, kv_index),
            BlockMapping("v_pages", kv_shape, kv_block, kv_index),
        ),
        out_mappings=(dataclasses.replace(q_map, name="out"),),
        num_scalar_prefetch=2,
    )


def _decode_kernel(
    # scalar-prefetch refs
    block_tables_ref,    # [B, pages_per_seq] int32
    lengths_ref,         # [B] int32
    # inputs
    q_ref,               # [1, 1, group, head_dim]
    k_ref,               # [1, 1, page_size, head_dim]
    v_ref,               # [1, 1, page_size, head_dim]
    # outputs
    out_ref,             # [1, 1, group, head_dim]
    # scratch
    m_ref,               # [group, 1] f32
    l_ref,               # [group, 1] f32
    acc_ref,             # [group, head_dim] f32
    *,
    page_size: int,
    scale: float,
):
    b = pl.program_id(0)
    page_idx = pl.program_id(2)
    num_pages = pl.num_programs(2)
    length = lengths_ref[b]

    @pl.when(page_idx == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    start = page_idx * page_size

    @pl.when(start < length)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32) * scale       # [G, hd]
        k = k_ref[0, 0].astype(jnp.float32)                # [P, hd]
        v = v_ref[0, 0].astype(jnp.float32)                # [P, hd]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))  # [G, P]
        pos = start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(pos < length, s, NEG_INF)

        m_prev = m_ref[...]                                # [G, 1]
        m_cur = jnp.max(s, axis=-1, keepdims=True)         # [G, 1]
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)                             # [G, P]
        alpha = jnp.exp(m_prev - m_new)                    # [G, 1]
        l_new = alpha * l_ref[...] + jnp.sum(p, -1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())))
        m_ref[...] = m_new
        l_ref[...] = l_new

    @pl.when(page_idx == num_pages - 1)
    def _finalize():
        denom = jnp.maximum(l_ref[...], 1e-30)
        out_ref[0, 0] = (acc_ref[...] / denom).astype(out_ref.dtype)


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
    scale = 1.0 / (head_dim ** 0.5)

    kg = paged_attention_grid(batch, q_heads, head_dim, kv_heads,
                              num_pages, page_size, pages_per_seq)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=kg.num_scalar_prefetch,
        grid=kg.grid,
        in_specs=block_specs(kg.in_mappings),
        out_specs=block_specs(kg.out_mappings)[0],
        scratch_shapes=[
            pltpu.VMEM((group, 1), jnp.float32),
            pltpu.VMEM((group, 1), jnp.float32),
            pltpu.VMEM((group, head_dim), jnp.float32),
        ],
    )

    kernel = pl.pallas_call(
        functools.partial(_decode_kernel, page_size=page_size, scale=scale),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(
            kg.out_mappings[0].array_shape, q.dtype),
        interpret=interpret,
    )
    out = kernel(block_tables.astype(jnp.int32), lengths.astype(jnp.int32),
                 q.reshape(batch, kv_heads, group, head_dim), k_pages,
                 v_pages)
    return out.reshape(batch, q_heads, head_dim)
