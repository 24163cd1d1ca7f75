"""Decode-attention block sweep and poisoned-tail check on one TPU chip.

    PYTHONPATH=src python -m benchmarks.decode_block_sweep
    PYTHONPATH=src python -m benchmarks.decode_block_sweep --small  # CPU

Runs ``paged_attention_decode`` at the shapes of the two served benchmark
configurations (qwen2-0.5b: 64 slots, 14 query heads over 2 KV heads,
head_dim 64; r1-distill-14b: 32 slots, 40 over 8, head_dim 128; bf16
pages of 16 tokens, 320-entry block tables), rows holding a seeded spread
of context lengths on distinct pages, sentinel-padded tables and one
empty row. For each configuration it

  * checks the kernel against ``paged_attention_decode_ref`` (to
    ``REF_RTOL``), and that
    filling every token past a row's length with NaN (the rest of its last
    page, its unused pages and the clamped sentinel page) leaves the output
    bit-for-bit as it was, finite, and zero on the empty row;
  * times the kernel at several ``pages_per_block`` values, by setting the
    module's ``BLOCK_TOKENS`` (the one constant of the block rule) for that
    variant, and at the rule as it stands.

Prints one JSON object per configuration; exits non-zero if a check fails.
``--small`` runs tiny shapes in interpret mode, to check the script on the
CPU; its times mean nothing.
"""
from __future__ import annotations

import argparse
import json
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.paged_attention import paged_attention as pa
from repro.kernels.paged_attention.ref import paged_attention_decode_ref

# (batch, q_heads, kv_heads, head_dim, num_pages, mean context tokens);
# the mean is near what each cell's kv_pages_used reads while it serves,
# as far as rows on distinct pages fit the pool (r1's cell shares pages)
CONFIGS = {
    "qwen2-0.5b": (64, 14, 2, 64, 10240, 280),
    "r1-distill-14b": (32, 40, 8, 128, 3072, 1200),
}
SMALL = {"small": (4, 4, 2, 32, 64, 40)}
PAGE, TABLE, SMALL_TABLE = 16, 320, 8
SWEEP = (8, 16, 24, 32, 48, 64)        # pages per block
ITERS = 200
# |kernel - reference| allowed, as a share of the largest |reference|:
# both round their output to bf16 (8 significant bits), so a few units in
# the last place of the largest value; a misread page is off by O(1)
REF_RTOL = 2 ** -5


def make_inputs(seed, batch, q_heads, kv_heads, head_dim, num_pages, mean,
                table):
    """q, K/V pools, tables, lengths and each pool token's liveness."""
    rng = np.random.default_rng(seed)
    lengths = rng.integers(1, 2 * mean, size=batch).clip(1, table * PAGE)
    lengths[-1] = 0                                    # an empty slot
    need = -(-lengths // PAGE)
    assert need.sum() < num_pages - 1, "pool too small for the lengths"
    pages = rng.permutation(num_pages - 1)             # never the last
    tables = np.full((batch, table), num_pages, np.int32)  # sentinels
    live = np.zeros((num_pages, PAGE), bool)
    at = 0
    for b, n in enumerate(need):
        own = pages[at:at + n]
        at += n
        tables[b, :n] = own
        for i, p in enumerate(own):
            live[p, :max(min(lengths[b] - i * PAGE, PAGE), 0)] = True
    shape = (kv_heads, num_pages, PAGE, head_dim)
    k = jnp.asarray(rng.standard_normal(shape), jnp.bfloat16)
    v = jnp.asarray(rng.standard_normal(shape), jnp.bfloat16)
    q = jnp.asarray(rng.standard_normal((batch, q_heads, head_dim)),
                    jnp.bfloat16)
    return (q, k, v, jnp.asarray(tables), jnp.asarray(lengths, jnp.int32),
            jnp.asarray(live))


def kernel(interpret):
    return jax.jit(lambda q, k, v, bt, ln: pa.paged_attention_decode(
        q, k, v, bt, ln, interpret=interpret))


def timed(interpret, args, iters):
    """ms per kernel call, over ``iters`` calls chained in one program
    (each call's output is the next one's q), so that the host's dispatch
    is not timed."""
    def chain(q, k, v, bt, ln):
        return jax.lax.fori_loop(0, iters, lambda i, q: (
            pa.paged_attention_decode(q, k, v, bt, ln,
                                      interpret=interpret)), q)
    fn = jax.jit(chain)
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    jax.block_until_ready(fn(*args))
    return (time.perf_counter() - t0) / iters * 1e3


def run(name, shape, table, sweep, interpret, iters):
    q, k, v, bt, ln, live = make_inputs(0, *shape, table)
    args = (q, k, v, bt, ln)
    out = np.asarray(kernel(interpret)(*args), np.float32)
    ref = np.asarray(paged_attention_decode_ref(*args), np.float32)
    dead = ~live[None, :, :, None]
    poisoned = np.asarray(kernel(interpret)(
        q, jnp.where(dead, jnp.nan, k), jnp.where(dead, jnp.nan, v), bt, ln),
        np.float32)
    row = {"config": name, "device": jax.devices()[0].device_kind,
           "live_tokens": int(ln.sum()),
           # the reference has no answer for the empty row
           "max_abs_vs_ref": float(np.abs(out - ref)[:-1].max()),
           "max_abs_ref": float(np.abs(ref)[:-1].max()),
           "poisoned_equal": bool(np.array_equal(out, poisoned)),
           "finite": bool(np.isfinite(poisoned).all()),
           "empty_row_zero": bool((out[-1] == 0).all())}
    row["rule_ms"] = timed(interpret, args, iters)
    default = pa.BLOCK_TOKENS
    try:
        for ppb in sweep:
            pa.BLOCK_TOKENS = ppb * PAGE
            row[f"ppb{ppb}_ms"] = timed(interpret, args, iters)
    finally:
        pa.BLOCK_TOKENS = default
    ok = (row["max_abs_vs_ref"] <= REF_RTOL * row["max_abs_ref"]
          and row["poisoned_equal"] and row["finite"]
          and row["empty_row_zero"])
    return row, ok


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--small", action="store_true",
                    help="tiny shapes in interpret mode (CPU)")
    args = ap.parse_args(argv)
    configs, table, sweep, iters = ((SMALL, SMALL_TABLE, (2,), 2)
                                    if args.small else
                                    (CONFIGS, TABLE, SWEEP, ITERS))
    ok = True
    for name, shape in configs.items():
        row, good = run(name, shape, table, sweep, args.small, iters)
        ok &= good
        print(json.dumps(row), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
