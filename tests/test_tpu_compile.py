"""Compile the serving path's Pallas kernels for a described TPU v5e chip.

Interpret mode (``tests/test_kernels.py``) cannot see Mosaic's tiling
rules, VMEM limits or device memory; the TPU compiler installed with JAX
can, for a chip that is described and not attached. Each test compiles one
kernel at a registry model's published head widths (bf16 KV pages of 16
tokens) and asserts the compiled program holds the kernel as a
``tpu_custom_call``. Kernels the compiler refuses today are strict xfails
carrying its message, so a fix shows up as an XPASS failure.

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and every test worker imports this file.
"""
import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels.flash_prefill.paged_prefill import paged_flash_prefill_fwd
from repro.kernels.paged_attention.paged_attention import \
    paged_attention_decode
from repro.kernels.paged_attention.tree_decode import paged_tree_attention_fwd
from repro.kernels.ssd_scan.ssd_scan import ssd_scan

BATCH, NUM_PAGES, PAGE_SIZE, PAGES_PER_SEQ, CHUNK = 16, 4096, 16, 32, 64
SERVED = ("qwen2-0.5b", "r1-distill-14b")


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")   # else the compiler logs to /tmp
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # no TPU compiler here: nothing to compile
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # a described-device executable can be written to the persistent
        # cache but never read back without a chip
        enabled = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        yield SingleDeviceSharding(topo.devices[0])
        jax.config.update("jax_enable_compilation_cache", enabled)
        compilation_cache.reset_cache()


def _compile_custom_calls(fn, *args) -> int:
    compiled = jax.jit(fn).lower(*args).compile()
    return compiled.as_text().count('custom_call_target="tpu_custom_call"')


def _heads(name):
    cfg = get_config(name)
    return cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim


def _paged_args(one_chip, name):
    qh, kvh, hd = _heads(name)
    sds = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    pages = sds((kvh, NUM_PAGES, PAGE_SIZE, hd), jnp.bfloat16)
    return sds, qh, hd, pages


@pytest.mark.parametrize("name", SERVED)
def test_paged_attention_compiles(one_chip, name):
    sds, qh, hd, pages = _paged_args(one_chip, name)
    fn = functools.partial(paged_attention_decode, interpret=False)
    assert _compile_custom_calls(
        fn, sds((BATCH, qh, hd), jnp.bfloat16), pages, pages,
        sds((BATCH, PAGES_PER_SEQ), jnp.int32),
        sds((BATCH,), jnp.int32)) == 1


@pytest.mark.parametrize("name", SERVED)
def test_paged_flash_prefill_compiles(one_chip, name):
    sds, qh, hd, pages = _paged_args(one_chip, name)
    fn = functools.partial(paged_flash_prefill_fwd, block_q=CHUNK,
                           interpret=False)
    assert _compile_custom_calls(
        fn, sds((CHUNK, qh, hd), jnp.bfloat16), pages, pages,
        sds((PAGES_PER_SEQ,), jnp.int32), sds((), jnp.int32),
        sds((), jnp.int32)) == 1


@pytest.mark.xfail(strict=True, raises=ValueError, reason=(
    "Pallas TPU lowering: the branch pass's q block (1, group, head_dim) "
    "over [batch, q_heads, head_dim] needs a group divisible by 8; with a "
    "[batch, kv, group, head_dim] layout the shared pass's membership "
    "mask reshape then fails in Mosaic (infer-vector-layout: unsupported "
    "shape cast)"))
@pytest.mark.parametrize("name", SERVED)
def test_tree_decode_pair_compiles(one_chip, name):
    sds, qh, hd, pages = _paged_args(one_chip, name)
    row = sds((BATCH,), jnp.int32)
    table = sds((BATCH, PAGES_PER_SEQ), jnp.int32)
    fn = functools.partial(paged_tree_attention_fwd, interpret=False)
    assert _compile_custom_calls(
        fn, sds((BATCH, qh, hd), jnp.bfloat16), pages, pages, row, table,
        row, table, row) == 2


@pytest.mark.xfail(strict=True, raises=ValueError, reason=(
    "Pallas TPU lowering: the x block (1, chunk, 1, head_dim) over "
    "[batch, seq, heads, head_dim] has a second-last dim of 1, neither "
    "divisible by 8 nor the whole heads dim"))
def test_ssd_scan_compiles(one_chip):
    cfg = get_config("mamba2-130m")
    h, p, n, q = cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state, cfg.ssm_chunk
    sds = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    seq = 2 * q
    fn = functools.partial(ssd_scan, chunk=q, interpret=False)
    assert _compile_custom_calls(
        fn, sds((1, seq, h, p), jnp.float32), sds((1, seq, h), jnp.float32),
        sds((h,), jnp.float32), sds((1, seq, h, n), jnp.float32),
        sds((1, seq, h, n), jnp.float32)) == 1
