"""Pallas kernel validation: shape/dtype sweeps vs the pure-jnp oracles
(interpret mode on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.paged_attention.ops import paged_attention
from repro.kernels.paged_attention.ref import paged_attention_decode_ref
from repro.kernels.ssd_scan.ops import ssd
from repro.kernels.ssd_scan.ref import ssd_scan_ref
from repro.models.mamba2 import ssd_chunked


def _decode_tables(rng, lens, ps, pps, npages):
    """Sentinel-padded block tables (entry ``npages`` past each row's
    pages) over distinct pages below ``npages - 1``, so the page the
    sentinel clamps to is nobody's; row 4 shares row 5's first two
    pages, as siblings share a prompt."""
    bt = np.full((len(lens), pps), npages, np.int32)
    free = iter(rng.permutation(npages - 1))
    for row, n in enumerate(lens):
        for i in range(-(-n // ps)):
            bt[row, i] = next(free)
    bt[4, :2] = bt[5, :2]
    return bt


# the last two cases are qwen2-0.5b's GQA (group 7, head_dim 64) and
# r1-distill-14b's (group 5, head_dim 128), where a compute block is 512
# tokens, 32 pages of 16. Lengths of 1, one page, one past a
# page, one block, one past a block, the full table (whose width the block
# does not divide), and an empty slot, over sentinel-padded tables
@pytest.mark.parametrize("b,qh,kvh,hd,ps,pps,lens", [
    (2, 4, 2, 64, 8, 4, None),
    (3, 8, 8, 128, 16, 3, None),
    (1, 8, 1, 256, 8, 5, None),
    (4, 2, 2, 32, 4, 8, None),
    (7, 14, 2, 64, 16, 40, (1, 16, 17, 512, 513, 640, 0)),
    (7, 10, 2, 128, 16, 36, (1, 16, 17, 512, 513, 576, 0)),
])
def test_paged_attention_shapes(rng, b, qh, kvh, hd, ps, pps, lens):
    npages = b * pps + 2
    q = jnp.asarray(rng.normal(size=(b, qh, hd)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(kvh, npages, ps, hd)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(kvh, npages, ps, hd)), jnp.float32)
    if lens is None:
        bt = jnp.asarray(rng.integers(0, npages, size=(b, pps)), jnp.int32)
        lens = rng.integers(1, pps * ps + 1, size=(b,))
    else:
        bt = jnp.asarray(_decode_tables(rng, lens, ps, pps, npages))
    lens = jnp.asarray(lens, jnp.int32)
    out = paged_attention(q, k, v, bt, lens)
    ref = paged_attention_decode_ref(q, k, v, bt, lens)
    live = np.asarray(lens) > 0
    np.testing.assert_allclose(out[live], ref[live], atol=2e-4)
    # an empty slot runs no block and writes zeros
    np.testing.assert_array_equal(out[~live], 0.0)


def test_paged_attention_ignores_poisoned_tail(rng):
    """NaN in every pool slot past every row's length — the rest of a
    row's last page, the pages that share its last compute block, and
    the page sentinel entries clamp to — must not reach any output: the
    output equals the reference's over the clean pool."""
    b, qh, kvh, hd, ps, pps = 7, 14, 2, 64, 16, 40
    lens = (1, 17, 300, 512, 513, 630, 0)
    npages = b * pps + 2
    q = jnp.asarray(rng.normal(size=(b, qh, hd)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(kvh, npages, ps, hd)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(kvh, npages, ps, hd)), jnp.float32)
    bt = _decode_tables(rng, lens, ps, pps, npages)
    live = np.zeros((npages, ps), bool)
    for row, n in enumerate(lens):
        for t in range(n):
            live[bt[row, t // ps], t % ps] = True
    assert not live[npages - 1].any()
    dead = jnp.asarray(~live)[None, :, :, None]
    bt, lens = jnp.asarray(bt), jnp.asarray(lens, jnp.int32)
    out = paged_attention(q, jnp.where(dead, jnp.nan, k),
                          jnp.where(dead, jnp.nan, v), bt, lens)
    ref = paged_attention_decode_ref(q, k, v, bt, lens)
    assert np.isfinite(np.asarray(out)).all()
    np.testing.assert_allclose(out[:-1], ref[:-1], atol=2e-4)
    np.testing.assert_array_equal(out[-1], 0.0)


@pytest.mark.parametrize("dtype,atol", [(jnp.float32, 2e-4),
                                        (jnp.bfloat16, 5e-2)])
def test_paged_attention_dtypes(rng, dtype, atol):
    b, qh, kvh, hd, ps, pps = 2, 4, 2, 64, 8, 4
    npages = 16
    q = jnp.asarray(rng.normal(size=(b, qh, hd))).astype(dtype)
    k = jnp.asarray(rng.normal(size=(kvh, npages, ps, hd))).astype(dtype)
    v = jnp.asarray(rng.normal(size=(kvh, npages, ps, hd))).astype(dtype)
    bt = jnp.asarray(rng.integers(0, npages, size=(b, pps)), jnp.int32)
    lens = jnp.asarray(rng.integers(1, pps * ps, size=(b,)), jnp.int32)
    out = paged_attention(q, k, v, bt, lens)
    ref = paged_attention_decode_ref(q, k, v, bt, lens)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=atol)


def test_paged_attention_shared_prefix(rng):
    """Two sequences whose block tables share prefix pages: identical
    prefix + identical query => identical output."""
    kvh, hd, ps, pps = 2, 64, 8, 4
    npages = 12
    k = jnp.asarray(rng.normal(size=(kvh, npages, ps, hd)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(kvh, npages, ps, hd)), jnp.float32)
    qrow = jnp.asarray(rng.normal(size=(4, hd)), jnp.float32)
    q = jnp.stack([qrow, qrow])
    shared = [3, 7]
    bt = jnp.asarray([shared + [1, 2], shared + [5, 6]], jnp.int32)
    lens = jnp.asarray([2 * ps, 2 * ps], jnp.int32)  # only shared pages live
    out = paged_attention(q, k, v, bt, lens)
    np.testing.assert_allclose(out[0], out[1], atol=1e-6)


def test_paged_attention_length_masking(rng):
    """Tokens beyond `lengths` must not affect the result."""
    b, qh, kvh, hd, ps, pps = 1, 2, 1, 32, 4, 3
    npages = 6
    q = jnp.asarray(rng.normal(size=(b, qh, hd)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(kvh, npages, ps, hd)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(kvh, npages, ps, hd)), jnp.float32)
    bt = jnp.asarray([[0, 1, 2]], jnp.int32)
    lens = jnp.asarray([5], jnp.int32)
    base = paged_attention(q, k, v, bt, lens)
    k2 = k.at[:, 1, 3].set(99.0)  # token index 7 > length 5
    v2 = v.at[:, 1, 3].set(99.0)
    pert = paged_attention(q, k2, v2, bt, lens)
    np.testing.assert_allclose(base, pert, atol=1e-6)


@pytest.mark.parametrize("b,s,h,p,n,q", [
    (2, 32, 3, 16, 8, 8),
    (1, 64, 2, 32, 16, 16),
    (2, 40, 4, 8, 4, 16),
    (1, 16, 1, 64, 32, 4),
])
def test_ssd_kernel_shapes(rng, b, s, h, p, n, q):
    x = jnp.asarray(rng.normal(size=(b, s, h, p)), jnp.float32)
    dt = jnp.asarray(rng.uniform(0.01, 0.2, size=(b, s, h)), jnp.float32)
    a = -jnp.asarray(rng.uniform(0.5, 2, size=(h,)), jnp.float32)
    bb = jnp.asarray(rng.normal(size=(b, s, h, n)), jnp.float32)
    cc = jnp.asarray(rng.normal(size=(b, s, h, n)), jnp.float32)
    ref = ssd_scan_ref(x, dt, a, bb, cc)
    ker = ssd(x, dt, a, bb, cc, chunk=q)
    chk, _ = ssd_chunked(x, dt, a, bb, cc, chunk=q)
    np.testing.assert_allclose(ker, ref, atol=2e-4)
    np.testing.assert_allclose(chk, ref, atol=2e-4)


def test_ssd_kernel_nondivisible_padding(rng):
    x = jnp.asarray(rng.normal(size=(1, 13, 2, 8)), jnp.float32)
    dt = jnp.asarray(rng.uniform(0.01, 0.2, size=(1, 13, 2)), jnp.float32)
    a = -jnp.ones((2,), jnp.float32)
    bb = jnp.asarray(rng.normal(size=(1, 13, 2, 4)), jnp.float32)
    cc = jnp.asarray(rng.normal(size=(1, 13, 2, 4)), jnp.float32)
    ref = ssd_scan_ref(x, dt, a, bb, cc)
    ker = ssd(x, dt, a, bb, cc, chunk=8)
    np.testing.assert_allclose(ker, ref, atol=2e-4)


@pytest.mark.parametrize("vl", [1, 7, 11, 16])
def test_ssd_kernel_valid_mask_matches_unpadded_prefix(rng, vl):
    """Masked-dt through the kernel wrapper: ssd(..., valid=mask) over a
    right-padded sequence must reproduce the unpadded scan at every valid
    position — pad positions are identity transitions that contribute
    nothing downstream (same contract the serving chunk lane relies on)."""
    x = jnp.asarray(rng.normal(size=(2, 16, 2, 8)), jnp.float32)
    dt = jnp.asarray(rng.uniform(0.01, 0.2, size=(2, 16, 2)), jnp.float32)
    a = -jnp.ones((2,), jnp.float32)
    bb = jnp.asarray(rng.normal(size=(2, 16, 2, 4)), jnp.float32)
    cc = jnp.asarray(rng.normal(size=(2, 16, 2, 4)), jnp.float32)
    valid = jnp.arange(16)[None, :] < vl
    for use_kernel in (True, False):
        got = ssd(x, dt, a, bb, cc, chunk=8, use_kernel=use_kernel,
                  valid=valid)
        want = ssd(x[:, :vl], dt[:, :vl], a, bb[:, :vl], cc[:, :vl],
                   chunk=8, use_kernel=use_kernel)
        np.testing.assert_allclose(got[:, :vl], want, atol=2e-4)


def test_ssd_kernel_bf16(rng):
    x = jnp.asarray(rng.normal(size=(1, 16, 2, 8))).astype(jnp.bfloat16)
    dt = jnp.asarray(rng.uniform(0.01, 0.2, size=(1, 16, 2))).astype(jnp.bfloat16)
    a = -jnp.ones((2,), jnp.float32)
    bb = jnp.asarray(rng.normal(size=(1, 16, 2, 4))).astype(jnp.bfloat16)
    cc = jnp.asarray(rng.normal(size=(1, 16, 2, 4))).astype(jnp.bfloat16)
    ker = ssd(x, dt, a, bb, cc, chunk=8)
    ref = ssd_scan_ref(x.astype(jnp.float32), dt.astype(jnp.float32), a,
                       bb.astype(jnp.float32), cc.astype(jnp.float32))
    np.testing.assert_allclose(np.asarray(ker, np.float32), ref, atol=0.15)


@pytest.mark.parametrize("b,s,h,hd,bq,bk", [
    (2, 64, 4, 64, 16, 16),
    (1, 128, 2, 128, 32, 64),
    (2, 48, 3, 32, 16, 16),
    (1, 100, 2, 64, 32, 32),   # non-divisible: causal padding path
])
def test_flash_prefill_shapes(rng, b, s, h, hd, bq, bk):
    from repro.kernels.flash_prefill.ops import flash_attention
    from repro.kernels.flash_prefill.ref import flash_prefill_ref
    q = jnp.asarray(rng.normal(size=(b, s, h, hd)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(b, s, h, hd)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(b, s, h, hd)), jnp.float32)
    out = flash_attention(q, k, v, block_q=bq, block_k=bk)
    ref = flash_prefill_ref(q, k, v)
    np.testing.assert_allclose(out, ref, atol=2e-4)


def test_flash_prefill_bf16(rng):
    from repro.kernels.flash_prefill.ops import flash_attention
    from repro.kernels.flash_prefill.ref import flash_prefill_ref
    q = jnp.asarray(rng.normal(size=(1, 64, 2, 64))).astype(jnp.bfloat16)
    k = jnp.asarray(rng.normal(size=(1, 64, 2, 64))).astype(jnp.bfloat16)
    v = jnp.asarray(rng.normal(size=(1, 64, 2, 64))).astype(jnp.bfloat16)
    out = flash_attention(q, k, v, block_q=16, block_k=16)
    ref = flash_prefill_ref(q.astype(jnp.float32), k.astype(jnp.float32),
                            v.astype(jnp.float32))
    np.testing.assert_allclose(np.asarray(out, np.float32), ref, atol=5e-2)


def test_flash_prefill_noncausal(rng):
    from repro.kernels.flash_prefill.ops import flash_attention
    from repro.kernels.flash_prefill.ref import flash_prefill_ref
    q = jnp.asarray(rng.normal(size=(1, 32, 2, 32)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(1, 32, 2, 32)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(1, 32, 2, 32)), jnp.float32)
    out = flash_attention(q, k, v, causal=False, block_q=16, block_k=16)
    ref = flash_prefill_ref(q, k, v, causal=False)
    np.testing.assert_allclose(out, ref, atol=2e-4)


def test_flash_prefill_noncausal_nondivisible(rng):
    """Regression: internal padding used to require causal masking (the
    wrapper asserted), so non-causal non-divisible shapes crashed. Now the
    kernel masks padded key columns explicitly for either mode."""
    from repro.kernels.flash_prefill.ops import flash_attention
    from repro.kernels.flash_prefill.ref import flash_prefill_ref
    q = jnp.asarray(rng.normal(size=(1, 37, 2, 32)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(1, 37, 2, 32)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(1, 37, 2, 32)), jnp.float32)
    out = flash_attention(q, k, v, causal=False, block_q=16, block_k=16)
    ref = flash_prefill_ref(q, k, v, causal=False)
    np.testing.assert_allclose(out, ref, atol=2e-4)


@pytest.mark.parametrize("h,hkv", [(4, 2), (8, 1), (6, 3)])
def test_flash_prefill_gqa_native_kv(rng, h, hkv):
    """Regression for Hkv < H: KV stays [B, S, Hkv, hd] and the kernel
    indexes the head group in the BlockSpec — callers never pre-repeat
    (which doubled KV HBM traffic and broke silently when forgotten)."""
    from repro.kernels.flash_prefill.ops import flash_attention
    from repro.kernels.flash_prefill.ref import flash_prefill_ref
    b, s, hd = 2, 48, 32
    q = jnp.asarray(rng.normal(size=(b, s, h, hd)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(b, s, hkv, hd)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(b, s, hkv, hd)), jnp.float32)
    out = flash_attention(q, k, v, block_q=16, block_k=16)
    ref = flash_prefill_ref(q, k, v)
    np.testing.assert_allclose(out, ref, atol=2e-4)
    # pre-repeated KV must agree with the native-GQA call
    rep = flash_attention(q, jnp.repeat(k, h // hkv, 2),
                          jnp.repeat(v, h // hkv, 2),
                          block_q=16, block_k=16)
    np.testing.assert_allclose(out, rep, atol=1e-6)


def test_flash_prefill_masked_rows_exact_zeros(rng):
    """Regression: fully-masked (pad) query rows used to emit mis-normalized
    garbage (denominator clamped to 1e-30, or exp(-inf - -inf) = 1 claims).
    With an explicit row-validity mask they are exact zeros."""
    from repro.kernels.flash_prefill.flash_prefill import flash_prefill
    from repro.kernels.flash_prefill.ref import flash_prefill_ref
    q = jnp.asarray(rng.normal(size=(1, 16, 2, 32)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(1, 16, 2, 32)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(1, 16, 2, 32)), jnp.float32)
    for causal in (True, False):
        out = flash_prefill(q, k, v, causal=causal, block_q=8, block_k=8,
                            interpret=True, true_len=13)
        ref = flash_prefill_ref(q[:, :13], k[:, :13], v[:, :13],
                                causal=causal)
        np.testing.assert_allclose(out[:, :13], ref, atol=2e-4)
        assert np.all(np.asarray(out)[:, 13:] == 0.0)


# ---------------------------------------------------------------------------
# fused paged flash-prefill (the mixed-step chunk-row kernel)


def _paged_setup(rng, t, pos0, valid, qh, kvh, hd, ps, dtype=jnp.float32):
    """Random paged KV + a block table shaped like the engine's: real pages
    cover positions 0..pos0+valid-1 (in permuted order), the rest of the
    static-width table is the OOB sentinel (== num_pages)."""
    need = -(-(pos0 + valid) // ps)
    npages = need + 2
    pps = need + 3
    kp = jnp.asarray(rng.normal(size=(kvh, npages, ps, hd))).astype(dtype)
    vp = jnp.asarray(rng.normal(size=(kvh, npages, ps, hd))).astype(dtype)
    bt = np.full((pps,), npages, np.int32)
    bt[:need] = rng.permutation(npages)[:need]
    q = jnp.asarray(rng.normal(size=(t, qh, hd))).astype(dtype)
    return q, kp, vp, jnp.asarray(bt)


@pytest.mark.parametrize("t,pos0,valid,qh,kvh,hd,ps,bq", [
    (8, 0, 8, 4, 2, 32, 4, 8),     # fresh prompt, GQA
    (8, 5, 8, 4, 4, 32, 4, 4),     # chunk straddles a page boundary
    (8, 13, 3, 8, 2, 64, 8, 8),    # bucket-pad rows (valid < t)
    (16, 7, 11, 4, 1, 32, 4, 4),   # MQA, ragged, multiple q blocks
    (8, 16, 8, 2, 2, 16, 8, 8),    # chunk starts exactly at a page edge
    (6, 3, 5, 4, 2, 32, 4, 4),     # t not a block_q multiple: wrapper pads
])
def test_paged_flash_prefill_vs_ref(rng, t, pos0, valid, qh, kvh, hd, ps,
                                    bq):
    from repro.kernels.flash_prefill.ops import paged_flash_prefill
    from repro.kernels.flash_prefill.ref import paged_flash_prefill_ref
    q, kp, vp, bt = _paged_setup(rng, t, pos0, valid, qh, kvh, hd, ps)
    out = paged_flash_prefill(q, kp, vp, bt, jnp.int32(pos0),
                              jnp.int32(valid), block_q=bq)
    ref = paged_flash_prefill_ref(q, kp, vp, bt, jnp.int32(pos0),
                                  jnp.int32(valid))
    np.testing.assert_allclose(out, ref, atol=2e-4)
    # bucket-pad rows are exact zeros, never near-zero residue
    assert np.all(np.asarray(out)[valid:] == 0.0)


@pytest.mark.parametrize("t,pos0,valid,qh,kvh,hd,ps", [
    (8, 5, 8, 4, 2, 32, 4),
    (8, 13, 3, 8, 2, 64, 8),
    (16, 7, 11, 4, 4, 32, 4),
])
def test_paged_flash_prefill_matches_decode_path(rng, t, pos0, valid, qh,
                                                 kvh, hd, ps):
    """The fused kernel and the per-token flash-decode path are the same
    math: row i of the chunk == a decode call with length pos0 + i + 1
    against the same block table."""
    from repro.kernels.flash_prefill.ops import paged_flash_prefill
    from repro.kernels.paged_attention.ref import paged_attention_decode_ref
    q, kp, vp, bt = _paged_setup(rng, t, pos0, valid, qh, kvh, hd, ps)
    out = paged_flash_prefill(q, kp, vp, bt, jnp.int32(pos0),
                              jnp.int32(valid))
    bt_rows = jnp.broadcast_to(bt, (t, bt.shape[0]))
    lens = pos0 + jnp.arange(t) + 1
    dec = paged_attention_decode_ref(q, kp, vp, bt_rows, lens)
    np.testing.assert_allclose(np.asarray(out)[:valid],
                               np.asarray(dec)[:valid], atol=2e-4)


def test_paged_flash_prefill_bf16(rng):
    from repro.kernels.flash_prefill.ops import paged_flash_prefill
    from repro.kernels.flash_prefill.ref import paged_flash_prefill_ref
    q, kp, vp, bt = _paged_setup(rng, 8, 5, 8, 4, 2, 32, 4,
                                 dtype=jnp.bfloat16)
    out = paged_flash_prefill(q, kp, vp, bt, jnp.int32(5), jnp.int32(8),
                              block_q=4)
    ref = paged_flash_prefill_ref(q, kp, vp, bt, jnp.int32(5), jnp.int32(8))
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=5e-2)


def test_paged_flash_prefill_ignores_poisoned_future_pages(rng):
    """Keys past a row's causal horizon — slots the chunk has not written
    yet and sentinel-table garbage — must not affect its output."""
    from repro.kernels.flash_prefill.ops import paged_flash_prefill
    t, pos0, valid, qh, kvh, hd, ps = 8, 5, 6, 4, 2, 32, 4
    q, kp, vp, bt = _paged_setup(rng, t, pos0, valid, qh, kvh, hd, ps)
    base = paged_flash_prefill(q, kp, vp, bt, jnp.int32(pos0),
                               jnp.int32(valid), block_q=4)
    # poison every slot at absolute positions >= pos0 + valid
    npages = kp.shape[1]
    flat = np.zeros((npages * ps,), bool)
    for pos in range(pos0 + valid, (npages - 2) * ps):
        flat[int(bt[pos // ps]) * ps + pos % ps] = True
    poison = jnp.asarray(flat).reshape(npages, ps)
    kp2 = jnp.where(poison[None, :, :, None], 1e4, kp)
    vp2 = jnp.where(poison[None, :, :, None], 1e4, vp)
    pert = paged_flash_prefill(q, kp2, vp2, bt, jnp.int32(pos0),
                               jnp.int32(valid), block_q=4)
    np.testing.assert_allclose(base, pert, atol=1e-6)


def test_mixed_step_bytes_fused_strictly_fewer():
    """Acceptance: at chunk >= 256 and context >= 2048 the fused path reads
    strictly fewer K/V bytes than the per-token flash-decode loop (the
    O(chunk · context) -> O(q_blocks · context) collapse)."""
    from repro.kernels.flash_prefill.ops import mixed_step_bytes_read
    for chunk, ctx in [(256, 2048), (256, 4096), (512, 2048)]:
        dec = mixed_step_bytes_read(chunk, ctx, 16, 8, 64, path="decode")
        fus = mixed_step_bytes_read(chunk, ctx, 16, 8, 64, path="fused")
        assert fus < dec, (chunk, ctx, fus, dec)
        # one 128-row q block streams the context once, not 128 times
        assert dec > 50 * fus, (chunk, ctx, fus, dec)


# ---------------------------------------------------------------------------
# tree/cascade decode attention (shared-ancestor pass + per-branch suffix
# pass, merged by online-softmax partials). Differential backbone: the tree
# kernel, the tree jnp ref, the per-branch decode kernel and the per-branch
# decode ref over the SAME reconstructed full tables must all agree. All
# lengths are >= 1: the engine always attends at least the current token,
# and at length 0 the refs' uniform-softmax convention diverges from the
# kernels' exact-zero rows by design.


def _tree_topology(rng, groups, singles, *, qh, kvh, hd, ps):
    """Build a fork topology and every table the four paths consume.

    ``groups``: list of ``(shared_pages, [branch_len_tokens, ...])`` fork
    groups — each branch holds the group's shared ancestor pages plus a
    private suffix covering its remaining tokens. ``singles``: lengths of
    ungrouped rows (full table stays in ``branch_bt``). Page ids are
    distinct across the whole topology; tables are sentinel-padded to a
    common static width with one guaranteed pad column.
    """
    next_page = 0

    def take(n):
        nonlocal next_page
        ids = list(range(next_page, next_page + n))
        next_page += n
        return ids

    full_tables, lengths, group_of, shared_of = [], [], [], []
    for gi, (ns, br_lens) in enumerate(groups):
        sp = take(ns)
        shared_of.append(sp)
        for tokens in br_lens:
            suffix = max(tokens - ns * ps, 0)
            sfx = take(-(-suffix // ps)) if suffix else []
            full_tables.append(sp + sfx)
            lengths.append(tokens)
            group_of.append(gi)
    for tokens in singles:
        full_tables.append(take(-(-tokens // ps)))
        lengths.append(tokens)
        group_of.append(None)

    b = len(full_tables)
    num_pages = next_page + 2            # two never-referenced live pages
    pps = max(len(t) for t in full_tables) + 1   # >= 1 pad column
    full_bt = np.full((b, pps), num_pages, np.int32)
    shared_bt = np.full((b, pps), num_pages, np.int32)
    shared_lens = np.zeros((b,), np.int32)
    branch_bt = np.full((b, pps), num_pages, np.int32)
    row_group = np.full((b,), b, np.int32)
    for i, pages in enumerate(full_tables):
        full_bt[i, :len(pages)] = pages
        gi = group_of[i]
        if gi is None:
            branch_bt[i, :len(pages)] = pages
            continue
        row_group[i] = gi
        sp = shared_of[gi]
        shared_bt[gi, :len(sp)] = sp
        shared_lens[gi] = len(sp) * ps
        sfx = pages[len(sp):]
        branch_bt[i, :len(sfx)] = sfx

    q = jnp.asarray(rng.normal(size=(b, qh, hd)), jnp.float32)
    kp = jnp.asarray(rng.normal(size=(kvh, num_pages, ps, hd)), jnp.float32)
    vp = jnp.asarray(rng.normal(size=(kvh, num_pages, ps, hd)), jnp.float32)
    return dict(q=q, kp=kp, vp=vp, row_group=jnp.asarray(row_group),
                shared_bt=jnp.asarray(shared_bt),
                shared_lens=jnp.asarray(shared_lens),
                branch_bt=jnp.asarray(branch_bt),
                full_bt=jnp.asarray(full_bt),
                lengths=jnp.asarray(lengths, jnp.int32),
                full_tables=full_tables, group_of=group_of,
                shared_of=shared_of, num_pages=num_pages, ps=ps)


def _tree_all_paths(t):
    """(tree kernel, tree ref, per-branch kernel, per-branch ref)."""
    from repro.kernels.paged_attention.ops import paged_tree_attention
    from repro.kernels.paged_attention.ref import paged_tree_attention_ref
    tree_args = (t["q"], t["kp"], t["vp"], t["row_group"], t["shared_bt"],
                 t["shared_lens"], t["branch_bt"], t["lengths"])
    return (paged_tree_attention(*tree_args),
            paged_tree_attention_ref(*tree_args),
            paged_attention(t["q"], t["kp"], t["vp"], t["full_bt"],
                            t["lengths"]),
            paged_attention_decode_ref(t["q"], t["kp"], t["vp"],
                                       t["full_bt"], t["lengths"]))


def _assert_tree_differential(t, atol=2e-4):
    ker, tref, pb_ker, pb_ref = _tree_all_paths(t)
    # the tree ref reconstructs the exact full tables: bit-identical to
    # the per-branch ref, not merely close
    np.testing.assert_array_equal(np.asarray(tref), np.asarray(pb_ref))
    np.testing.assert_allclose(ker, pb_ref, atol=atol)
    np.testing.assert_allclose(ker, pb_ker, atol=atol)


@pytest.mark.parametrize("qh,kvh", [(4, 2), (4, 1), (4, 4)])  # GQA/MQA/MHA
def test_tree_decode_matches_per_branch(rng, qh, kvh):
    """Mixed topology: a 3-way fork, a 2-way fork and a singleton, ragged
    suffix lengths, under every head regime."""
    t = _tree_topology(
        rng,
        groups=[(2, [2 * 4 + 5, 2 * 4 + 1, 2 * 4 + 9]),
                (1, [4 + 3, 4 + 4])],
        singles=[7],
        qh=qh, kvh=kvh, hd=32, ps=4)
    _assert_tree_differential(t)


def test_tree_decode_ragged_depths(rng):
    """Shared depths 1..3 pages across groups; one branch's context ends
    INSIDE its group's shared span (its suffix pass has zero pages and
    the shared pass must mask tokens past its own length)."""
    t = _tree_topology(
        rng,
        groups=[(3, [3 * 4 + 2, 2 * 4 + 1]),   # second row ends mid-span
                (2, [2 * 4 + 4, 2 * 4 + 7]),
                (1, [4 + 1, 4 + 2, 4 + 3])],
        singles=[],
        qh=4, kvh=2, hd=32, ps=4)
    _assert_tree_differential(t)


def test_tree_decode_fork_alignment(rng):
    """Boundary fork vs mid-page fork. A fork at a page boundary keeps
    the full prefix shared; a mid-page fork copies the straddling page
    into each branch (CoW), so only the floor-to-page prefix is shared
    and the straddled page rides in each suffix table."""
    # boundary: 2 shared pages, suffixes start exactly at token 8
    t = _tree_topology(rng, groups=[(2, [8 + 1, 8 + 2])], singles=[],
                       qh=4, kvh=2, hd=32, ps=4)
    _assert_tree_differential(t)
    # mid-page: fork at token 6 -> 1 shared page, the half-filled page is
    # private to each branch (distinct page ids, same logical prefix)
    t = _tree_topology(rng, groups=[(1, [4 + 6, 4 + 8])], singles=[],
                       qh=4, kvh=2, hd=32, ps=4)
    _assert_tree_differential(t)


def test_tree_decode_single_branch_degenerate(rng):
    """A 1-member fork group and a fully ungrouped batch must both
    reproduce the plain decode kernel — the tree machinery degenerates to
    per-branch streaming. Ungrouped, a row within one page is one softmax
    update in both kernels and must match bit-for-bit; a longer row only
    to rounding, since the decode kernel merges a block of pages per
    update and the tree's branch pass one page."""
    t = _tree_topology(rng, groups=[(2, [2 * 4 + 3])], singles=[9, 5],
                       qh=4, kvh=2, hd=32, ps=4)
    ker, _tref, pb_ker, _pb_ref = _tree_all_paths(t)
    np.testing.assert_allclose(ker, pb_ker, atol=1e-6)
    # all-ungrouped: sentinel row_group, zero shared spans
    t2 = _tree_topology(rng, groups=[], singles=[13, 6, 2],
                        qh=4, kvh=2, hd=32, ps=4)
    ker2, _t2ref, pb_ker2, _ = _tree_all_paths(t2)
    ker2, pb_ker2 = np.asarray(ker2), np.asarray(pb_ker2)
    one_page = np.asarray(t2["lengths"]) <= 4
    assert one_page.any() and not one_page.all()
    np.testing.assert_array_equal(ker2[one_page], pb_ker2[one_page])
    np.testing.assert_allclose(ker2[~one_page], pb_ker2[~one_page],
                               atol=1e-6)


def test_tree_decode_poisoned_unshared_page_invariance(rng):
    """Pages a row does not own — other branches' suffixes and
    never-referenced pages — must not leak into its output through the
    shared pass's parked iterations or sentinel clamps. Poisoning branch
    B's suffix pages leaves every OTHER row bitwise unchanged."""
    t = _tree_topology(
        rng,
        groups=[(2, [2 * 4 + 5, 2 * 4 + 6, 2 * 4 + 2])],
        singles=[7],
        qh=4, kvh=2, hd=32, ps=4)
    from repro.kernels.paged_attention.ops import paged_tree_attention
    args = (t["row_group"], t["shared_bt"], t["shared_lens"],
            t["branch_bt"], t["lengths"])
    base = np.asarray(paged_tree_attention(t["q"], t["kp"], t["vp"], *args))
    victim = 1                           # poison this branch's suffix
    own = set(t["full_tables"][victim]) - set(t["shared_of"][0])
    # plus the two never-referenced live pages and the sentinel clamp
    # target (num_pages - 1 is never-referenced here by construction)
    poison = own | {t["num_pages"] - 2, t["num_pages"] - 1}
    assert not any(p in poison
                   for i, pages in enumerate(t["full_tables"])
                   if i != victim for p in pages)
    mask = np.zeros((t["num_pages"],), bool)
    mask[sorted(poison)] = True
    sel = jnp.asarray(mask)[None, :, None, None]
    kp2 = jnp.where(sel, 1e4, t["kp"])
    vp2 = jnp.where(sel, 1e4, t["vp"])
    pert = np.asarray(paged_tree_attention(t["q"], kp2, vp2, *args))
    rows = [i for i in range(base.shape[0]) if i != victim]
    np.testing.assert_array_equal(base[rows], pert[rows])


def test_tree_decode_bf16_pages(rng):
    """bf16 K/V pages through both passes (the engine's serving dtype)."""
    t = _tree_topology(rng, groups=[(2, [2 * 4 + 3, 2 * 4 + 6])],
                       singles=[5], qh=4, kvh=2, hd=32, ps=4)
    from repro.kernels.paged_attention.ops import paged_tree_attention
    kp = t["kp"].astype(jnp.bfloat16)
    vp = t["vp"].astype(jnp.bfloat16)
    out = paged_tree_attention(t["q"].astype(jnp.bfloat16), kp, vp,
                               t["row_group"], t["shared_bt"],
                               t["shared_lens"], t["branch_bt"],
                               t["lengths"])
    ref = paged_attention_decode_ref(t["q"], t["kp"], t["vp"],
                                     t["full_bt"], t["lengths"])
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref), atol=5e-2)


def test_tree_decode_grid_lattice_bounds():
    """STEP007-style containment proof over the tree grids' full grid ×
    scalar-case lattice, plus the negative control: stripping the
    sentinel clamp from the shared pass's KV map must be caught on the
    all-sentinel case."""
    import dataclasses
    import sys
    from pathlib import Path
    repo = Path(__file__).resolve().parents[1]
    if str(repo) not in sys.path:
        sys.path.insert(0, str(repo))
    from tools.stepcheck import bounds
    from tools.stepcheck.bounds import verify_kernel_grid
    from repro.kernels import paged_tree_branch_grid, paged_tree_shared_grid

    num_pages, ps, pps = 16, 4, 6
    for kvh in (1, 2, 4):
        kg = paged_tree_shared_grid(3, 4, 8, kvh, num_pages, ps, 3, pps)
        cases = bounds.tree_shared_cases(num_pages, ps, pps, 3)
        assert verify_kernel_grid(kg, cases) == []
        bg = paged_tree_branch_grid(3, 4, 8, kvh, num_pages, ps, pps)
        assert verify_kernel_grid(
            bg, bounds.tree_branch_cases(num_pages, ps, pps, 3)) == []

    kg = paged_tree_shared_grid(3, 4, 8, 2, num_pages, ps, 3, pps)
    broken = dataclasses.replace(kg, in_mappings=tuple(
        dataclasses.replace(
            m, index_map=lambda h, g, ki, sbt, sl: (h, sbt[g, ki], 0, 0))
        if m.name in ("k_pages", "v_pages") else m
        for m in kg.in_mappings))
    caught = verify_kernel_grid(
        broken, bounds.tree_shared_cases(num_pages, ps, pps, 3))
    assert {f.symbol for f in caught} == {"k_pages", "v_pages"}
    # the sentinel chase specifically: only the num_pages-1 clamp keeps
    # an all-sentinel (no fork groups) step in bounds
    sentinel = [c for c in bounds.tree_shared_cases(num_pages, ps, pps, 3)
                if c.name == "all-sentinel"]
    caught = verify_kernel_grid(broken, sentinel)
    assert any(f.rule == "STEP007" and "all-sentinel" in f.message
               for f in caught)
