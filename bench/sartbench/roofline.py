"""Peaks of the chip and the work an operation needs, from shapes.

The counts are of the work the step needs, not of what today's kernels
read: decode attention reads each distinct K/V page that the step's live
rows reference once per layer (the N branches of a request share their
prompt pages, which count once), and only the tokens of a page that some
row attends; FLOPs cover valid rows only. A later kernel that reads shared
pages once is measured by the same count, so no share can pass 100%.
"""
from __future__ import annotations

from typing import Iterable, List, Tuple

import numpy as np

# Published peaks per chip, keyed by jax's ``device_kind``. Source: Google
# Cloud documentation, "TPU v5e" (system architecture): 197 TFLOP/s bf16,
# 819 GB/s HBM bandwidth, 16 GB HBM.
PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9},
}


def peaks(device_kind: str) -> dict:
    """The peaks of ``device_kind``; a device not in the table is an
    error, never a default."""
    if device_kind not in PEAKS:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}")
    return PEAKS[device_kind]


def dims(model: dict) -> dict:
    m = dict(model)
    m.setdefault("head_dim", m["d_model"] // m["num_heads"])
    return m


def layer_matmul_flops(model: dict) -> int:
    """FLOPs of one token through one layer's projections and MLP."""
    m = dims(model)
    d, h, kv, hd, ff = (m["d_model"], m["num_heads"], m["num_kv_heads"],
                        m["head_dim"], m["d_ff"])
    return 2 * (d * h * hd + 2 * d * kv * hd + h * hd * d + 3 * d * ff)


def attention_flops(model: dict, attended: int) -> int:
    """FLOPs of one layer's attention for queries that attend
    ``attended`` keys in all (QK and PV)."""
    m = dims(model)
    return 4 * m["num_heads"] * m["head_dim"] * int(attended)


def unembed_flops(model: dict) -> int:
    return 2 * model["d_model"] * model["vocab_size"]


def step_model_flops(model: dict, rows: int, ctx: int,
                     lanes: Iterable[Tuple[int, int]]) -> int:
    """Model FLOPs of one serving step's useful rows: ``rows`` decode rows
    attending ``ctx`` tokens in all, and chunk lanes ``(pos0, n)`` of which
    only the last row's logits are used."""
    L = model["num_layers"]
    mm = layer_matmul_flops(model)
    total = rows * (L * mm + unembed_flops(model))
    total += L * attention_flops(model, ctx)
    for pos0, n in lanes:
        total += n * L * mm + unembed_flops(model)
        total += L * attention_flops(model, n * pos0 + n * (n + 1) // 2)
    return total


def kv_token_bytes(model: dict, itemsize: int = 2) -> int:
    """K plus V bytes of one token in one layer."""
    m = dims(model)
    return 2 * m["num_kv_heads"] * m["head_dim"] * itemsize


def distinct_tokens(tables: np.ndarray, lengths: np.ndarray,
                    page_size: int) -> int:
    """Tokens in the distinct pages that rows attend: for each page id
    referenced by a row within its first ``lengths`` tokens, the most
    tokens of it any row attends."""
    pages: List[np.ndarray] = []
    counts: List[np.ndarray] = []
    for row, n in zip(tables, lengths):
        n = int(n)
        if n <= 0:
            continue
        k = -(-n // page_size)
        pages.append(row[:k])
        c = np.full(k, page_size)
        c[-1] = n - (k - 1) * page_size
        counts.append(c)
    if not pages:
        return 0
    p = np.concatenate(pages)
    c = np.concatenate(counts)
    order = np.lexsort((-c, p))
    p, c = p[order], c[order]
    first = np.ones(len(p), bool)
    first[1:] = p[1:] != p[:-1]
    return int(c[first].sum())


def decode_attention_work(model: dict, tables, lengths,
                          page_size: int) -> Tuple[int, int]:
    """(FLOPs, bytes) decode attention needs in one step over all layers:
    rows with block tables ``tables`` attending ``lengths`` tokens each."""
    m = dims(model)
    L = m["num_layers"]
    q_bytes = 2 * len(lengths) * m["num_heads"] * m["head_dim"] * 2
    kv = distinct_tokens(tables, lengths, page_size) * kv_token_bytes(m)
    flops = L * attention_flops(m, int(np.sum(lengths)))
    return flops, L * (kv + q_bytes)


def prefill_attention_work(model: dict, pos0: int,
                           n: int) -> Tuple[int, int]:
    """(FLOPs, bytes) one chunk lane of ``n`` rows at ``pos0`` needs for
    attention over its paged context, all layers."""
    m = dims(model)
    L = m["num_layers"]
    flops = L * attention_flops(m, n * pos0 + n * (n + 1) // 2)
    q_bytes = 2 * n * m["num_heads"] * m["head_dim"] * 2
    return flops, L * ((pos0 + n) * kv_token_bytes(m) + q_bytes)


def least_seconds(flops: float, nbytes: float, device_kind: str) -> float:
    """The least time the chip could take: the larger of the two
    bounds."""
    pk = peaks(device_kind)
    return max(flops / pk["bf16_flops"], nbytes / pk["hbm_bytes_per_s"])
