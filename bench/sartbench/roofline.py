"""Peaks of the chip and the work an operation needs, from shapes.

The counts are of the work the step needs, not of what today's kernels
read: decode attention reads each distinct K/V page that the step's live
rows reference once per layer (the N branches of a request share their
prompt pages, which count once), and only the tokens of a page that some
row attends; FLOPs cover valid rows only. A later kernel that reads shared
pages once is measured by the same count, so no share can pass 100%.

What each layer holds is the configuration file's top-level ``work``: a
list of layer groups in the harness's own words, each with ``kind``,
``layers`` (how many layers hold it) and the kind's widths:

* ``attention``: ``heads``, ``kv_heads``, ``head_dim``;
* ``mlp``: ``width``, ``gated``;
* ``moe``: ``experts`` (published), ``held`` (on this chip),
  ``per_token``, ``width``, ``shared_width`` (0 for none), ``gated``;
* ``mamba2``: ``heads``, ``head_dim``, ``state``, ``groups``, ``conv``.

``d_model``, ``vocab_size`` and ``tie_embeddings`` come from ``model``. A
layer that holds attention and an MLP appears in two groups. A file
without ``work`` is read as ``num_layers`` x (attention of ``model``'s
heads + an MLP of ``d_ff``, gated unless ``mlp_gated`` is false).
``spec.describe`` reads it into a ``Work``, which every count here takes:
an unknown kind, a missing width, a key of no kind, or attention widths
other than ``model``'s (from which the program builds its K/V pool) is a
``SpecError``, never a default. ``work`` lives beside ``model`` and not in it: the
program builds its model from ``model`` alone and never reads ``work``,
which is the benchmark's record of the published architecture, so the
counts stay independent of the code under test and a configuration cut in
depth states its depth to the program once and its layers to the count by
kind.

Bytes are at the served type's itemsize (2, bf16). A step's weight bytes
count the routed experts of an MoE group only as far as the step reached
them, by the program's counter ``EXPERTS_REACHED`` (an engine counter, read
by ``loadgen.engine_counters`` and kept per step on ``Step.counts``): the
held experts that got at least one of the step's tokens, summed over the
MoE layers of the step. Without that counter, or in a step where it did
not move, routed experts count zero and the step's bytes stay a lower
bound. The counter does not say how its sum splits between MoE groups, so
a configuration with more than one is a ``SpecError`` where a step's
record holds it (``experts_reached``).
"""
from __future__ import annotations

from typing import Dict, Iterable, List, Optional, Sequence, Tuple

import numpy as np

from .spec import SpecError, Work, uniform

# the program's counter of routed experts reached in a step
EXPERTS_REACHED = "moe.experts_reached"

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


def _mults(g: dict) -> int:
    return 3 if g["gated"] else 2


def _mamba2_sizes(g: dict) -> Tuple[int, int]:
    """(inner width, conv channels: x, B and C) of a Mamba-2 group."""
    di = g["heads"] * g["head_dim"]
    return di, di + 2 * g["groups"] * g["state"]


def layer_flops(g: dict, d: int) -> float:
    """FLOPs of one token through one layer of group ``g``, attention's
    context aside: projections and MLPs; an MoE layer's router at its
    published width, its routed experts' expected share on this chip and
    its shared expert; a Mamba-2 layer's projections, conv and state
    update and read-out."""
    kind = g["kind"]
    if kind == "attention":
        h, kv, hd = g["heads"], g["kv_heads"], g["head_dim"]
        return 2 * (d * h * hd + 2 * d * kv * hd + h * hd * d)
    if kind == "mlp":
        return 2 * _mults(g) * d * g["width"]
    if kind == "moe":
        expert = 2 * _mults(g) * d * g["width"]
        return (2 * d * g["experts"]
                + g["per_token"] * g["held"] * expert / g["experts"]
                + 2 * _mults(g) * d * g["shared_width"])
    di, xbc = _mamba2_sizes(g)
    h, n = g["heads"], g["state"]
    return (2 * d * (2 * di + 2 * g["groups"] * n + h) + 2 * di * d
            + 2 * g["conv"] * xbc + 6 * h * g["head_dim"] * n)


def token_flops(w: Work) -> float:
    """FLOPs of one token through every layer, attention's context and
    the output head aside."""
    return sum(g["layers"] * layer_flops(g, w.d_model) for g in w.groups)


def layer_matmul_flops(model: dict) -> int:
    """FLOPs of one token through one layer's projections and MLP, of a
    model whose every layer is alike."""
    return sum(layer_flops(g, model["d_model"]) for g in uniform(model))


def _context_flops(g: dict, attended: int) -> int:
    """FLOPs of one attention layer of group ``g`` for queries that attend
    ``attended`` keys in all (QK and PV)."""
    return 4 * g["heads"] * g["head_dim"] * int(attended)


def unembed_flops(w: Work) -> int:
    return 2 * w.d_model * w.vocab_size


def step_model_flops(w: Work, rows: int, ctx: int,
                     lanes: Iterable[Tuple[int, int]]) -> float:
    """Model FLOPs of one serving step's useful rows: ``rows`` decode rows
    attending ``ctx`` tokens in all, and chunk lanes ``(pos0, n)`` of which
    only the last row's logits are used; attention over the attention
    layers only."""
    per_token, head = token_flops(w), unembed_flops(w)
    attn = w.of("attention")

    def context(attended):
        return sum(g["layers"] * _context_flops(g, attended) for g in attn)
    total = rows * (per_token + head) + context(ctx)
    for pos0, n in lanes:
        total += n * per_token + head + context(n * pos0 + n * (n + 1) // 2)
    return total


def _kv_bytes(g: dict, itemsize: int = 2) -> int:
    return 2 * g["kv_heads"] * g["head_dim"] * itemsize


def kv_token_bytes(model: dict, itemsize: int = 2) -> int:
    """K plus V bytes of one token in one layer, of a model whose every
    layer is alike."""
    return _kv_bytes(uniform(model)[0], itemsize)


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


def _q_bytes(g: dict, rows: int) -> int:
    """The queries in and the outputs out of one attention layer."""
    return 2 * rows * g["heads"] * g["head_dim"] * 2


def decode_attention_work(w: Work, tables, lengths,
                          page_size: int) -> Tuple[int, int]:
    """(FLOPs, bytes) decode attention needs in one step over all
    attention layers: rows with block tables ``tables`` attending
    ``lengths`` tokens each."""
    distinct = distinct_tokens(tables, lengths, page_size)
    attended = int(np.sum(lengths))
    flops = nbytes = 0
    for g in w.of("attention"):
        flops += g["layers"] * _context_flops(g, attended)
        nbytes += g["layers"] * (distinct * _kv_bytes(g)
                                 + _q_bytes(g, len(lengths)))
    return flops, nbytes


def prefill_attention_work(w: Work, pos0: int, n: int) -> Tuple[int, int]:
    """(FLOPs, bytes) one chunk lane of ``n`` rows at ``pos0`` needs for
    attention over its paged context, all attention layers."""
    flops = nbytes = 0
    for g in w.of("attention"):
        flops += g["layers"] * _context_flops(
            g, n * pos0 + n * (n + 1) // 2)
        nbytes += g["layers"] * ((pos0 + n) * _kv_bytes(g) + _q_bytes(g, n))
    return flops, nbytes


def mamba2_state_bytes(g: dict, itemsize: int = 2) -> int:
    """Bytes of one row's state in one Mamba-2 layer: the SSM state
    (heads x head_dim x state) and the conv's last ``conv - 1`` inputs of
    x, B and C."""
    di, xbc = _mamba2_sizes(g)
    return (g["heads"] * g["head_dim"] * g["state"]
            + (g["conv"] - 1) * xbc) * itemsize


def weight_bytes(g: dict, d: int, reached: int = 0,
                 itemsize: int = 2) -> int:
    """Bytes of the weight matrices of group ``g``'s layers, each read
    once. An MoE group counts its router, its shared expert and, of its
    routed experts, the ``reached`` (summed over the group's layers) that
    a step's record says it reached."""
    kind = g["kind"]
    if kind == "attention":
        h, kv, hd = g["heads"], g["kv_heads"], g["head_dim"]
        per_layer = d * h * hd + 2 * d * kv * hd + h * hd * d
    elif kind == "mlp":
        per_layer = _mults(g) * d * g["width"]
    elif kind == "moe":
        per_layer = d * g["experts"] + _mults(g) * d * g["shared_width"]
    else:
        di, xbc = _mamba2_sizes(g)
        per_layer = (d * (2 * di + 2 * g["groups"] * g["state"]
                          + g["heads"]) + di * d + g["conv"] * xbc)
    routed = reached * _mults(g) * d * g["width"] if kind == "moe" else 0
    return (g["layers"] * per_layer + routed) * itemsize


def step_bytes(w: Work, tables, lengths, lanes: Sequence[Tuple[int, int]],
               page_size: int, reached: Optional[Sequence[int]] = None,
               itemsize: int = 2) -> int:
    """The least bytes one serving step moves: every weight matrix it
    multiplies, read once (``reached``: routed experts reached per MoE
    group, in order; none counts zero); the output head, read once, and
    the embedding rows the step's tokens gather unless the head is tied
    (its read covers them); in each attention layer the K/V of the
    distinct tokens the decode rows attend (``tables``, ``lengths``) and
    of each chunk lane's ``pos0`` tokens before it, and the new tokens'
    K/V written; in each Mamba-2 layer each row's and lane's state, read
    and written."""
    d = w.d_model
    rows = len(lengths)
    new = rows + sum(n for _, n in lanes)
    seqs = rows + len(lanes)
    kv_tokens = (distinct_tokens(tables, lengths, page_size)
                 + sum(pos0 for pos0, _ in lanes) + new)
    total = w.vocab_size * d * itemsize
    if not w.tie_embeddings:
        total += new * d * itemsize
    moe = iter(reached or ())
    for g in w.groups:
        total += weight_bytes(
            g, d, next(moe, 0) if g["kind"] == "moe" else 0, itemsize)
        if g["kind"] == "attention":
            total += g["layers"] * kv_tokens * _kv_bytes(g, itemsize)
        elif g["kind"] == "mamba2":
            total += g["layers"] * 2 * seqs * mamba2_state_bytes(g, itemsize)
    return total


def experts_reached(w: Work, counts: Dict[str, int]) -> Optional[List[int]]:
    """Routed experts reached per MoE group (``step_bytes``'s ``reached``)
    from a step's counter changes; None without an MoE group or where
    the step's record does not hold ``EXPERTS_REACHED``."""
    moe = w.of("moe")
    if not moe or EXPERTS_REACHED not in counts:
        return None
    if len(moe) > 1:
        raise SpecError(f"{EXPERTS_REACHED} sums over {len(moe)} MoE "
                        f"groups and cannot be split between them")
    reached, g = counts[EXPERTS_REACHED], moe[0]
    if not 0 <= reached <= g["layers"] * g["held"]:
        raise ValueError(f"{EXPERTS_REACHED} = {reached} in one step; "
                         f"{g['layers']} MoE layers hold {g['held']} "
                         f"experts each")
    return [reached]


def least_seconds(flops: float, nbytes: float, device_kind: str) -> float:
    """The least time the chip could take: the larger of the two
    bounds."""
    pk = peaks(device_kind)
    return max(flops / pk["bf16_flops"], nbytes / pk["hbm_bytes_per_s"])
