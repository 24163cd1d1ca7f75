"""The one traffic generator: every mix is a data file of parameters.

A request is a prompt of random token ids and N branch lengths. Every seed
gets the same multiset of sizes and of inter-arrival gaps, drawn once from
the mix's fixed ``sizes_seed``; the run's ``--seed`` only orders them
(a fresh permutation for each block of ``cycle`` requests) and draws the
token ids. So any stretch of ``cycle`` requests carries the same work
whatever the seed, and a branch's length depends on (seed, request, branch
index) alone, never on timing.

Parameters (``bench/traffic/<name>.json``):

* ``loop``: ``"open"`` (arrivals on a schedule, ``rate_per_s``) or
  ``"closed"`` (keep ``ceil(backlog_factor * max_slots / n)`` requests
  waiting for admission at all times).
* ``prompt_tokens``, ``branch_tokens``: log-normal ``median`` and
  ``sigma``, clipped to ``[min, max]``.
* ``n``, ``m``: branches sampled per request, and SART's early stop at the
  m-th finished branch.
* ``cycle``, ``sizes_seed``: the block of sizes and gaps, and its seed.
* ``warm_s``: seconds of this traffic run before the window opens.
"""
from __future__ import annotations

import dataclasses
import math
from typing import List, Optional

from statistics import NormalDist

import numpy as np


@dataclasses.dataclass(frozen=True)
class RequestSpec:
    index: int
    prompt: List[int]
    branch_lengths: List[int]      # generated tokens per branch, in order


def _lognormal_quantiles(spec: dict, count: int, rng) -> np.ndarray:
    """``count`` stratified log-normal sizes (one per quantile bin,
    jittered inside it), clipped to the spec's range, as ints."""
    u = (np.arange(count) + rng.random(count)) / count
    x = spec["median"] * np.exp(spec["sigma"] * np.array(
        [NormalDist().inv_cdf(v) for v in u]))
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(int)


class Traffic:
    """Requests and due times of one mix for one seed."""

    def __init__(self, spec: dict, seed: int, vocab_size: int,
                 max_slots: int):
        self.spec = spec
        self.seed = int(seed)
        self.vocab_size = int(vocab_size)
        self.n = int(spec["n"])
        self.m = int(spec["m"])
        self.cycle = int(spec["cycle"])
        self.open_loop = spec["loop"] == "open"
        if spec["loop"] not in ("open", "closed"):
            raise ValueError(f"unknown loop {spec['loop']!r}")
        fixed = np.random.default_rng(int(spec["sizes_seed"]))
        self._prompt_lens = _lognormal_quantiles(
            spec["prompt_tokens"], self.cycle, fixed)
        fixed.shuffle(self._prompt_lens)
        branches = _lognormal_quantiles(spec["branch_tokens"],
                                        self.cycle * self.n, fixed)
        fixed.shuffle(branches)
        self._branch_lens = branches.reshape(self.cycle, self.n)
        if self.open_loop:
            rate = float(spec["rate_per_s"])
            gaps = -np.log1p(-(np.arange(self.cycle) + 0.5) / self.cycle)
            self._gaps = gaps / gaps.mean() / rate   # mean exactly 1/rate
            self.waiting = 0
        else:
            self._gaps = None
            self.waiting = math.ceil(float(spec["backlog_factor"])
                                     * max_slots / self.n)
        self._orders: dict = {}

    def _order(self, block: int) -> np.ndarray:
        if block not in self._orders:
            rng = np.random.default_rng([self.seed, block, 1])
            self._orders[block] = rng.permutation(self.cycle)
        return self._orders[block]

    def request(self, i: int) -> RequestSpec:
        """The i-th request of the stream (0-based)."""
        k = int(self._order(i // self.cycle)[i % self.cycle])
        rng = np.random.default_rng([self.seed, i, 2])
        prompt = rng.integers(0, self.vocab_size,
                              int(self._prompt_lens[k])).tolist()
        return RequestSpec(i, prompt, self._branch_lens[k].tolist())

    def due_s(self, i: int) -> Optional[float]:
        """Seconds after the traffic origin at which request i is due
        (open loop); None for a closed loop, where a request is due when
        it is sent."""
        if not self.open_loop:
            return None
        blocks, rest = divmod(i, self.cycle)
        per_block = float(self._gaps.sum())
        gaps = self._gaps[self._order_gaps(blocks)]
        return blocks * per_block + float(gaps[:rest + 1].sum())

    def _order_gaps(self, block: int) -> np.ndarray:
        key = ("gaps", block)
        if key not in self._orders:
            rng = np.random.default_rng([self.seed, block, 3])
            self._orders[key] = rng.permutation(self.cycle)
        return self._orders[key]
