"""Process Reward Models.

The paper scores partial reasoning branches with Qwen2.5-Math-PRM-7B. In this
reproduction the PRM is pluggable behind one protocol — ``score(request,
handles) -> rewards in [0,1]`` — with two implementations:

  * ``RewardHeadPRM`` — a linear+sigmoid head over the serving model's own
    last hidden state (returned by every decode step for free). Trained on
    synthetic CoT data by ``repro.training``. This is the live end-to-end
    path; it adapts the paper's separate-PRM-server design to a co-located
    TPU-friendly head.
  * ``OraclePRM`` — task-aware reward for controlled experiments: fraction of
    correct reasoning steps in the branch so far, plus configurable noise.
    Lets experiments isolate scheduler behaviour from PRM quality.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np


# --------------------------------------------------------------- reward head


def init_prm_head(key, d_model: int, hidden_dim: int = 64) -> dict:
    """Two-layer MLP reward head (a linear head underfits the step-
    correctness signal — measured BCE plateau near ln 2)."""
    k1, k2 = jax.random.split(key)
    return {
        "w1": jax.random.normal(k1, (d_model, hidden_dim)) * (d_model ** -0.5),
        "b1": jnp.zeros((hidden_dim,)),
        "w2": jax.random.normal(k2, (hidden_dim,)) * (hidden_dim ** -0.5),
        "b2": jnp.zeros(()),
    }


def reward_logit(params: dict, hidden) -> jax.Array:
    """Pre-sigmoid reward head output for ``hidden [..., D] -> [...]``;
    dispatches on the param pytree shape (MLP vs legacy linear head)."""
    if "w1" in params:
        h = jax.nn.tanh(hidden @ params["w1"] + params["b1"])
        return h @ params["w2"] + params["b2"]
    return hidden @ params["w"] + params["b"]   # legacy linear head


@jax.jit
def reward_from_hidden(params: dict, hidden) -> jax.Array:
    """hidden [..., D] -> rewards [...] in (0, 1)."""
    with jax.named_scope("prm_head"):
        return jax.nn.sigmoid(reward_logit(params, hidden))


def prm_head_loss(params: dict, hidden, labels) -> jax.Array:
    """Binary cross-entropy on per-step goodness labels."""
    logit = reward_logit(params, hidden)
    return jnp.mean(
        jnp.maximum(logit, 0) - logit * labels +
        jnp.log1p(jnp.exp(-jnp.abs(logit))))


# ------------------------------------------------------------------ protocols


class PRM:
    """Scores live branches of a request. Higher = more right-thinking."""

    def score(self, request, handles: Sequence) -> List[float]:
        """Reward in [0, 1] per handle, aligned with ``handles`` order."""
        raise NotImplementedError


class RewardHeadPRM(PRM):
    """Reads the engine's cached last-hidden rows for the handles' slots."""

    def __init__(self, engine):
        self.engine = engine

    def score(self, request, handles) -> List[float]:
        """Index the engine's per-slot reward vector by handle slot (one
        host sync per pruning round, not per handle)."""
        rewards = self.engine.score_slots()  # [max_slots]
        return [float(rewards[h.slot]) for h in handles]


class OraclePRM(PRM):
    """Deterministic task-aware reward with optional noise.

    ``grader(request, tokens) -> float in [0,1]`` judges the partial branch;
    the synthetic-task grader lives in ``repro.data.tasks``.
    """

    def __init__(self, grader: Callable, noise: float = 0.0, seed: int = 0):
        self.grader = grader
        self.noise = noise
        self._rng = np.random.default_rng(seed)

    def score(self, request, handles) -> List[float]:
        """Grade each handle's partial token stream, clipping the noised
        reward back into [0, 1]."""
        out = []
        for h in handles:
            r = float(self.grader(request, h.tokens))
            if self.noise:
                r = float(np.clip(r + self._rng.normal(0, self.noise), 0, 1))
            out.append(r)
        return out
