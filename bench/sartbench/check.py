"""Whether what the timed path served is correct.

Once the window has closed and the program's state is freed, a sample of
the requests, drawn from the seed and holding the one with the most served
tokens, goes through the plain float32 reference of the configuration:
once over each prompt with each distinct branch's served tokens. The
sample is drawn from the requests finished in the window; where fewer than
the sample's size finished, from the requests whose branches were served
in it (a branch still running at the close has served its tokens so far).
Two numbers are compared:

* ``token_gap``: the widest gap by which a served token's reference logit
  lies below the reference's best logit at its position. The served tokens
  are greedy, so a sound program serves the reference's best up to
  rounding. The first token of a branch comes from the chunked prefill
  (``paged_flash_prefill``), every later one from a paged decode step.
* ``reward_gap``: the widest difference between a reward the PRM head
  gave (to a running branch each time the scheduler scored it for
  pruning, and to a finished branch) and the head on the reference's
  hidden state at the same position.

The control puts the reference in the program's place at fp8 (e4m3's four
significant bits; the configurations serve bf16): at each position of the
same tokens, the token fp8 puts first and its gap below the float32 best,
and the reward head on fp8's hidden state. It goes through the same limits
as the program and has to come out as not correct.
"""
from __future__ import annotations

import dataclasses
import importlib.util
from typing import Dict, List, Optional, Tuple

import numpy as np

from .spec import BENCH

PAD_TO = 512          # reference sequence lengths round up to this
CONTROL = "fp8"       # the precision the control computes in


@dataclasses.dataclass
class Branch:
    request_id: int
    prompt: List[int]
    tokens: List[int]              # served tokens
    reward: Optional[float]        # the PRM head's reward at completion
    scores: List[Tuple[int, float]] = dataclasses.field(
        default_factory=list)      # (tokens, reward) while it ran


def load_reference(config: dict):
    name = config["reference"]
    path = BENCH / "configs" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"sartbench_ref_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def served_branches(sch, proxy, eos_id: int) -> Dict[int, List[Branch]]:
    """request_id -> its branches that reached their drawn length, with
    the tokens as served and the reward the scheduler recorded, and those
    still running in a slot, with their tokens so far (no reward)."""
    out: Dict[int, List[Branch]] = {}
    for h in proxy.inner.slots:
        if h is not None and h.tokens:
            req = sch.requests[h.request_id]
            out.setdefault(h.request_id, []).append(
                Branch(h.request_id, list(req.prompt), list(h.tokens), None,
                       proxy.scores.get(h.branch_id, [])))
    for bid, (rid, tokens) in proxy.served.items():
        req = sch.requests[rid]
        reward = None
        for toks, r, _ in req.completed:
            if toks[:-1] == tokens[:-1] and toks[-1] == eos_id:
                reward = float(r)
                break
        out.setdefault(rid, []).append(
            Branch(rid, list(req.prompt), tokens, reward,
                   proxy.scores.get(bid, [])))
    return out


def sample(by_request: Dict[int, List[Branch]], finished: List[int],
           seed: int, k: int) -> List[int]:
    """k request ids: the one with the most served tokens, then others
    drawn from the seed; from the ``finished`` requests where there are k,
    else from every request with a served branch."""
    ids = [r for r in finished if r in by_request]
    if len(ids) < k:
        ids = sorted(by_request)
    if not ids:
        return []
    size = {r: sum(len(b.tokens) for b in by_request[r]) for r in ids}
    longest = max(ids, key=lambda r: (size[r], -r))
    rest = sorted(r for r in ids if r != longest)
    rng = np.random.default_rng([int(seed), 7])
    pick = rng.choice(len(rest), size=min(k - 1, len(rest)),
                      replace=False) if rest else []
    picked = [longest] + [rest[i] for i in sorted(pick)]
    # the reward head is checked on finished branches: where the draw
    # holds none, its last request gives way to one that has
    rewarded = sorted(r for r in by_request if any(
        b.reward is not None or b.scores for b in by_request[r]))
    if rewarded and not set(picked) & set(rewarded):
        other = [r for r in rewarded if r not in picked]
        picked[-1 if len(picked) == k else len(picked):] = [
            other[int(rng.integers(len(other)))]]
    return picked


def distinct(branches: List[Branch]) -> List[Tuple[Branch, List[Branch]]]:
    """Group a request's branches under the longest whose tokens start
    with theirs: one reference run per group."""
    groups: List[Tuple[Branch, List[Branch]]] = []
    for b in sorted(branches, key=lambda b: -len(b.tokens)):
        for head, members in groups:
            if head.tokens[:len(b.tokens)] == b.tokens:
                members.append(b)
                break
        else:
            groups.append((b, [b]))
    return groups


def compare(config: dict, params, prm, branches: List[Branch],
            control: bool = False) -> Dict[str, float]:
    """The compared numbers over ``branches`` (program), or over the same
    tokens and positions with fp8 in the program's place (``control``):
    the gap of the token fp8 puts first, and the reward on fp8's hidden
    state."""
    import jax.numpy as jnp
    ref = load_reference(config)
    model = config["model"]
    token_gap, reward_gap, n_tokens, n_rewards = 0.0, 0.0, 0, 0
    for head, members in distinct(branches):
        seq = head.prompt + head.tokens
        p, t = len(head.prompt), len(head.tokens)
        padded = np.asarray(seq + [0] * (-len(seq) % PAD_TO), np.int32)
        h = ref.hidden(model, params, padded, "f32")
        # position p-1+k predicts served token k
        rows = h[p - 1:p - 1 + t]
        served = np.asarray(head.tokens)
        h_alt = ref.hidden(model, params, padded, CONTROL) if control \
            else None
        for s, logits in ref.logit_blocks(model, params, rows, "f32"):
            best = jnp.max(logits, axis=-1)
            if control:
                alt = ref.logit_blocks(
                    model, params, h_alt[p - 1 + s:p - 1 + s + len(best)],
                    CONTROL)
                _, alt_logits = next(alt)
                pick = jnp.argmax(alt_logits, axis=-1)
            else:
                pick = jnp.asarray(served[s:s + len(best)])
            at = jnp.take_along_axis(logits, pick[:, None], axis=-1)[:, 0]
            token_gap = max(token_gap, float(jnp.max(best - at)))
            n_tokens += int(len(best))
        # a reward read at n served tokens is the head on the hidden state
        # of token n-1, at position p-2+n
        scored = [(n, r) for b in members for n, r in b.scores]
        scored += [(len(b.tokens), b.reward) for b in members
                   if b.reward is not None]
        if scored:
            pos = np.asarray([p - 2 + n for n, _ in scored])
            want = ref.reward(prm, h[pos])
            got = (ref.reward(prm, h_alt[pos]) if control
                   else jnp.asarray([r for _, r in scored]))
            reward_gap = max(reward_gap, float(jnp.max(jnp.abs(want - got))))
            n_rewards += len(scored)
    return {"token_gap": token_gap, "tokens_compared": n_tokens,
            "reward_gap": reward_gap, "rewards_compared": n_rewards}
