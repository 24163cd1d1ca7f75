"""A duck-typed proxy round the live ``Engine``, as
``repro.serving.FaultInjector`` puts one round it: the ``Scheduler`` drives
it through the engine's own interface, and every attribute it does not
override is the engine's.

It stamps the host clock at the entry and exit of each ``decode_step`` (the
step's token sync makes the exit a device boundary), stamps branch spawns,
ends a branch at its drawn length by writing ``eos_id`` as its newest
token (the path a sampled EOS takes through ``_check_completions``), keeps
the rewards the PRM head gave running branches, and gives the load
generator a hook before and after every step. With tracing on it
wraps the engine's calls in ``jax.profiler.TraceAnnotation`` host spans.
"""
from __future__ import annotations

import contextlib
import time
from typing import Callable, Dict, List, Tuple

import numpy as np


class WindowClosed(BaseException):
    """Ends the measured window from inside ``Scheduler.run``. A
    ``BaseException``, so that ``_decode_window``'s ``except Exception``
    does not count it as an engine fault."""


class EngineProxy:
    def __init__(self, engine, eos_id: int):
        self.inner = engine
        self.eos_id = eos_id
        self.span: Callable = no_span
        self.before_step: Callable[[], None] = lambda: None
        self.after_step: Callable[[float, float, Dict[int, int]], None] = \
            lambda t0, t1, out: None
        # branch_id -> (request_id, index among the request's branches)
        self.branch_of: Dict[int, Tuple[int, int]] = {}
        self.spawn_time: Dict[int, float] = {}
        self._spawned: Dict[int, int] = {}
        # request_id -> branch lengths, set at submission
        self.lengths: Dict[int, List[int]] = {}
        # branch_id -> (request_id, tokens as served) of each branch that
        # reached its drawn length; the last of them is what eos_id replaced
        self.served: Dict[int, Tuple[int, List[int]]] = {}
        # branch_id -> (tokens served, reward) each time the PRM head
        # scored the branch while it ran
        self.scores: Dict[int, List[Tuple[int, float]]] = {}

    def __getattr__(self, name):
        # only reached for names not set on the proxy itself
        return getattr(self.inner, name)

    def decode_step(self):
        self.before_step()
        t0 = time.perf_counter()
        with self.span("engine.decode_step"):
            out = self.inner.decode_step()
        t1 = time.perf_counter()
        for slot in out:
            h = self.inner.slots[slot]
            rid, idx = self.branch_of[h.branch_id]
            if len(h.tokens) >= self.lengths[rid][idx]:
                self.served[h.branch_id] = (rid, list(h.tokens))
                h.tokens[-1] = self.eos_id
                out[slot] = self.eos_id
        self.after_step(t0, t1, out)
        return out

    def spawn_branch(self, request_id, *args, **kwargs):
        h = self.inner.spawn_branch(request_id, *args, **kwargs)
        if h is not None:
            idx = self._spawned.get(request_id, 0)
            self._spawned[request_id] = idx + 1
            self.branch_of[h.branch_id] = (request_id, idx)
            self.spawn_time[h.branch_id] = time.perf_counter()
        return h

    def score_slots(self) -> np.ndarray:
        with self.span("prm.score"):
            rewards = self.inner.score_slots()
        for slot, h in enumerate(self.inner.slots):
            # a slot's hidden row is its last decode input: the branch's
            # token before the newest
            if h is not None and len(h.tokens) >= 2:
                self.scores.setdefault(h.branch_id, []).append(
                    (len(h.tokens), float(rewards[slot])))
        return rewards


def step_lanes(engine) -> List[Tuple[object, int]]:
    """(state, next_pos) of each prefill waiting for chunks, read before a
    step; after it, the states whose ``next_pos`` moved rode the step."""
    return [(st, st.next_pos) for st in engine._pending_prefills]


def lanes_run(before: List[Tuple[object, int]]) -> List[Tuple[int, int]]:
    """(pos0, valid rows) of each chunk lane that rode the step."""
    return [(pos0, st.next_pos - pos0) for st, pos0 in before
            if st.next_pos != pos0]


def no_span(name: str):
    return contextlib.nullcontext()


def profiler_span(name: str):
    import jax
    return jax.profiler.TraceAnnotation(name)


