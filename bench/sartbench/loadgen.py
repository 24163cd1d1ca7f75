"""Drive ``Scheduler.run`` over the proxied engine for a warm stretch and
a measured window, on the host clock.

Open loop: each request is due at its scheduled time after the traffic
origin and is submitted (``arrival=sch.clock``) at the first step boundary
after that; when the scheduler has nothing left it returns, and the load
generator sleeps until the next due time. Closed loop: before every step
it tops the requests waiting for admission back up to the mix's backlog, each
due when it is sent. The window closes by raising ``WindowClosed`` before
the next step: in-flight requests are not drained.

Every scheduler tick is mapped to the host time at which the step that
produced it ended, so the scheduler's tick stamps (``first_service``,
``first_branch``, ``finish``) become host times.

After every step the load generator reads the engine's cumulative
counters (``engine_counters``) and keeps on the step's record what moved
in it (``Step.counts``): a reader sums a counter over the steps it reads,
so a counter the program adds reaches a reader file with no edit here.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Callable, Dict, List, Optional, Tuple

import numpy as np

from .proxy import (EngineProxy, WindowClosed, lanes_run, no_span,
                    step_lanes)


def engine_counters(engine) -> Dict[str, int]:
    """The engine's cumulative counters by name: what ``engine.counters()``
    returns where the engine has it, else every public attribute of the
    engine that holds an integer (``rows_computed``, ``rows_useful``,
    ``attn_blocks_run``, ...)."""
    read = getattr(engine, "counters", None)
    items = read().items() if callable(read) else (
        (k, v) for k, v in vars(engine).items() if not k.startswith("_"))
    return {k: int(v) for k, v in items
            if isinstance(v, (int, np.integer)) and not isinstance(v, bool)}


@dataclasses.dataclass
class Step:
    t0: float                      # host clock at decode_step entry
    t1: float                      # ... and exit
    rows: int                      # decode rows (active slots)
    ctx: int                       # sum over decode rows of attended tokens
    lanes: List[Tuple[int, int]]   # (pos0, valid rows) of each chunk lane
    tokens: int                    # tokens the step produced
    used_pages: int                # KV pages in use after the step
    tables: Optional[tuple] = None  # (block tables, lengths) when traced
    # the engine's counters that moved in the step, by how much
    counts: Dict[str, int] = dataclasses.field(default_factory=dict)


@dataclasses.dataclass
class Records:
    t_origin: float                # traffic time 0 (host clock)
    t_open: float                  # window opens
    t_close: float                 # window closes
    steps: List[Step]
    itl: List[float]               # gaps whose token came in the window
    tick_time: Dict[int, float]    # scheduler clock -> host time
    due: Dict[int, float]          # request_id -> host time due
    late: List[float]              # submit time minus due time
    trace_span: Optional[Tuple[float, float]] = None  # profiled stretch
    # the engine's counters after the last step that ended before the
    # window opened, and after the last that ended in it
    counters_open: Dict[str, int] = dataclasses.field(default_factory=dict)
    counters_close: Dict[str, int] = dataclasses.field(default_factory=dict)

    def in_window(self, t: float) -> bool:
        return self.t_open <= t <= self.t_close

    def host_time(self, tick: Optional[int]) -> Optional[float]:
        """Host time of a scheduler tick: the end of the step that made
        the clock reach it (the latest such step at or before it)."""
        if tick is None or tick < 0:
            return None
        while tick not in self.tick_time and tick > 0:
            tick -= 1
        return self.tick_time.get(tick)


class LoadGen:
    """One run: ``run(warm_s, seconds)`` returns the ``Records``."""

    def __init__(self, sch, proxy: EngineProxy, traffic, *,
                 trace: Optional[Tuple[float, float]] = None,
                 start_trace: Callable = None, stop_trace: Callable = None,
                 record_tables: bool = False, span=None):
        self.sch = sch
        self.proxy = proxy
        self.traffic = traffic
        self.trace = trace             # (start, stop) s after window opens
        self.start_trace = start_trace
        self.stop_trace = stop_trace
        self.record_tables = record_tables
        self.span = span
        self._tracing = False
        self._sched_span = None
        self._next = 0                 # next request index to submit
        self._last_tok: Dict[int, float] = {}
        self._pending_lanes = []
        proxy.before_step = self._before_step
        proxy.after_step = self._after_step

    # ---------------------------------------------------------- submission
    def _submit(self, now: float) -> None:
        tr, rec = self.traffic, self.rec
        if tr.open_loop:
            while True:
                due = rec.t_origin + tr.due_s(self._next)
                if due > now:
                    return
                self._send(due, now)
        else:
            while len(self.sch.request_queue) < tr.waiting:
                self._send(now, now)

    def _send(self, due: float, now: float) -> None:
        spec = self.traffic.request(self._next)
        self._next += 1
        req = self.sch.submit(spec.prompt, arrival=self.sch.clock)
        self.rec.due[req.request_id] = due
        self.rec.late.append(now - due)
        self.proxy.lengths[req.request_id] = spec.branch_lengths

    def _next_due(self) -> float:
        return self.rec.t_origin + self.traffic.due_s(self._next)

    # --------------------------------------------------------- step hooks
    def _close_sched_span(self) -> None:
        if self._sched_span is not None:
            self._sched_span.__exit__(None, None, None)
            self._sched_span = None

    def _before_step(self) -> None:
        self._close_sched_span()
        now = time.perf_counter()
        rec = self.rec
        if now >= rec.t_close:
            raise WindowClosed()
        if self.trace is not None:
            start, stop = (rec.t_open + self.trace[0],
                           rec.t_open + self.trace[1])
            if not self._tracing and start <= now < stop and \
                    rec.trace_span is None:
                self.start_trace()
                self._tracing = True
                rec.trace_span = (time.perf_counter(), None)
            elif self._tracing and now >= stop:
                self._end_trace()
        self._submit(now)
        self._pending_lanes = step_lanes(self.proxy.inner)

    def _end_trace(self) -> None:
        t = time.perf_counter()
        self.stop_trace()
        self._tracing = False
        self.rec.trace_span = (self.rec.trace_span[0], t)

    def _after_step(self, t0: float, t1: float, out: Dict[int, int]) -> None:
        rec, eng = self.rec, self.proxy.inner
        inside = rec.t_open <= t1 <= rec.t_close
        for slot in out:
            bid = eng.slots[slot].branch_id
            prev = self._last_tok.get(bid)
            if prev is None:
                prev = self.proxy.spawn_time[bid]
            self._last_tok[bid] = t1
            if inside:
                rec.itl.append(t1 - prev)
        active = eng._active
        tables = None
        if self.record_tables and self._tracing:
            tables = (eng._block_tables[active].copy(),
                      eng._lengths[active].copy())
        counters, prev = engine_counters(eng), self._counters
        self._counters = counters
        if t1 < rec.t_open:
            rec.counters_open = counters
        if t1 <= rec.t_close:
            rec.counters_close = counters
        rec.steps.append(Step(
            t0, t1, int(active.sum()), int(eng._lengths[active].sum())
            + int(active.sum()), lanes_run(self._pending_lanes), len(out),
            eng.allocator.used_pages, tables,
            {k: v - prev.get(k, 0) for k, v in counters.items()
             if v != prev.get(k, 0)}))
        rec.tick_time[self.sch.clock + 1] = t1
        if self.span is not None:
            self._sched_span = self.span("scheduler")
            self._sched_span.__enter__()

    # ------------------------------------------------------------- run
    def run(self, warm_s: float, seconds: float) -> Records:
        t = time.perf_counter()
        self.rec = Records(t_origin=t, t_open=t + warm_s,
                           t_close=t + warm_s + seconds, steps=[], itl=[],
                           tick_time={0: t}, due={}, late=[])
        rec = self.rec
        self._counters: Dict[str, int] = engine_counters(self.proxy.inner)
        rec.counters_open = rec.counters_close = self._counters
        try:
            while True:
                now = time.perf_counter()
                if now >= rec.t_close:
                    break
                self._submit(now)
                if self.traffic.open_loop and self._idle():
                    wake = min(self._next_due(), rec.t_close)
                    with (self.span or no_span)("idle.wait_arrival"):
                        time.sleep(max(wake - time.perf_counter(), 0.0))
                    continue
                self.sch.run(max_steps=2 ** 62)
                self._close_sched_span()
        except WindowClosed:
            pass
        finally:
            self._close_sched_span()
            if self._tracing:
                self._end_trace()
        return rec

    def _idle(self) -> bool:
        return all(r.done or r.quarantined
                   for r in self.sch.requests.values())


def request_times(rec: Records, sch) -> List[dict]:
    """Per request: host times due, first service, first token, finish."""
    out = []
    for rid, req in sch.requests.items():
        out.append(dict(
            request_id=rid, due=rec.due[rid],
            first_service=rec.host_time(req.first_service),
            first_token=rec.host_time(req.first_branch),
            finish=rec.host_time(req.finish) if req.done else None))
    return out


