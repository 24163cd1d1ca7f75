"""Scheduler host time per step: the traced stretch's time outside
``engine.decode_step`` spans and outside waits for arrivals, over the steps
in it (includes the PRM's ``score_slots`` and the benchmark's own per-step
bookkeeping, tens of microseconds)."""
from sartbench import trace


def read(run):
    if run.trace is None:
        return None
    lo, hi = run.trace_ns
    steps = [e for e in trace.clip(run.trace["host"], lo, hi)
             if e[0] == "engine.decode_step"]
    if not steps:
        return None
    idle = trace.span_ns(run.trace, "idle.wait_arrival", lo, hi)
    outside = (hi - lo) - sum(d for _, _, d in steps) - idle
    return outside / len(steps) / 1e6
