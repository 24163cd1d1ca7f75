"""Engine host time per step: ``engine.decode_step`` span time minus the
device busy time inside those spans, over the steps in the traced
stretch."""
from sartbench import trace


def read(run):
    if run.trace is None or not trace.has_device_ops(run.trace):
        return None
    lo, hi = run.trace_ns
    steps = [e for e in trace.clip(run.trace["host"], lo, hi)
             if e[0] == "engine.decode_step"]
    if not steps:
        return None
    wall = sum(d for _, _, d in steps)
    busy = trace.device_ns_within(run.trace, steps)
    return (wall - busy) / len(steps) / 1e6
