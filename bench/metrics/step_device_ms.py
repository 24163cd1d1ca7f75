"""Device busy time per step over the traced stretch."""
from sartbench import trace


def read(run):
    if run.trace is None or not trace.has_device_ops(run.trace):
        return None
    lo, hi = run.trace_ns
    n = sum(1 for e in trace.clip(run.trace["host"], lo, hi)
            if e[0] == "engine.decode_step")
    if not n:
        return None
    return trace.busy_ns(run.trace, lo, hi) / n / 1e6
