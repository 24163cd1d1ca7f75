"""Share of the traced stretch, in percent, in which no operation ran on
the device: 1 minus the union of device op intervals over the stretch."""
from sartbench import trace


def read(run):
    if run.trace is None or not trace.has_device_ops(run.trace):
        return None
    lo, hi = run.trace_ns
    return 100.0 * (1.0 - trace.busy_ns(run.trace, lo, hi) / (hi - lo))
