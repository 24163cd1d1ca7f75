"""The engine's busy host time per step: the program's ``engine.prepare``,
``engine.dispatch`` and ``engine.harvest`` spans (every phase of
``Engine.decode_step`` but its wait for the device, ``engine.sync``) in
the traced stretch, over the ``engine.step`` spans in it."""
from sartbench import trace

PHASES = ("engine.prepare", "engine.dispatch", "engine.harvest")


def read(run):
    if run.trace is None:
        return None
    lo, hi = run.trace_ns
    spans = trace.program(run.trace, lo, hi)
    steps = sum(1 for e in spans or () if e[0] == "engine.step")
    if not steps:
        return None
    busy = sum(d for name, _, d, _ in spans if name in PHASES)
    return busy / steps / 1e6
