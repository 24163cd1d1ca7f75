"""The step program's share of its roofline, in percent: the least time
the chip could take for a traced step (its useful FLOPs and the least
bytes it moves, sartbench.roofline.step_bytes: weights read once, K/V of
the distinct tokens attended and of the new tokens, Mamba-2 state, the
routed experts the program's counter says the step reached), over the
device busy time inside an ``engine.decode_step`` span, each as a mean
over the traced stretch. The bytes are a lower bound, so the share stays
under 100%; unlike a kernel's share it sees every copy and re-layout the
step makes."""
from sartbench import roofline, trace


def read(run):
    if run.trace is None or not trace.has_device_ops(run.trace):
        return None
    lo, hi = run.trace_ns
    spans = [e for e in trace.clip(run.trace["host"], lo, hi)
             if e[0] == "engine.decode_step"]
    steps = [s for s in run.traced_steps() if s.tables is not None]
    busy = trace.device_ns_within(run.trace, spans)
    if not steps or not busy:
        return None
    least = sum(roofline.least_seconds(
        roofline.step_model_flops(run.work, s.rows, s.ctx, s.lanes),
        roofline.step_bytes(run.work, s.tables[0], s.tables[1], s.lanes,
                            run.page_size,
                            roofline.experts_reached(run.work, s.counts)),
        run.device_kind) for s in steps)
    return 100.0 * (least / len(steps)) / (busy / len(spans) / 1e9)
