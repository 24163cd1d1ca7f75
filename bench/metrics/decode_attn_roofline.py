"""Decode attention's share of its roofline, in percent: the least time
the chip could take for the attention the traced steps' decode rows need
(distinct K/V pages once, sartbench.roofline), over the device time of
the operations that implement it."""
from sartbench import roofline, trace

KERNELS = ("paged_attention",)


def read(run):
    if run.trace is None:
        return None
    lo, hi = run.trace_ns
    kernel = trace.kernel_ns(run.trace, KERNELS, lo, hi)
    steps = [s for s in run.traced_steps() if s.tables is not None]
    if not kernel or not steps:
        return None
    least = 0.0
    for s in steps:
        flops, nbytes = roofline.decode_attention_work(
            run.work, s.tables[0], s.tables[1], run.page_size)
        least += roofline.least_seconds(flops, nbytes, run.device_kind)
    return 100.0 * least / (kernel / 1e9)
