"""Chunked-prefill attention's share of its roofline, in percent: the
least time for the traced steps' chunk lanes' attention over their paged
context, over the device time of the operations that implement it."""
from sartbench import roofline, trace

KERNELS = ("paged_flash_prefill",)


def read(run):
    if run.trace is None:
        return None
    lo, hi = run.trace_ns
    kernel = trace.kernel_ns(run.trace, KERNELS, lo, hi)
    lanes = [ln for s in run.traced_steps() for ln in s.lanes]
    if not kernel or not lanes:
        return None
    least = sum(roofline.least_seconds(
        *roofline.prefill_attention_work(run.work, pos0, n),
        run.device_kind) for pos0, n in lanes)
    return 100.0 * least / (kernel / 1e9)
