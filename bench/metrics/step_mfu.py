"""Model FLOPs of the useful rows (decode rows and valid chunk rows, no
padding, no recompute) of every step in the window, over the window's
seconds times the chip's bf16 peak, in percent."""
from sartbench import roofline
from sartbench.stats import window_steps


def read(run):
    steps = window_steps(run)
    if not steps:
        return None
    flops = sum(roofline.step_model_flops(run.work, s.rows, s.ctx, s.lanes)
                for s in steps)
    peak = roofline.peaks(run.device_kind)["bf16_flops"]
    return 100.0 * flops / ((run.rec.t_close - run.rec.t_open) * peak)
