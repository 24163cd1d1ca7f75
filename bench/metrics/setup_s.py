"""Set-up time: process start to the window's opening (weights drawn on
the device, engine built, step programs compiled or loaded from the
persistent cache, and the cell's own traffic run for its warm stretch)."""


def read(run):
    return run.setup_s
