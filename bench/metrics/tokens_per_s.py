"""Tokens that decode steps produced in the window, over the window's
seconds."""
from sartbench.stats import window_steps


def read(run):
    rec = run.rec
    return sum(s.tokens for s in window_steps(run)) / (rec.t_close
                                                       - rec.t_open)
