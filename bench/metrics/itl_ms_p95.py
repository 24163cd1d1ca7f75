"""95th percentile of the gap between consecutive tokens of a branch, over
every branch-token produced in the window (a branch's first gap runs from
its spawn), on the host clock."""
from sartbench.stats import percentile


def read(run):
    p = percentile(run.rec.itl, 95)
    return None if p is None else p * 1e3
