"""Helpers the metric readers share over one window's records."""
from __future__ import annotations

from typing import Optional

import numpy as np


def percentile(values, q: float) -> Optional[float]:
    """The q-th percentile (linear interpolation), None without samples."""
    if len(values) == 0:
        return None
    return float(np.percentile(np.asarray(values, np.float64), q))


def window_steps(run):
    """Steps that ended inside the window."""
    rec = run.rec
    return [s for s in rec.steps if rec.in_window(s.t1)]


def counter_sum(run, steps, name: str) -> Optional[int]:
    """A program counter's change summed over ``steps`` (``Step.counts``);
    None where the engine has no counter of that name."""
    if name not in run.rec.counters_close:
        return None
    return sum(s.counts.get(name, 0) for s in steps)
