"""Median time to first token over every request due in the window, timed
from its due time (host clock). A request with no first token when the
window closes counts with its wait so far, and one that was refused with the
whole rest of the window, so a stall cannot shorten it.

The median and not the 95th percentile: the window holds 143 requests, and
the tail, the seventh-longest wait, swings with the timing of a few slot
waits from run to run (PERF.md)."""
import numpy as np


def read(view):
    waits = [
        (r.first if r.first is not None and r.first <= view.t_end else view.t_end) - r.due
        for r in view.records
    ]
    return 1e3 * float(np.median(waits)) if waits else None
