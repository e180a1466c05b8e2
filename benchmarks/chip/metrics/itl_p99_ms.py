"""99th percentile of every gap between consecutive streamed tokens of every
request, over the gaps that close inside the window (host clock): the gaps
that span a prefill, which a streaming user sees as a stall."""
import numpy as np


def read(view):
    gaps = [b - a for r in view.records for a, b in zip(r.times, r.times[1:])]
    return 1e3 * float(np.percentile(gaps, 99)) if gaps else None
