"""Median prefill as the request feels it: from the start of the body of its
first prefill (``RequestHandle.prefill_start_t``) to the hand-off of the
result to the join queue (``prefill_done_t``), host clock. The device time of
the prefill plus its wait behind a decode tick that holds the chip. A program
whose handles carry no such marks reads nothing."""
import numpy as np


def read(view):
    walls = []
    for r in view.records:
        start = getattr(r.handle, "prefill_start_t", None)
        done = getattr(r.handle, "prefill_done_t", None)
        if start is not None and done is not None:
            walls.append(done - start)
    return 1e3 * float(np.median(walls)) if walls else None
