"""Median wait from a request's due time to the start of its prefill task,
``prefill:<rid>`` in the engine's pool tracer (host clock): admission, the
admit heap and the pool's prefill band."""
import numpy as np


def read(view):
    starts = {}
    for name, s, _e in view.spans:
        if name.startswith("prefill:"):
            starts.setdefault(int(name.split(":")[1]), s)
    waits = [
        starts[r.handle.rid] - r.due
        for r in view.records
        if r.handle is not None and r.handle.rid in starts
    ]
    return 1e3 * float(np.median(waits)) if waits else None
