"""Median host time per decode tick in which the chip has no tick queued:
from the end of a tick's ``serve.tick.sync`` span (the host holds the new
tokens) to the end of the next ``serve.tick.dispatch`` span (the next tick is
enqueued), over syncs that end in the window; the engine's tracer spans, host
clock. A pair with a ``tick-entry`` span between them straddles a drain and
restart of the tick loop, time with no work rather than host work, and is
left out. A program without these spans reads nothing."""
from bisect import bisect_right

import numpy as np


def read(view):
    syncs = sorted(e for n, _s, e in view.spans if n == "serve.tick.sync")
    dispatched = sorted(e for n, _s, e in view.spans if n == "serve.tick.dispatch")
    restarts = sorted(s for n, s, _e in view.spans if n == "tick-entry")
    gaps = []
    for t in syncs:
        if not view.t0 <= t <= view.t_end:
            continue
        i = bisect_right(dispatched, t)
        if i == len(dispatched):
            continue
        j = bisect_right(restarts, t)
        if j < len(restarts) and restarts[j] < dispatched[i]:
            continue
        gaps.append(dispatched[i] - t)
    return 1e3 * float(np.median(gaps)) if gaps else None
