"""Median wait of a computed first token to be delivered: from the hand-off
of a request's first prefill to the join queue (``RequestHandle.
prefill_done_t``) to its first token (``first_token_t``, pushed when a decode
tick joins it to a slot), over requests whose first token came by the
window's close, host clock. The tick in flight, and a wait for a free slot.
A program whose handles carry no such mark reads nothing."""
import numpy as np


def read(view):
    waits = []
    for r in view.records:
        done = getattr(r.handle, "prefill_done_t", None)
        if done is not None and r.first is not None and r.first <= view.t_end:
            waits.append(r.first - done)
    return 1e3 * float(np.median(waits)) if waits else None
