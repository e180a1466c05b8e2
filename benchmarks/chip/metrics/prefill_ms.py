"""Mean device time of one run of the prefill program (``jit_prefill``,
batch 1, bucketed) in the traced window."""

PROGRAM = "jit_prefill"


def read(view):
    if view.trace is None:
        return None
    d = [e - s for n, s, e in view.trace["programs"] if n == PROGRAM]
    return 1e3 * sum(d) / len(d) if d else None
