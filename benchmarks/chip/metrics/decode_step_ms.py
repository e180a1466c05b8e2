"""Mean device time of one decode tick (``jit__ptick``: page gather,
vmapped decode step, scatter) in the traced window."""

PROGRAM = "jit__ptick"


def read(view):
    if view.trace is None:
        return None
    d = [e - s for n, s, e in view.trace["programs"] if n == PROGRAM]
    return 1e3 * sum(d) / len(d) if d else None
