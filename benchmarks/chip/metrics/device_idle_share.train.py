"""Share of the span of chip 0's whole train steps in the trace (first one's
start to last one's end) in which no operation ran on chip 0, in %."""


def read(view):
    if view.trace is None or view.trace["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - view.trace["busy0_s"] / view.trace["window_s"])
