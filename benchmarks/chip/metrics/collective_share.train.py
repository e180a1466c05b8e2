"""Exposed collective time on chip 0, in %: the time collective ops run while
no compute op does, over the device time of chip 0's whole train steps in
the trace."""


def read(view):
    if view.trace is None or view.trace["step_device_s"] <= 0:
        return None
    return 100.0 * view.trace["exposed_collective_s"] / view.trace["step_device_s"]
