"""Output tokens delivered inside the window, over the window (host clock)."""


def read(view):
    return sum(len(r.times) for r in view.records) / view.seconds
