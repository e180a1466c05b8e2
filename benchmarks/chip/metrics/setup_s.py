"""Set-up: process start to the opening of the measured window (host clock).

Loading, making the weights, compiling or loading compiled programs, and
warming up every shape the window uses all land here."""


def read(view):
    return view.setup_s
