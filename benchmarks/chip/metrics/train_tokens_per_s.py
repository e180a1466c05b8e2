"""Tokens of the train steps taken in the window, over the window (host
clock): the window runs from the first step's dispatch until the last step
has been blocked on."""


def read(view):
    return view.steps * view.tokens_per_step / view.seconds
