"""Process start to the window's opening: weights, compile-cache loads,
warm-up, and the prefills the traffic needs before the window."""


def read(r):
    return r.setup_s
