"""setup_s: process start to window start (build, weights, compile or
cache load, warm-up of every shape, warm-in of the traffic)."""


def read(w):
    return w.setup_s
