"""Mean number of requests that gained a decoded token per decode call
in the window (each engine step makes at most one decode call)."""


def read(run):
    return run.decode_occupancy()
