"""The paged decode attention kernel's share of its roofline: the least
time the traced steps' live rows needed, over the kernel's time."""


def read(run):
    return run.roofline("decode")
