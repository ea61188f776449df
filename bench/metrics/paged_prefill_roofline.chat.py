"""The paged prefill attention kernel's share of its roofline: the least
time the traced chunks needed, over the kernel's time."""


def read(run):
    return run.roofline("prefill")
