"""Share of the traced sub-window in which no operation ran on the
device (mean over chips)."""


def read(run):
    return run.idle_share()
