"""Device time per decode program (the fused horizon or a single step)
in the traced sub-window."""


def read(run):
    return run.program_ms("decode")
