"""Device time per chunked-prefill program in the traced sub-window."""


def read(run):
    return run.program_ms("prefill")
