"""Share of the traced sub-window in which a collective ran on a chip
and no other operation overlapped it, mean over chips
(``trace_reduce.Summary.collective_exposed_s``); None without a trace
of more than one chip."""


def read(run):
    t = run.trace
    if t is None or t.chips < 2:
        return None
    return 100.0 * t.collective_exposed_s / t.window_s
