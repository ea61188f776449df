"""Output tokens the client saw in the window, over the window's length."""


def read(run):
    return run.tokens_seen(run.start, run.end) / (run.end - run.start)
