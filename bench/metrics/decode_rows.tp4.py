"""Decoding slots per decode call, as the engine counts them: the mean
``rows`` stat of the ``serve.decode`` spans in the traced sub-window's
steps (``bench/spans.py``)."""

from bench import spans


def read(run):
    return spans.of_run(run).mean_stat("serve.decode", "rows")
