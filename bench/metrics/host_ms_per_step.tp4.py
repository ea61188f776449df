"""Host time per engine step: the mean over the traced sub-window's
steps that began with a request queued or resident of the
``serve.step`` span less its ``.wait`` spans (``bench/spans.py``)."""

from bench import spans


def read(run):
    return spans.of_run(run).host_ms_per_step()
