"""95th percentile of every gap between successive tokens the client
saw, across all requests that count (a fused horizon's burst gives gaps
of 0)."""

from bench import drive


def read(run):
    p = drive.percentile(run.itl_gaps(), 95)
    return None if p is None else 1000.0 * p
