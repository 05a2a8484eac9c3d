"""Median of every gap between consecutive output tokens of a request, as
the client's loop saw them, over all gaps that ended in the window (ms)."""

from soibench.window import percentile


def read(run):
    p = percentile(run.token_gaps(), 50)
    return None if p is None else 1e3 * p
