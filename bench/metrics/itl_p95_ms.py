"""95th percentile of every gap between consecutive output tokens of a
request that ended in the window (ms)."""

from soibench.window import percentile


def read(run):
    p = percentile(run.token_gaps(), 95)
    return None if p is None else 1e3 * p
