"""95th percentile, over the requests due in the window, of the first
token's arrival on the host minus the time the request was due (ms)."""

from soibench.window import percentile


def read(run):
    p = percentile(run.ttfts(), 95)
    return None if p is None else 1e3 * p
