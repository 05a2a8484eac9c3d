"""Device: share of the traced window's time with at least one request in
flight in which no operation ran on the chip (%)."""


def read(run):
    s = run.trace and run.trace.idle_share
    return None if s is None else 100.0 * s
