"""Kernels: the least time of the window's ``paged_decode_attention``
calls (their operations over the peak FLOP/s or their HBM bytes over the
peak bandwidth, whichever is larger, from the copied cost formulas) over
the time the trace gives them (%)."""

from soibench.roofline import share


def read(run):
    return share(run, "paged_decode_attention")
