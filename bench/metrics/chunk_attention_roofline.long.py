"""Kernels: the least time of the window's ``chunk_attention`` calls
(copied cost formulas over the peak table) over the time the trace gives
them (%). Prefill chunks stall the decode of the other slots."""

from soibench.roofline import share


def read(run):
    return share(run, "chunk_attention")
