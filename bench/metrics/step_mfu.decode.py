"""Whole decode step: operations the SOI schedule requires for the tokens
decoded in the window, over the device time of the window's ``generate``
executions times the chip's peak bf16 FLOP/s (%)."""


def read(run):
    return run.program_mfu("jit__gen", "decode")
