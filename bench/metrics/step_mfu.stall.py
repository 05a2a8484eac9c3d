"""Whole prefill step: operations the SOI schedule requires for the
prompts prefilled in the window (prefix-cache hits excluded), over the
device time of the window's ``prefill_chunk`` executions times the chip's
peak bf16 FLOP/s (%)."""


def read(run):
    return run.program_mfu("jit__prefill_chunk", "prompt")
