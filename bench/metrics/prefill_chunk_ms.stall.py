"""Jitted programs: device time per execution of the engine's
``prefill_chunk`` program (``jit__prefill_chunk``), from the trace of the
window (ms). Each prefill between two decode steps stalls the other
slots, so it moves the tail of the token gaps."""


def read(run):
    return run.program_ms("jit__prefill_chunk")
