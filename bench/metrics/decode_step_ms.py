"""Jitted programs: device time per execution of the engine's ``generate``
program (``jit__gen``), from the trace of the window (ms)."""


def read(run):
    return run.program_ms("jit__gen")
