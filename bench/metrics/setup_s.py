"""Seconds from the start of the process to the opening of the window:
making the weights, building the engine, loading or compiling every
program, warming the traffic's shapes and filling slots or prefix cache."""


def read(run):
    return run.setup_s
