"""Jitted programs: device time of the ops under the ``cast_params`` scope
(the f32 -> bf16 weight cast) per execution of the ``generate`` program
(``jit__gen``) begun in the window, from the trace (ms)."""

from soibench import inside


def read(run):
    return inside.read(run, inside.scope_ms, "jit__gen", "cast_params")
