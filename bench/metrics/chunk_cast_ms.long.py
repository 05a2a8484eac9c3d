"""Jitted programs: device time of the ops under the ``cast_params`` scope
(the f32 -> bf16 weight cast) per execution of the ``prefill_chunk``
program (``jit__prefill_chunk``) begun in the window, from the trace (ms).
Each chunk stalls the other slots, so it moves the tail of the token
gaps."""

from soibench import inside


def read(run):
    return inside.read(run, inside.scope_ms, "jit__prefill_chunk",
                       "cast_params")
