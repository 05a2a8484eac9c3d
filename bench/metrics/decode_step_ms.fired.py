"""SOI middle: device time per execution of the ``generate`` program
(``jit__gen``) on the window's steps whose ``engine.generate`` span says
the compressed middle fires (``mid=1``: some active slot at phase 0),
from the trace (ms)."""

from soibench import inside


def read(run):
    return inside.read(run, inside.step_ms, 1)
