"""Engine host layer: mean duration of the engine's own ``engine.generate``
span over the steps begun in the window (page backing, copy-on-write
flush, page-map refresh, dispatch), from the trace (ms). The program times
it from inside; ``host_ms_per_step`` is the benchmark's span around the
call."""

from soibench import inside


def read(run):
    return inside.read(run, inside.host_ms)
