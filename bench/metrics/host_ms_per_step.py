"""Engine host layer: mean host time of a ``generate`` call in the window
(page backing, copy-on-write flush, page-map refresh, dispatch; the call
returns once the step is dispatched), from the benchmark's span around it
(ms)."""


def read(run):
    spans = run.spans("generate")
    if not spans:
        return None
    return 1e3 * sum(b - a for a, b in spans) / len(spans)
