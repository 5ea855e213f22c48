"""Milliseconds per fit spent tracing, lowering and compiling (or reading
the compilation cache): the union of JAX's compile-event time spans
recorded while the window's fits ran, over their number.  Layer:
estimator (`core/api.py` builds a new jitted program on every fit)."""

UNIT = "ms"


def read(run):
    spans = sorted(run.compile_spans)
    n = len(run.fits)
    if not n:
        return None
    total, end = 0.0, float("-inf")
    for s, e in spans:
        if e > end:
            total += e - max(s, end)
            end = e
    return 1e3 * total / n
