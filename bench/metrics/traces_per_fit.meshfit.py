"""Traces of `AAKMeans.fit`'s mesh program per fit: the change in
``repro.core.api.mesh_fit_program_traces()`` over the window, over the
window's fits (`traffic/meshfit.py` reads the counter before and after).
1.0 while every fit builds its program anew; 0 once a configuration's
program is built once per process.  Layer: estimator (`core/api.py`)."""

UNIT = "traces"


def read(run):
    before = getattr(run, "traces_before", None)
    if before is None or not run.fits:
        return None
    return (run.traces_after - before) / len(run.fits)
