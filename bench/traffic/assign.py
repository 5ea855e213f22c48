"""Bulk-assign traffic: ``model.predict(db)`` back to back.

The model holds a K-row codebook drawn as in the serve traffic; the
database is ``params.db_rows`` fresh rows of the configuration's data
distribution, drawn from ``--seed`` and held on the host, as an IVF
index's ``add`` assigns its database to lists.  Calls start back to back
while the window is open; the call running when it closes is finished
and counted.

End to end: ``assign_rows_per_s``, the rows assigned by the window's
calls over their total wall time.  The check holds every distinct answer
of the window to the reference's nearest centroid (``label_gap``).
"""

from __future__ import annotations

import hashlib
import time

import jax
import numpy as np

from lib import codebook, gen, reference


def setup(run):
    t0 = time.perf_counter()
    run.centroids = codebook.centroids(run)
    run.db = np.asarray(gen.fresh_rows(run.config["data"], run.seed,
                                       run.params["db_rows"], stream=3))
    t1 = time.perf_counter()
    run.model = codebook.model(run, run.centroids)
    # one chunk compiles the one chunk program every call runs
    run.model.predict(run.db[:1])
    run.log("setup", data_s=round(t1 - t0, 3),
            warmup_s=round(time.perf_counter() - t1, 3))


def window(run):
    run.calls, run.answers = [], {}
    t_close = time.perf_counter() + run.seconds
    while True:
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.predict"):
            labels = run.model.predict(run.db)
        t1 = time.perf_counter()
        run.calls.append({"rows": int(labels.shape[0]), "wall_s": t1 - t0})
        run.answers.setdefault(hashlib.sha1(labels.tobytes()).hexdigest(),
                               labels)
        if t1 >= t_close:
            return


def end_to_end(run):
    rows = sum(c["rows"] for c in run.calls)
    wall = sum(c["wall_s"] for c in run.calls)
    return {"metrics": {"assign_rows_per_s": (rows / wall, "rows/s")},
            "attempted": len(run.calls), "failed": 0}


def release(run):
    run.model = None


def check(run):
    want, _ = reference.assign(run.db, run.centroids)
    c = np.asarray(run.centroids)
    gap, short = 0.0, 0
    for got in run.answers.values():
        if got.shape != want.shape:
            short += 1
        else:
            gap = max(gap, reference.label_gap(run.db, c, got, want))
    limits = run.workload["limits"]
    return {"label_gap": {"value": gap, "limit": limits["label_gap"]},
            "wrong_shape": {"value": short, "limit": 0}}
