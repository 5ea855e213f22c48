"""Fit traffic: ``AAKMeans(**estimator).fit(X)`` back to back.

The configuration fixes the data (its ``data`` block and seed, like a
published dataset) and the cell fixes a set of fit seeds
(``params.fit_seeds``), one per fit, as the paper runs each dataset from
several seedings.  ``--seed`` orders that set and draws the fits whose
answers are checked.  Fits start back to back, in whole passes over the
set: the window runs at least ``--seconds`` and closes at the end of the
pass that is running then, so every run does whole passes of the same
work in another order, and no run's mean leans on which seeds fell into
a partial pass.

End to end: ``fit_s``, the total wall time of the window's fits over
their number.  The check holds one fit of each seed, drawn from
``--seed`` among the passes, to the plain reference: its labels against
the nearest-centroid labels of its centroids (``label_gap``), its energy
against the reference energy of those labels (``energy_dev``), and the
share of rows that one reference Lloyd update from its labels would
move (``lloyd_churn``), which a fit that stopped early, or returned its
seeding, cannot hide.
"""

from __future__ import annotations

import time

import jax
import numpy as np

from lib import gen, reference


def _estimator(run, seed):
    from repro.core import AAKMeans
    return AAKMeans(seed=int(seed), **run.config["estimator"])


def setup(run):
    data = run.config["data"]
    t0 = time.perf_counter()
    run.x = jax.block_until_ready(gen.dataset(data, data["seed"]))
    t1 = time.perf_counter()
    seeds = list(run.params["fit_seeds"])
    rng = np.random.default_rng(run.seed)
    run.order = [seeds[i] for i in rng.permutation(len(seeds))]
    run.rng = rng
    # one fit outside the set compiles and warms every program a fit uses
    model = _estimator(run, run.params["warmup_seed"]).fit(run.x)
    jax.block_until_ready(model.labels_)
    run.log("setup", data_s=round(t1 - t0, 3),
            warmup_fit_s=round(time.perf_counter() - t1, 3))


def window(run):
    run.fits, run.models, run.errors = [], [], []
    t_close = time.perf_counter() + run.seconds
    i = 0
    while True:
        seed = run.order[i % len(run.order)]
        t0 = time.perf_counter()
        try:
            with jax.profiler.TraceAnnotation("bench.fit"):
                model = _estimator(run, seed).fit(run.x)
                jax.block_until_ready(model.labels_)
        except Exception as e:       # a fit that raises counts as failed
            run.errors.append(f"fit seed {seed}: {e!r}")
            model = None
        t1 = time.perf_counter()
        run.fits.append({"seed": seed, "wall_s": t1 - t0,
                         "n_iter": model.n_iter_ if model else None,
                         "n_accepted": model.n_accepted_ if model else None})
        run.models.append(model)
        i += 1
        if t1 >= t_close and i % len(run.order) == 0:
            return


def end_to_end(run):
    for f in run.fits:
        run.log("fit", seed=f["seed"], wall_s=round(f["wall_s"], 4),
                n_iter=f["n_iter"], n_accepted=f["n_accepted"])
    walls = [f["wall_s"] for f in run.fits]
    return {"metrics": {"fit_s": (sum(walls) / len(walls), "s")},
            "attempted": len(run.fits), "failed": len(run.errors)}


def release(run):
    """Keep, on the host, the answers of one fit of each seed, drawn from
    ``--seed`` among the passes, and drop every model."""
    by_seed = {}
    for i, f in enumerate(run.fits):
        by_seed.setdefault(f["seed"], []).append(i)
    run.answers = []
    for seed in sorted(by_seed):
        i = int(run.rng.choice(by_seed[seed]))
        m = run.models[i]
        if m is not None:
            run.answers.append({
                "seed": seed,
                "centroids": np.asarray(m.centroids_, np.float32),
                "labels": np.asarray(m.labels_),
                "energy": float(m.energy_),
                "n_iter": int(m.n_iter_)})
    run.models = None


def check(run):
    gap = dev = churn = 0.0
    for a in run.answers:
        c, labels = a["centroids"], a["labels"]
        want, mind = reference.assign(run.x, c)
        gap = max(gap, reference.label_gap(run.x, c, labels, want))
        e_ref = float(np.sum(mind, dtype=np.float64))
        dev = max(dev, abs(a["energy"] - e_ref) / e_ref)
        moved, _ = reference.assign(run.x, reference.means(run.x, labels, c))
        churn = max(churn, float(np.mean(moved != labels)))
    limits = run.workload["limits"]
    return {"label_gap": {"value": gap, "limit": limits["label_gap"]},
            "energy_dev": {"value": dev, "limit": limits["energy_dev"]},
            "lloyd_churn": {"value": churn, "limit": limits["lloyd_churn"]},
            "failed_fits": {"value": len(run.errors), "limit": 0}}
