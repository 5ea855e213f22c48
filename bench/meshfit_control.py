"""Readings that set the limits of the mesh fit cell's check, on the chip.

    python bench/meshfit_control.py --seed 11 --cut 10 \\
        [--fit-seeds 3 4] [--answers cut control]
    XLA_FLAGS=--xla_force_host_platform_device_count=4 JAX_PLATFORMS=cpu \\
        python bench/meshfit_control.py --seed 11 --cut 2 --tiny

One process sets up ``mnist8m-k256.meshfit`` as a benchmark run does
(the sharded rows, the warm-up fit), then, for each of the cell's fit
seeds, fits as the window does and reads the cell's check on:

  * ``program`` — the fit as the cell runs it (the lower readings);
  * ``cut`` — the same fit with ``max_iter`` set to ``--cut``: a sound
    fit stopped early, the control for ``lloyd_churn``;
  * ``seeding`` — the fit's own random seeding with the reference's
    labels and energy: a fit that returned its seeding, which no limit
    may pass;
  * ``control`` — the program's centroids with labels and energy from
    the reference at the precision below the configuration's
    (``precision="high"``, three bf16 passes): the upper reading of
    ``label_gap``.

Each line holds every number the check compares.  ``--fit-seeds`` and
``--answers`` read a subset (default: the cell's fit seeds, all four
answers).  The benchmark's own
runs never run this; `bench/control.py` does the same for one-chip
cells.
"""

from __future__ import annotations

import argparse
import json
import sys

import jax
import numpy as np

import run as harness
from lib import reference

CELL = "mnist8m-k256.meshfit"
ANSWERS = ("program", "cut", "seeding", "control")


def _answer(seed, c, labels, energy, n_iter=None):
    return {"seed": seed, "centroids": np.asarray(c, np.float32),
            "labels": np.asarray(labels), "energy": float(energy),
            "n_iter": n_iter}


def _relabelled(rows, seed, c, precision="highest"):
    c = np.asarray(c, np.float32)          # off the mesh, onto the host
    labels, mind = reference.assign(rows, c, precision)
    return _answer(seed, c, labels, np.sum(mind, dtype=np.float64))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--cut", type=int, required=True)
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--fit-seeds", type=int, nargs="+")
    ap.add_argument("--answers", nargs="+", choices=ANSWERS, default=ANSWERS)
    args = ap.parse_args(argv)
    workload, config = harness.cell(CELL, tiny=args.tiny)
    sys.path.insert(0, str(harness.ROOT / "src"))
    args.trace, args.seconds = 0, 0.0
    device, _, peaks = harness._device(args, workload)
    if not args.tiny:
        from repro.runtime.compile_cache import enable_compile_cache
        enable_compile_cache()
    traffic = harness.load_module("traffic", workload["traffic"])
    run = harness.Run(args, workload, config)
    run.peaks, run.device = peaks, device
    traffic.setup(run)
    rows = traffic.Rows(run.x)
    init = config["estimator"]["init"]
    from repro.core.init_schemes import batched_init
    from repro.core.distributed import row_take
    take = row_take(run.mesh, (traffic.AXIS,))
    for seed in args.fit_seeds or run.params["fit_seeds"]:
        m = traffic._estimator(run, seed).fit(run.x)
        cut = traffic._estimator(run, seed, max_iter=args.cut).fit(run.x)
        keys = jax.random.split(jax.random.PRNGKey(seed), 1)
        answers = {
            "program": lambda: _answer(seed, m.centroids_, m.labels_,
                                       m.energy_, m.n_iter_),
            "cut": lambda: _answer(seed, cut.centroids_, cut.labels_,
                                   cut.energy_, cut.n_iter_),
            "seeding": lambda: _relabelled(rows, seed, batched_init(
                init, keys, run.x, m.n_clusters, take=take)[0]),
            "control": lambda: _relabelled(rows, seed, m.centroids_,
                                           "high")}
        for name in args.answers:
            a = answers[name]()
            print(json.dumps({"workload": CELL, "seed": args.seed,
                              "fit_seed": seed, "answers": name,
                              "n_iter": a["n_iter"], "device": device,
                              "checks": traffic.readings(rows, a)}),
                  flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
