"""Readings that set the limits of a cell's check, on the chip.

    python bench/control.py --workload table1-kddcup99.fit \\
        --seconds 1 --seeds 11 12 13

For each seed, one process sets up the cell and runs a short window as a
benchmark run does, keeps the window's answers, and then puts other
answers in their place and runs the cell's own check on each:

  * ``program`` — the window's answers as they came (the lower reading);
  * ``control`` — the reference computed at the precision below the one
    the configuration states (``precision="high"``, three bf16 passes,
    for float32 at ``highest``): the upper reading of ``label_gap``;
  * the faults a cell can have, planted in the answers: the state left
    unchanged (a fit that returns its seeding), half the rows left out
    (half the labels lost, the energy of the other half), an answer
    altered where it is produced (one label moved);
  * for fits, a fit cut off early: the same fit refitted with
    ``max_iter`` at half its ``n_iter_``, as the program returns it
    (``truncated``) and with its labels and energy recomputed by the
    reference from its centroids (``truncated_relabelled``), which is
    what a capped fit that ended on a final assignment would return.

A fit cell's check holds one fit of each seed, and each is read on its
own line; a serve or assign cell prints one line per
seed and answer kind.  Each line holds every number the check compares.
The benchmark's own runs never run this.
"""

from __future__ import annotations

import argparse
import json
import sys

import numpy as np

import run as harness
from lib import reference


def _fit_variants(run):
    x = run.x
    base = run.answers
    out = {"program": base}
    ctl = []
    for a in base:
        lab, mind = reference.assign(x, a["centroids"], "high")
        ctl.append({**a, "labels": lab,
                    "energy": float(np.sum(mind, dtype=np.float64))})
    out["control"] = ctl
    rng = np.random.default_rng(run.seed)
    k = base[0]["centroids"].shape[0]
    c0 = np.asarray(x[np.sort(rng.choice(x.shape[0], k, replace=False))])
    lab0, mind0 = reference.assign(x, c0)
    out["state_unchanged"] = [{**base[0], "centroids": c0, "labels": lab0,
                               "energy": float(np.sum(mind0, dtype=np.float64))}]
    a = base[0]
    h = a["labels"].shape[0] // 2
    _, mind = reference.assign(x, a["centroids"])
    half = a["labels"].copy()
    half[h:] = 0
    out["half_batch"] = [{**a, "labels": half,
                          "energy": float(np.sum(mind[:h], dtype=np.float64))}]
    moved = a["labels"].copy()
    moved[0] = (moved[0] + 1) % k
    out["answer_altered"] = [{**a, "labels": moved}]
    out["truncated"], out["truncated_relabelled"] = [], []
    from repro.core import AAKMeans
    est = run.config["estimator"]
    for a in base:
        m = AAKMeans(seed=int(a["seed"]), **{
            **est, "max_iter": max(a["n_iter"] // 2, 1)}).fit(x)
        c = np.asarray(m.centroids_, np.float32)
        cut = {**a, "centroids": c, "labels": np.asarray(m.labels_),
               "energy": float(m.energy_), "n_iter": int(m.n_iter_)}
        lab, mind = reference.assign(x, c)
        out["truncated"].append(cut)
        out["truncated_relabelled"].append(
            {**cut, "labels": lab,
             "energy": float(np.sum(mind, dtype=np.float64))})
    return out


def _rows_variants(run, answers, rows_of):
    """Serve and assign: answers are label arrays of known rows.  The
    control assigns all answered rows in one pass and splits them
    back."""
    c = run.centroids
    live = [i for i, a in enumerate(answers) if a is not None]
    ctl = [None] * len(answers)
    if live:
        rows = [rows_of(i) for i in live]
        lab = reference.assign(np.concatenate(rows), c, "high")[0]
        ends = np.cumsum([r.shape[0] for r in rows])
        for i, part in zip(live, np.split(lab, ends[:-1])):
            ctl[i] = part
    half, moved = [], []
    for i, a in enumerate(answers):
        if a is None:
            half.append(None), moved.append(None)
            continue
        h = a.copy()
        h[len(h) // 2:] = 0
        half.append(h)
        m = a.copy()
        if i == live[0]:
            m[0] = (m[0] + 1) % c.shape[0]
        moved.append(m)
    return {"program": answers, "control": ctl, "half_batch": half,
            "answer_altered": moved}


def variants(run):
    kind = run.workload["traffic"]
    if kind == "fit":
        return _fit_variants(run)
    if kind == "serve":
        return _rows_variants(run, run.answers, lambda i: run.requests[i])
    if kind == "assign":
        keys = list(run.answers)
        got = _rows_variants(run, [run.answers[k] for k in keys],
                             lambda i: run.db)
        return {name: dict(zip(keys, v)) for name, v in got.items()}
    raise ValueError(f"no control for traffic {kind!r}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--tiny", action="store_true")
    args = ap.parse_args(argv)
    workload, config = harness.cell(args.workload, tiny=args.tiny)
    sys.path.insert(0, str(harness.ROOT / "src"))
    args.trace = 0
    device, _, peaks = harness._device(args, workload)
    if not args.tiny:
        from repro.runtime.compile_cache import enable_compile_cache
        enable_compile_cache()
    traffic = harness.load_module("traffic", workload["traffic"])
    fit = workload["traffic"] == "fit"
    for seed in args.seeds:
        args.seed = seed
        run = harness.Run(args, workload, config)
        run.peaks, run.device = peaks, device
        traffic.setup(run)
        try:
            traffic.window(run)
        finally:
            traffic.release(run)
        for name, answers in variants(run).items():
            for one in ([[a] for a in answers] if fit else [answers]):
                run.answers = one
                checks = traffic.check(run)
                print(json.dumps({
                    "workload": args.workload, "seed": seed,
                    "answers": name, "device": device,
                    **({"fit_seed": one[0]["seed"],
                        "n_iter": one[0]["n_iter"]} if fit else {}),
                    "checks": {k: v["value"] for k, v in checks.items()}}),
                    flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
