"""Kernel micro-benchmarks: fused vs split Lloyd pass + arithmetic-intensity
derivation for the kernel roofline (EXPERIMENTS.md §Roofline, K-Means rows).

On this CPU container the Pallas kernels run in interpret mode (not
representative); wall times here benchmark the jnp reference path that XLA
compiles, while the DERIVED columns give the analytic TPU roofline of each
kernel variant: X passes per iteration, bytes moved, flops, arithmetic
intensity, and the predicted HBM-bound iteration time on v5e (819 GB/s,
197 TFLOP/s).  The v2 fused kernel is priced with its k-tiled traffic
model: X once, C re-streamed per X row tile.

``--json [PATH]`` emits the full table as ``BENCH_kernels.json`` — the
machine-readable seed of the perf trajectory (one record per kernel
variant x shape: x_passes_per_iter, bytes_per_iter, flops_per_iter, wall
time where measured).  ``--smoke`` shrinks the shapes and additionally
drives the real Pallas kernels in interpret mode, so CI can assert the
benchmark harness end-to-end without a TPU (test.sh --slow).

Schema v3 adds the tile-skip dimension (DESIGN.md §Bounds): every record
carries ``skipped_tile_frac`` (None for the bound-free kernels) and
``phase``, and `bounds_records` drives the ``fused_bounds`` engine
through an "early" (first step — no valid bounds, zero skip, the worst
case) and a "converged" (post-refinement — the plateau the solver
spends most iterations in) phase, reporting the measured skipped-tile
fraction and the traffic model it implies.  X passes stay at 1.0:
skipping removes C re-streams and distance flops, never the single X
read.

Schema v4 adds the row-layout dimension (DESIGN.md §Locality): every
record carries ``layout`` (None off the bounds arms) and the bounds
phases run over three layouts — "ordered" (rows laid out cluster by
cluster: the best case the tile predicate was designed for),
"interleaved" (the same rows deterministically shuffled — the make_blobs
regime, where a converged row tile still spans many clusters and the
tile-level ANY predicate never fires), and "interleaved+reorder" (the
interleaved rows driven through the ``fused_bounds_reorder`` locality
engine, which sorts rows by current label on-device and should recover
the ordered layout's converged skip).  `solver_records` adds end-to-end
``aa_kmeans_traced`` wall-time rows on the interleaved workload with and
without reordering, reporting the post-accept-phase skip
(`split_bound_phases` — the flat average would dilute it with warm-up
iterations).  Records are emitted in a deterministic order with fixed
seeds and sorted JSON keys, so two runs differ only in wall times.
"""

from __future__ import annotations

import argparse
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import csv_row, timed
from repro.core.backends import get_backend
from repro.kernels import ref, tiles

HBM_BW = 819e9
PEAK = 197e12

SHAPES = [(100_000, 9, 10), (100_000, 9, 100),
          (53_500, 385, 10), (131_072, 64, 1000),
          (131_072, 64, 65_536)]          # beyond the old fused VMEM gate
SMOKE_SHAPES = [(512, 9, 10), (384, 17, 33)]

# Deliberately a curated subset of backends.backend_names(): the backends
# whose CPU wall clock is meaningful (Pallas engines join on real TPUs —
# see step_bench).
STEP_BACKENDS = ("dense", "blocked", "hamerly", "elkan", "yinyang")


def analyze(n, d, k, variant: str):
    """Per-Lloyd-iteration X passes / bytes / flops on TPU (bf16 X, f32
    accum).  Pipeline variants: "split" (assignment pass + update pass),
    "fused_v1" (whole C resident — the old gated kernel, for reference),
    "fused" (v2 k-tiled: X once, C re-streamed per X row tile).
    Single-kernel variants (one X pass each, their own byte/flop terms):
    "assignment" (distances + labels/mind out), "update" (labels in,
    one-hot matmul, stats out)."""
    itemsize = 2
    x_bytes = n * d * itemsize
    c_bytes = k * d * itemsize
    out_bytes = n * 4 + k * d * 4                  # labels+mind, f32 stats
    dist_flops = 2 * n * k * d     # distance cross-term
    onehot_flops = 2 * n * k * d   # one-hot matmul for the update
    flops = dist_flops + onehot_flops
    if variant == "split":
        x_passes = 2.0
        bytes_moved = 2 * x_bytes + 2 * c_bytes + 2 * n * 4 + k * d * 4
    elif variant == "fused_v1":
        x_passes = 1.0
        bytes_moved = x_bytes + c_bytes + out_bytes
    elif variant == "fused":
        x_passes = 1.0
        tn, _ = tiles.choose_tiles(n, k, d, itemsize, kind="fused")
        n_tiles = max(1, -(-n // tn))
        bytes_moved = x_bytes + n_tiles * c_bytes + out_bytes
    elif variant == "assignment":
        x_passes = 1.0
        tn, _ = tiles.choose_tiles(n, k, d, itemsize, kind="assignment")
        n_tiles = max(1, -(-n // tn))
        bytes_moved = x_bytes + n_tiles * c_bytes + 2 * n * 4
        flops = dist_flops
    elif variant == "update":
        x_passes = 1.0
        bytes_moved = x_bytes + n * 4 + k * d * 4 + k * 4
        flops = onehot_flops
    else:
        raise ValueError(variant)
    ai = flops / bytes_moved
    t_mem = bytes_moved / HBM_BW
    t_comp = flops / PEAK
    return {"x_passes_per_iter": x_passes, "bytes_per_iter": bytes_moved,
            "flops_per_iter": flops, "ai": ai,
            "t_mem_us": t_mem * 1e6, "t_comp_us": t_comp * 1e6,
            "bound": "compute" if t_comp > t_mem else "memory"}


def kernel_records(shapes, smoke: bool = False):
    """One record per kernel variant x shape: analytic roofline columns
    plus a wall time where this host can measure one meaningfully (the
    XLA-compiled jnp path always; the Pallas kernels themselves only in
    --smoke interpret mode, flagged as such)."""
    rng = np.random.default_rng(0)
    records = []
    for (n, d, k) in shapes:
        x = jnp.asarray(rng.standard_normal((n, d)), jnp.float32)
        c = jnp.asarray(rng.standard_normal((k, d)), jnp.float32)

        if n * k <= 200e6:
            split = jax.jit(lambda a, b, kk=k: (
                ref.update_ref(a, ref.assignment_ref(a, b)[0], kk)))
            fused = jax.jit(lambda a, b: ref.fused_lloyd_ref(a, b))
            _, t_split = timed(split, x, c)
            _, t_fused = timed(fused, x, c)
        else:
            # the (N, K) distance matrix of the jnp path would not fit
            # host memory — analytic roofline rows only for this shape
            t_split = t_fused = None

        for variant, t in (("split", t_split), ("fused", t_fused),
                           ("fused_v1", None)):
            rec = {"variant": variant, "n": n, "d": d, "k": k,
                   "wall_us": None if t is None else t * 1e6,
                   "wall_path": None if t is None else "xla_ref",
                   "skipped_tile_frac": None, "phase": None,
                   "layout": None,
                   **analyze(n, d, k, variant)}
            records.append(rec)

        if smoke:
            # exercise the actual Pallas kernels (interpreted off-TPU)
            from repro.kernels.assignment import assignment_pallas
            from repro.kernels.fused_lloyd import fused_lloyd_pallas
            from repro.kernels.update import update_pallas
            w = jnp.ones((n,), jnp.float32)
            wall_path = ("pallas_interpret" if tiles.interpret_default()
                         else "pallas_tpu")
            for variant, fn in (
                    ("pallas.fused", lambda: fused_lloyd_pallas(x, c)),
                    ("pallas.fused_weighted", lambda: fused_lloyd_pallas(
                        x, c, w)),
                    ("pallas.assignment", lambda: assignment_pallas(x, c)),
                    ("pallas.update", lambda: update_pallas(
                        x, jnp.zeros((n,), jnp.int32), k, w=w))):
                _, t = timed(lambda fn=fn: fn(), warmup=1, reps=1)
                base = variant.split(".", 1)[1].replace("_weighted", "")
                records.append({"variant": variant, "n": n, "d": d, "k": k,
                                "wall_us": t * 1e6,
                                "wall_path": wall_path,
                                "skipped_tile_frac": None, "phase": None,
                                "layout": None,
                                **analyze(n, d, k, base)})
    return records


def bounds_workload(k=32, d=16, per=64, seed=7, layout="ordered"):
    """Synthetic tile-skip workloads in two row layouts.

    ``layout="ordered"`` lays rows out cluster by cluster (the favourable
    locality a sorted / sharded ingest provides), with the centroid order
    matching, so a converged row tile needs only the k tiles its own
    clusters live in.  ``layout="interleaved"`` deterministically shuffles
    those same rows — the `make_blobs` regime, where consecutive rows land
    in unrelated clusters, an X row *tile* always spans many groups, and
    the tile-level predicate (ANY row needs the k tile) never fires even
    when per-row elimination is near total.  The interleaved layout is the
    workload the locality engine (DESIGN.md §Locality) exists to fix."""
    rng = np.random.default_rng(seed)
    centers = rng.standard_normal((k, d)).astype(np.float32) * 20.0
    x = np.concatenate([centers[j] + rng.standard_normal((per, d))
                        .astype(np.float32) for j in range(k)])
    if layout == "interleaved":
        x = x[np.random.default_rng(seed + 1).permutation(x.shape[0])]
    elif layout != "ordered":
        raise ValueError(f"unknown layout {layout!r}")
    c0 = centers + 0.5 * rng.standard_normal((k, d)).astype(np.float32)
    return jnp.asarray(x), jnp.asarray(c0)


BOUNDS_LAYOUTS = ("ordered", "interleaved", "interleaved+reorder")


def bounds_records(group_size=8, refine_steps=4):
    """Early- vs converged-phase records for the ``fused_bounds`` engine
    across the three row layouts.

    Drives real steps (interpret mode off-TPU) and reports the MEASURED
    skipped-tile fraction per (layout, phase): "early" is the first step
    from the init carry (upper = +inf — no valid bounds, full scan, skip
    0 by construction), "converged" is the step after ``refine_steps``
    Lloyd refinements, where the bounds have tightened onto the stable
    assignment.  The "interleaved+reorder" arm wraps the engine in the
    locality engine (``fused_bounds_reorder``, warmup=1) so the kernel
    sees cluster-sorted rows from step 1 on — its converged skip should
    match the ordered layout's, against the raw interleaved arm's ~0.
    The analytic columns price the skip against the fused kernel's
    traffic model: the skipped fraction removes C re-streams and distance
    flops but never the single X read, so x_passes stays 1.0 and AI
    *drops* as bytes shrink slower than flops."""
    from repro.core.backends.bounds import extract_stats

    wall_path = ("pallas_interpret" if tiles.interpret_default()
                 else "pallas_tpu")
    records = []
    for layout in BOUNDS_LAYOUTS:
        reorder = layout.endswith("+reorder")
        x, c = bounds_workload(layout=layout.split("+")[0])
        n, d = x.shape
        k = c.shape[0]
        bk = get_backend("fused_bounds_reorder", warmup=1,
                         group_size=group_size) if reorder \
            else get_backend("fused_bounds", group_size=group_size)

        skips, walls = {}, {}
        carry = bk.init_carry(x, c, k)
        step = jax.jit(lambda a, b, cr, bk=bk: bk.step(a, b, k, cr))
        for i in range(refine_steps + 1):
            (res, carry), t = timed(step, x, c, carry, warmup=0, reps=1)
            skip = float(extract_stats(carry).skipped_frac)
            if i == 0:
                skips["early"], walls["early"] = skip, t
            c = bk.centroids_from_step(x, res, k, c)
        skips["converged"], walls["converged"] = skip, t

        for phase in sorted(skips):
            skip = skips[phase]
            base = analyze(n, d, k, "fused")
            itemsize = 2
            tn, _ = tiles.choose_tiles(n, k, d, itemsize,
                                       kind="fused_bounds")
            n_tiles = max(1, -(-n // tn))
            c_stream = n_tiles * k * d * itemsize
            base["bytes_per_iter"] = int(
                base["bytes_per_iter"] - skip * c_stream)
            base["flops_per_iter"] = int(base["flops_per_iter"]
                                         - skip * 2 * n * k * d)
            base["ai"] = base["flops_per_iter"] / base["bytes_per_iter"]
            base["t_mem_us"] = base["bytes_per_iter"] / HBM_BW * 1e6
            base["t_comp_us"] = base["flops_per_iter"] / PEAK * 1e6
            base["bound"] = ("compute"
                             if base["t_comp_us"] > base["t_mem_us"]
                             else "memory")
            records.append({"variant": "pallas.fused_bounds",
                            "n": n, "d": d, "k": k,
                            "wall_us": walls[phase] * 1e6,
                            "wall_path": wall_path,
                            "skipped_tile_frac": skip, "phase": phase,
                            "layout": layout,
                            **base})
    return records


def solver_records(max_iter=12):
    """End-to-end traced-solver rows: `aa_kmeans_traced` on the
    INTERLEAVED workload with and without the locality engine.

    Per-step micro-benchmarks can overstate a reordering win (they never
    pay the sort); these rows time the whole solve — warm-up iterations,
    churn-triggered sorts, gathers and all — and report the post-accept
    phase's mean skipped-tile fraction (`split_bound_phases`: the flat
    average would dilute any converged plateau with the boundless warm-up
    steps).  Expect that fraction to sit near 0 in BOTH arms on a
    from-scratch solve: the driver exits the moment labels stabilise, and
    tile-skipping only pays once drift ≈ 0 for consecutive steps — i.e.
    exactly the post-convergence plateau the driver never executes.  The
    converged-phase `bounds_records` arms isolate that plateau (the
    regime the segmented epoch drivers and serving-side refinement
    actually occupy); these rows price what reordering costs a cold solve
    that never reaches it.  Off-TPU the wall number is interpret
    overhead, not kernel time — it becomes meaningful on a real TPU."""
    from repro.core.kmeans import KMeansConfig, aa_kmeans_traced

    x, c_near = bounds_workload(layout="interleaved")
    n, d = x.shape
    k = c_near.shape[0]
    # random-row init: the near-solution init the per-step bench uses
    # converges in one iteration, leaving no post-accept phase to measure
    c0 = x[np.random.default_rng(11).choice(n, k, replace=False)]
    cfg = KMeansConfig(k=k, max_iter=max_iter)
    wall_path = ("pallas_interpret" if tiles.interpret_default()
                 else "pallas_tpu")
    records = []
    for layout, reorder in (("interleaved", False),
                            ("interleaved+reorder", True)):
        tr = aa_kmeans_traced(x, c0, cfg, backend="fused_bounds",
                              warmup=True, reorder=reorder)
        post = (tr.bound_phases or {}).get("post_accept", {})
        records.append({"variant": "solver.fused_bounds_traced",
                        "n": n, "d": d, "k": k,
                        "wall_us": tr.wall_time_s * 1e6,
                        "wall_path": wall_path,
                        "skipped_tile_frac": post.get("skipped_frac"),
                        "phase": "post_accept", "layout": layout,
                        "n_iters": len(tr.energies)})
    return records


def step_bench(backends=None, n=100_000, d=9, k=100):
    """Wall time of one step() — the solver's per-iteration unit — per
    backend.  The Pallas backends ("pallas"/"fused") are only timed on a
    real TPU: in CPU interpret mode their wall numbers would be pure
    Python-emulation overhead and read as the opposite of the TPU story
    (which the analytic roofline in `analyze` covers)."""
    if backends is None:
        backends = STEP_BACKENDS + (("pallas", "fused")
                                    if jax.default_backend() == "tpu"
                                    else ())
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((n, d)), jnp.float32)
    c = jnp.asarray(rng.standard_normal((k, d)), jnp.float32)
    rows = []
    for name in backends:
        # block size must divide N for the row-blocked path to engage
        bk = get_backend(name, block_n=n // 8) if name == "blocked" \
            else get_backend(name)
        carry = bk.init_carry(x, c, k)
        fn = jax.jit(lambda a, b, cr, bk=bk: bk.step(a, b, k, cr)[0])
        res, t = timed(fn, x, c, carry)
        rows.append(csv_row(f"backend.step.{name}.n{n}_d{d}_k{k}", t * 1e6,
                            f"energy={float(res.energy):.3e}"))
    return rows


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--json", nargs="?", const="BENCH_kernels.json",
                        default=None, metavar="PATH",
                        help="write records to PATH (default "
                             "BENCH_kernels.json in the repo root)")
    parser.add_argument("--smoke", action="store_true",
                        help="tiny shapes + drive the real Pallas kernels "
                             "in interpret mode (CI smoke)")
    args = parser.parse_args(argv)

    shapes = SMOKE_SHAPES if args.smoke else SHAPES
    records = kernel_records(shapes, smoke=args.smoke)
    records += bounds_records()
    records += solver_records()
    records.sort(key=lambda r: (r["variant"], r["n"], r["d"], r["k"],
                                r["layout"] or "", r["phase"] or ""))
    for r in records:
        phase = f".{r['phase']}" if r["phase"] else ""
        layout = f".{r['layout']}" if r["layout"] else ""
        skip = "" if r["skipped_tile_frac"] is None else \
            f";skip={r['skipped_tile_frac']:.3f}"
        detail = (f"x_passes={r['x_passes_per_iter']:g};"
                  f"tpu_bytes={r['bytes_per_iter']:.2e};ai={r['ai']:.1f};"
                  f"tpu_{r['bound']}_us="
                  f"{max(r['t_mem_us'], r['t_comp_us']):.1f}"
                  if "ai" in r else f"n_iters={r['n_iters']}")
        print(csv_row(
            f"kernel.{r['variant']}.n{r['n']}_d{r['d']}_k{r['k']}"
            f"{layout}{phase}",
            r["wall_us"] or 0.0, f"{detail}{skip}"))
    if not args.smoke:
        for row in step_bench():
            print(row)

    if args.json:
        path = Path(args.json)
        if not path.is_absolute():
            path = Path(__file__).resolve().parents[1] / path
        path.write_text(json.dumps(
            {"schema": "kernels_bench/v4",
             "backend": jax.default_backend(),
             "smoke": args.smoke, "records": records},
            indent=2, sort_keys=True))
        print(f"wrote {path}")
    return records


if __name__ == "__main__":
    main()
