"""Segmentation-overhead benchmark for the persistence engine
(DESIGN.md §Persistence).

A checkpointed solve is a host loop over jit'd `while_loop` segments, so
its cost over the monolithic solve decomposes into (a) host/dispatch
overhead per segment boundary and (b) the `device_get` + npz write per
snapshot.  This module times the same fixed-seed solve four ways —
monolithic, segmented with no snapshot writes (``checkpoint_cb`` only),
segmented with synchronous artifact writes (``sync_writes=True``), and
segmented with the default background `repro.runtime.writer` — and
reports the per-boundary overheads.  The async arm shows how much of the
sync write cost the writer thread hides (the remaining overhead is the
unavoidable ``device_get`` snapshot plus queue handoff); the perf
trajectory catches a regression that would make "resumable" cost more
than it must.

    PYTHONPATH=src python -m benchmarks.checkpoint_bench [--json [PATH]]
        [--checkpoint-every S] [--smoke]
"""

from __future__ import annotations

import argparse
import json
import tempfile
import time
from pathlib import Path

from benchmarks.common import csv_row

# full run: long enough that per-boundary cost is resolvable over noise;
# smoke: just proves the segmented path runs end to end (CI)
FULL = dict(n=20000, d=16, k=32, max_iter=60)
SMOKE = dict(n=512, d=8, k=8, max_iter=12)


def _solve_time(fn, reps=3):
    """Median wall time of a solve, compile excluded (one warm-up call;
    the segmented drivers block on every segment, so block_until_ready on
    the result is enough)."""
    import jax
    jax.block_until_ready(fn())
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        ts.append(time.perf_counter() - t0)
    ts.sort()
    return ts[len(ts) // 2]


def run(checkpoint_every: int = 10, smoke: bool = False) -> dict:
    import jax.numpy as jnp
    import jax

    from repro.core.init_schemes import kmeanspp_init
    from repro.core.kmeans import KMeansConfig, aa_kmeans
    from repro.data.synthetic import make_blobs

    p = SMOKE if smoke else FULL
    x = jnp.asarray(make_blobs(p["n"], p["d"], p["k"], seed=0, spread=1.0))
    c0 = kmeanspp_init(jax.random.PRNGKey(0), x, p["k"])
    cfg = KMeansConfig(k=p["k"], max_iter=p["max_iter"])
    every = max(1, int(checkpoint_every))

    # the monolithic baseline is the jitted whole-solve program, like any
    # production caller would run it
    mono = jax.jit(lambda xx, cc: aa_kmeans(xx, cc, cfg))
    ref = mono(x, c0)
    t_mono = _solve_time(lambda: mono(x, c0))
    t_seg = _solve_time(lambda: aa_kmeans(
        x, c0, cfg, checkpoint_every=every, checkpoint_cb=lambda st, t: None))
    with tempfile.TemporaryDirectory() as d:
        t_sync = _solve_time(lambda: aa_kmeans(
            x, c0, cfg, checkpoint_every=every, checkpoint_dir=d,
            sync_writes=True))
    with tempfile.TemporaryDirectory() as d:
        # default path: background CheckpointWriter (drained before the
        # driver returns, so every snapshot is on disk when timing stops)
        t_async = _solve_time(lambda: aa_kmeans(
            x, c0, cfg, checkpoint_every=every, checkpoint_dir=d))
        n_snaps = len(list(Path(d).glob("it_*.npz")))
        # roundtrip correctness rides along: resume the final artifact
        res = aa_kmeans(x, c0, cfg,
                        resume_from=max(Path(d).glob("it_*.npz")))
    assert float(res.energy) == float(ref.energy), \
        "resumed solve diverged from the monolithic result"
    n_bounds = max(1, n_snaps)

    # Direct per-boundary cost, free of solve-time noise (the end-to-end
    # deltas above bury a ~ms write under ~60 ms segments): what the
    # DRIVER pays at a boundary is device_get + npz write on the sync
    # path vs device_get + queue handoff on the async path — the write
    # itself runs on the writer thread, off the critical path.
    from repro.core import serialize
    from repro.runtime.writer import CheckpointWriter, write_snapshot
    holder = {}
    aa_kmeans(x, c0, cfg, checkpoint_every=every,
              checkpoint_cb=lambda st, t: holder.update(state=st))
    state = holder["state"]
    reps = 8 if smoke else 20

    def _boundary_time(fn, between=None):
        ts = []
        for i in range(reps):
            t0 = time.perf_counter()
            fn(i, jax.device_get(state))    # the snapshot point itself
            ts.append(time.perf_counter() - t0)
            if between is not None:
                between()
        ts.sort()
        return ts[len(ts) // 2] * 1e6

    with tempfile.TemporaryDirectory() as d:
        sync_us = _boundary_time(lambda i, st: write_snapshot(
            d, st, kind=serialize.KIND_LOOP, step=i))
    with tempfile.TemporaryDirectory() as d:
        with CheckpointWriter(d, kind=serialize.KIND_LOOP) as w:
            # drain OUTSIDE the timer: in a real run the next segment's
            # compute gives the writer its slack, so the driver pays only
            # the handoff; a tight rep loop would instead measure queue
            # back-pressure (disk saturation) that checkpoint_every
            # boundaries never reach
            async_us = _boundary_time(lambda i, st: w.submit(st, i),
                                      between=w.drain)

    return {
        "n": p["n"], "d": p["d"], "k": p["k"],
        "n_iter": int(ref.n_iter), "checkpoint_every": every,
        "segments": n_bounds, "snapshots": n_snaps,
        "t_monolithic_s": t_mono, "t_segmented_s": t_seg,
        "t_checkpointed_s": t_sync, "t_checkpointed_async_s": t_async,
        "seg_overhead_us_per_boundary": (t_seg - t_mono) / n_bounds * 1e6,
        "snap_overhead_us_per_snapshot": (t_sync - t_seg) / n_bounds * 1e6,
        "async_overhead_us_per_snapshot": (t_async - t_seg) / n_bounds * 1e6,
        "sync_boundary_us": sync_us,
        "async_boundary_us": async_us,
        "async_to_sync_overhead_ratio": async_us / sync_us,
    }


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--checkpoint-every", type=int, default=10,
                        metavar="S", help="segment length in iterations")
    parser.add_argument("--json", nargs="?", const="BENCH_checkpoint.json",
                        default=None, metavar="PATH",
                        help="write the record to PATH (default "
                             "BENCH_checkpoint.json in the repo root)")
    parser.add_argument("--smoke", action="store_true",
                        help="tiny problem; proves the segmented path (CI)")
    args = parser.parse_args(argv)

    import jax
    rec = run(checkpoint_every=args.checkpoint_every, smoke=args.smoke)
    tag = f"n{rec['n']}_k{rec['k']}_s{rec['checkpoint_every']}"
    print(csv_row(f"checkpoint.monolithic.{tag}",
                  rec["t_monolithic_s"] * 1e6))
    print(csv_row(f"checkpoint.segmented.{tag}", rec["t_segmented_s"] * 1e6,
                  f"boundary_us={rec['seg_overhead_us_per_boundary']:.1f}"))
    print(csv_row(f"checkpoint.snapshotted.{tag}",
                  rec["t_checkpointed_s"] * 1e6,
                  f"snapshot_us={rec['snap_overhead_us_per_snapshot']:.1f};"
                  f"snapshots={rec['snapshots']}"))
    print(csv_row(f"checkpoint.snapshotted_async.{tag}",
                  rec["t_checkpointed_async_s"] * 1e6,
                  f"snapshot_us={rec['async_overhead_us_per_snapshot']:.1f}"))
    print(csv_row(f"checkpoint.boundary_sync.{tag}",
                  rec["sync_boundary_us"]))
    print(csv_row(f"checkpoint.boundary_async.{tag}",
                  rec["async_boundary_us"],
                  f"ratio_vs_sync="
                  f"{rec['async_to_sync_overhead_ratio']:.3f}"))
    if args.json:
        path = Path(args.json)
        if not path.is_absolute():
            path = Path(__file__).resolve().parents[1] / path
        path.write_text(json.dumps(
            {"schema": "checkpoint_bench/v2",
             "backend": jax.default_backend(),
             "smoke": args.smoke, "record": rec}, indent=2))
        print(f"wrote {path}")
    return rec


if __name__ == "__main__":
    main()
