"""Benchmark orchestrator: one function per paper table + kernel/roofline
reports.  Prints ``name,us_per_call,derived`` CSV (plus human-readable
tables above each block).  Every block runs; a block that raises prints
its traceback and the run exits non-zero.

    PYTHONPATH=src python -m benchmarks.run [--scale 0.05] [--fast]
"""

from __future__ import annotations

import argparse
import sys
import traceback


def main() -> None:
    from repro.core.backends import backend_names

    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", type=float, default=0.05,
                    help="fraction of Table-1 dataset sizes (1.0 = paper)")
    ap.add_argument("--fast", action="store_true",
                    help="first 6 datasets only")
    ap.add_argument("--backend", default="dense",
                    choices=sorted(backend_names()),
                    help="solver engine for the table runs "
                         "(repro.core.backends registry)")
    ap.add_argument("--checkpoint-every", type=int, default=10, metavar="S",
                    help="segment length for the persistence-overhead "
                         "block (benchmarks/checkpoint_bench.py)")
    args = ap.parse_args()

    from benchmarks import kernels_bench, roofline, table2_dynamic_m, \
        table3_vs_lloyd
    from repro.data.synthetic import DATASETS
    from repro.runtime.compile_cache import enable_compile_cache

    enable_compile_cache()
    datasets = list(DATASETS)[:6] if args.fast else None

    def table2():
        s2 = table2_dynamic_m.run(scale=args.scale, datasets=datasets,
                                  backend=args.backend)
        n = s2["total"]
        mean = lambda key: sum(r[key]["time_s"] for r in s2["rows"]) / n
        print(f"table2.fixed_m2,{mean('fixed_m2')*1e6:.1f},")
        print(f"table2.dynamic_m2,{mean('dyn_m2')*1e6:.1f},"
              f"wins={s2['wins_dynamic_m2']}/{n}")
        print(f"table2.fixed_m5,{mean('fixed_m5')*1e6:.1f},")
        print(f"table2.dynamic_m5,{mean('dyn_m5')*1e6:.1f},"
              f"wins={s2['wins_dynamic_m5']}/{n}")

    def table3():
        s3 = table3_vs_lloyd.run(scale=args.scale, datasets=datasets,
                                 backend=args.backend)
        mean_l = sum(c["lloyd_time_s"] for c in s3["cases"]) / s3["total"]
        mean_a = sum(c["aa_time_s"] for c in s3["cases"]) / s3["total"]
        print(f"table3.lloyd,{mean_l*1e6:.1f},")
        print(f"table3.aa,{mean_a*1e6:.1f},"
              f"wins={s3['wins']}/{s3['total']};"
              f"iter_wins={s3['iter_wins']}/{s3['total']};"
              f"mean_time_decrease={s3['mean_time_decrease']:.1%};"
              f"mse_parity={s3['mse_parity']}/{s3['total']}")

    def batched():
        from benchmarks import batched_sweep
        batched_sweep.main(backend=args.backend)

    def checkpoint():
        from benchmarks import checkpoint_bench
        checkpoint_bench.main(
            ["--json", "--checkpoint-every", str(args.checkpoint_every)]
            + (["--smoke"] if args.fast else []))

    def serving():
        from benchmarks import serving_bench
        serving_bench.main(["--json"] + (["--smoke"] if args.fast else []))

    def hierarchy():
        from benchmarks import hierarchy_bench
        hierarchy_bench.main(["--json"] + (["--smoke"] if args.fast else []))

    blocks = [
        ("Table 2: fixed vs dynamic m", table2),
        ("Table 3: AA-KMeans vs Lloyd", table3),
        ("Batched engine: multi-restart + grid sweep", batched),
        ("Checkpoint segmentation overhead", checkpoint),
        ("Serving: closure-index recall vs latency", serving),
        ("Hierarchy: flat vs divide-and-conquer", hierarchy),
        # empty argv: run.py's own CLI args must not leak into the
        # benchmark's parser; the orchestrator always emits the JSON seed
        ("Kernel roofline (fused vs split Lloyd pass)",
         lambda: kernels_bench.main(["--json"])),
        ("LM roofline table (from dry-run artifacts)", roofline.main),
    ]
    failed = []
    for title, block in blocks:
        print(f"# === {title} ===", flush=True)
        try:
            block()
        except Exception:        # report every block, then fail the run
            traceback.print_exc()
            failed.append(title)
    if failed:
        print(f"# FAILED blocks: {'; '.join(failed)}", file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
