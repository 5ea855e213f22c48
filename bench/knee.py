"""Sweep the offered rate of a serve cell to find its knee, on the chip.

    python bench/knee.py --workload ivf4096-sift128.serve --seed 1 \\
        --seconds 8 --rates 250 500 1000 2000

One process sets up the cell once per rate (the serve traffic with its
``rate_per_s`` replaced) and runs its window.  For each rate it prints
one JSON line: the offered and completed request rates, p50 and p95 of
the latency from due time, and the p95 of each quarter of the window.
The knee is the highest rate whose completed rate keeps up with the
offered one and whose quarters show no growing backlog; the serve cell
runs at about four fifths of it.  This is a tool for defining a cell,
not a benchmark run: it prints no result line.
"""

from __future__ import annotations

import argparse
import json
import sys

import numpy as np

import run as harness


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
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
    for rate in args.rates:
        workload["params"] = {**workload["params"], "rate_per_s": rate}
        run = harness.Run(args, workload, config)
        run.peaks, run.device = peaks, device
        traffic.setup(run)
        try:
            traffic.window(run)
        finally:
            traffic.release(run)
        lat = traffic.latencies_ms(run)
        done = np.isfinite(lat)
        span = max(np.nanmax(run.done), run.due[-1]) if done.any() else 1.0
        quarters = [float(np.percentile(lat[(run.due >= q * args.seconds / 4)
                                            & (run.due < (q + 1) *
                                               args.seconds / 4)], 95))
                    for q in range(4)]
        print(json.dumps({
            "rate_offered": len(run.due) / args.seconds,
            "rate_completed": int(done.sum()) / span,
            "p50_ms": float(np.percentile(lat, 50)),
            "p95_ms": float(np.percentile(lat, 95)),
            "p95_ms_by_quarter": quarters,
            "rows_per_s": float(run.sizes.sum()) / args.seconds,
            "gen_lag_p95_ms": float(np.percentile(
                (run.sent - run.due) * 1e3, 95)),
            "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
