"""A traced benchmark run that also reports the program's span metrics.

    python bench/spans_run.py --workload ivf4096-sift128.assign \\
        --seed <n> --seconds <s>

Runs `bench/run.py` as it is, with ``--trace 1``, and with two additions:
the trace record gains the keys of `lib.spans.extend` (each device
operation's ``repro.*`` scope, the device's program runs, the TPU
runtime's enqueue, completion and transfer events), and the cell's
per-layer metrics gain those of ``SPAN_METRICS`` that read them.  The
result line and exit codes are the harness's.  On a checkout without
the program's spans the added metrics read None and are left out of the
line, as the harness leaves out any metric that reads None.
"""

from __future__ import annotations

import sys

import run as harness
from lib import spans, trace

SPAN_METRICS = {
    "table1-kddcup99.fit": ["idle_solve_pct.fit", "step_pct.fit",
                            "aa_pct.fit"],
    "ivf4096-sift128.assign": ["idle_put_pct.assign",
                               "idle_fetch_pct.assign", "put_gbps.assign"],
}


def cell(name: str, tiny: bool = False):
    """`run.cell` with the cell's span metrics added to its per-layer
    list."""
    workload, config = _cell(name, tiny)
    workload["per_layer"] = workload["per_layer"] + SPAN_METRICS.get(name, [])
    return workload, config


def record(profile_dir: str) -> dict:
    return spans.extend(_record(profile_dir), profile_dir)


_cell, _record = harness.cell, trace.record


def main(argv=None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    harness.cell, trace.record = cell, record
    try:
        return harness.main(argv + ["--trace", "1"])
    finally:
        harness.cell, trace.record = _cell, _record


if __name__ == "__main__":
    sys.exit(main())
