"""Fixtures of the benchmark's CPU tests: the harness loaded from
``bench/run.py`` and a way to drive one ``--tiny`` rehearsal in this
process and read its result line."""

import contextlib
import importlib.util
import io
import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
BENCH = ROOT / "bench"


def _load_harness():
    spec = importlib.util.spec_from_file_location("bench_run", BENCH / "run.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="session")
def harness():
    return _load_harness()


@pytest.fixture
def rehearse(harness):
    """Run one cell at its tiny size on the CPU; return (exit code, the
    result line the rehearsal printed to standard error, or None)."""
    def run(workload, seed=3, seconds=1.0, trace=0):
        err = io.StringIO()
        with contextlib.redirect_stderr(err):
            rc = harness.main(["--workload", workload, "--seed", str(seed),
                               "--seconds", str(seconds), "--trace",
                               str(trace), "--tiny"])
        lines = [ln for ln in err.getvalue().splitlines()
                 if ln.startswith("{")]
        return rc, (json.loads(lines[-1]) if lines else None), err.getvalue()
    return run


@pytest.fixture(scope="session")
def bench_path():
    if str(BENCH) not in sys.path:
        sys.path.insert(0, str(BENCH))
    return BENCH


@pytest.fixture
def drive(harness):
    """Set up a cell at its tiny size and run its window in this process;
    return the run (what its traffic module recorded) and its end-to-end readings."""
    import argparse

    def run(workload, seed=3, seconds=1.0):
        args = argparse.Namespace(workload=workload, seed=seed,
                                  seconds=seconds, trace=0, tiny=True)
        wl, cfg = harness.cell(workload, tiny=True)
        sys.path.insert(0, str(ROOT / "src"))
        r = harness.Run(args, wl, cfg)
        traffic = harness.load_module("traffic", wl["traffic"])
        traffic.setup(r)
        try:
            traffic.window(r)
            e2e = traffic.end_to_end(r)
        finally:
            traffic.release(r)
        return r, traffic, e2e
    return run
