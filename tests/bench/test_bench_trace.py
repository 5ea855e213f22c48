"""The reduction from a profiler trace to busy time, idle share, kernel
time and named idle gaps (`bench/lib/trace.py`)."""

import json
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent

# A window of 10 µs; device operations overlap, start before the window
# and end after it; the host names what it did in each gap.
HAND = {
    "window": [1000, 11000],
    "devices": {"/device:TPU:0": [
        ["fusion.1", 500, 1000],            # clipped to [1000, 1500)
        ["%_assignment_call.1", 2000, 3000],  # [2000, 5000)
        ["fusion.2", 4000, 2000],           # overlaps: busy [2000, 6000)
        ["copy", 9000, 4000],               # clipped to [9000, 11000)
    ]},
    "host": [
        ["bench.window", 1000, 10000, 0],
        ["bench.predict", 1000, 5000, 0],
        ["bench.predict", 7000, 3000, 0],
        ["Compile", 6100, 2000, 0],
        ["Compile", 6100, 2000, 1],         # other thread: not named
    ],
}


def test_busy_union_and_idle_share(bench_path):
    from lib import trace
    assert trace.busy_intervals(HAND, "/device:TPU:0") == [
        [1000, 1500], [2000, 6000], [9000, 11000]]
    assert trace.busy_s(HAND) == pytest.approx(6.5e-6)
    assert trace.window_s(HAND) == pytest.approx(1e-5)
    assert trace.idle_pct(HAND) == pytest.approx(35.0)


def test_kernel_and_op_sums(bench_path):
    from lib import trace
    assert trace.kernel_events(HAND, lambda n: "assignment" in n) == \
        pytest.approx([3e-6])
    ops = trace.op_seconds(HAND)
    assert ops == pytest.approx({"fusion.1": 5e-7, "%_assignment_call.1": 3e-6,
                                 "fusion.2": 2e-6, "copy": 2e-6})
    assert [n for n, _ in trace.top_ops(HAND, 2)] == ["%_assignment_call.1",
                                                     "fusion.2"]


def test_idle_gaps_are_named_by_the_host(bench_path):
    from lib import trace
    assert trace.idle_gaps(HAND) == [
        ["bench.predict/Compile", pytest.approx(3e-6)],
        ["bench.predict", pytest.approx(5e-7)]]


def test_no_device_operation_reads_nothing(bench_path):
    from lib import trace
    empty = {**HAND, "devices": {"/device:TPU:0": []}}
    assert trace.busy_s(empty) == 0.0
    assert trace.idle_pct(empty) is None
    assert trace.breakdown(empty) == {"device_ops": [], "idle_gaps": []}


def _busy_by_sweep(rec, device):
    """Busy ns by an independent sweep over interval edges."""
    w0, w1 = rec["window"]
    edges = []
    for _, s, d in rec["devices"][device]:
        s, e = max(s, w0), min(s + d, w1)
        if e > s:
            edges += [(s, 1), (e, -1)]
    edges.sort()
    depth, last, busy = 0, None, 0
    for t, step in edges:
        if depth > 0:
            busy += t - last
        depth += step
        last = t
    return busy


def test_recorded_chip_trace(bench_path):
    """A small record taken on a TPU v5e (tests/bench/trace_v5e.json):
    the union agrees with an independent sweep, and busy time never
    exceeds the window or the sum of the operations."""
    from lib import trace
    rec = json.loads((HERE / "trace_v5e.json").read_text())
    (device,) = [d for d, evs in rec["devices"].items() if evs]
    busy_ns = _busy_by_sweep(rec, device)
    assert trace.busy_s(rec) == pytest.approx(busy_ns / 1e9)
    assert 0 < trace.busy_s(rec) <= trace.window_s(rec)
    assert trace.busy_s(rec) <= sum(trace.op_seconds(rec).values()) + 1e-12
    assert 0 <= trace.idle_pct(rec) < 100
    kernel = trace.kernel_events(rec, lambda n: "%_assignment_call.1" in n)
    assert kernel and sum(kernel) <= trace.busy_s(rec) + 1e-12
    gaps = trace.idle_gaps(rec)
    assert gaps and all(g[0].startswith("bench.") for g in gaps)
    assert sum(g[1] for g in gaps) <= trace.window_s(rec) - trace.busy_s(rec) \
        + 1e-9


# Three fits: each span holds its solver loop (the longest ``while``)
# beside shorter operations; the third fit's loop starts inside its span
# and ends after the window.
FITS = {
    "window": [0, 1000],
    "devices": {"/device:TPU:0": [
        ["%fusion.2", 50, 30],
        ["%while.7", 60, 20],               # seeding: shorter loop
        ["%while.55", 100, 300],
        ["%while.55", 550, 200],
        ["%while.55", 900, 400],
    ]},
    "host": [
        ["bench.window", 0, 1000, 0],
        ["bench.fit", 0, 500, 0],
        ["bench.fit", 500, 300, 0],
        ["bench.fit", 800, 200, 0],
    ],
}


def _is_loop(name):
    return name.startswith("%while")


def test_longest_operation_in_each_span(bench_path):
    from lib import trace
    assert trace.longest_in_spans(FITS, "bench.fit", _is_loop) == \
        pytest.approx([3e-7, 2e-7, 4e-7])
    no_loop = {**FITS, "devices": {"/device:TPU:0": FITS["devices"][
        "/device:TPU:0"][:4]}}
    assert trace.longest_in_spans(no_loop, "bench.fit", _is_loop)[2] is None


def test_lloyd_roofline_reads_the_solver_loops(harness, bench_path):
    from types import SimpleNamespace

    from lib import peaks, work
    reader = harness.load_module("metrics", "lloyd_roofline")
    config = {"data": {"n": 4898431, "d": 37},
              "estimator": {"n_clusters": 10}}
    p = peaks.peaks_for("TPU v5 lite")
    fits = [{"n_iter": n, "n_accepted": 1, "wall_s": 1.0}
            for n in (80, 90, 70)]
    run = SimpleNamespace(trace=FITS, fits=fits, config=config, peaks=p)
    least = work.least_time(*work.lloyd_step(4898431, 10, 37), p)
    assert reader.read(run) == pytest.approx(100 * 240 * least / 9e-7)
    # the wall time of a fit (seeding, tracing, host work) plays no part
    run.fits = [{**f, "wall_s": 5.0} for f in fits]
    assert reader.read(run) == pytest.approx(100 * 240 * least / 9e-7)
    # a fit whose loop is missing from the trace reads nothing
    run.trace = {**FITS, "devices": {"/device:TPU:0": FITS["devices"][
        "/device:TPU:0"][:4]}}
    assert reader.read(run) is None
