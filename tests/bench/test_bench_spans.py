"""The program's spans and scopes in a trace record (`bench/lib/spans.py`),
the six per-layer readers that divide them, on synthetic records, and
the traced run that reports them (`bench/spans_run.py`)."""

import contextlib
import io
import json
from types import SimpleNamespace

import numpy as np
import pytest

DEV = "/device:TPU:0"
MS = 1_000_000
US = 1_000

# Three chunks of a predict.  Each put stages its rows for 1.4 ms; the
# runtime's transfer of them completes 0.6 ms after the chunk's dispatch
# (``repro.rows.run``) starts; the runtime enqueues the chunk's program
# as the transfer completes, and the program starts 20, 5 and 50 µs
# later on the host's clock, runs 2 ms, and is seen done 0.3 ms after it
# ends.  The device's clock runs 1 ms behind the host's.
LAG = 1 * MS
LATENCY = (20 * US, 5 * US, 50 * US)
RUNS = (2 * MS, 6 * MS, 10 * MS)
PUT, COPIED = 1_400 * US, 600 * US


def _rows_record(lag=LAG):
    host = [["bench.window", 0, 14 * MS, 0],
            ["bench.predict", 100 * US, 13 * MS, 0]]
    ops, programs = [], []
    runtime = {"DoEnqueueProgram": [], "tpu::System::Execute=>Done": [],
               "tpu::System::TransferToDevice=>IssueEvent=>Done": []}
    for run, lat in zip(RUNS, LATENCY):
        start = run + COPIED + lat                  # the program, host clock
        runtime["tpu::System::TransferToDevice=>IssueEvent=>Done"].append(
            [run + COPIED - 15 * US, 15 * US])
        runtime["DoEnqueueProgram"].append([run + COPIED, 30 * US])
        runtime["tpu::System::Execute=>Done"].append(
            [start + 2 * MS + 270 * US, 30 * US])
        # the fetch returns 0.5 ms after its program ends
        host += [["repro.rows.put", run - 1_500 * US, PUT, 0],
                 ["repro.rows.run", run, 100 * US, 0],
                 ["repro.rows.fetch", run + 100 * US,
                  start + 2_500 * US - run - 100 * US, 0]]
        programs.append([start - lag, 2 * MS])
        ops += [["%multiply_reduce_fusion", start - lag, 2 * US],
                ["%_assignment_call.1", start - lag + 3 * US, 1_997 * US]]
    return {"window": [0, 14 * MS], "devices": {DEV: ops}, "host": host,
            "programs": {DEV: programs}, "runtime": runtime}


def _run(rec, **kw):
    logged = []
    return SimpleNamespace(trace=rec, log=lambda what, **kv: logged.append(
        (what, kv)), logged=logged, **kw)


def test_offset_recovers_a_known_shift(bench_path):
    from lib import spans
    rec = _rows_record()
    off, upper, paired = spans.clock_offset(rec)
    # causality: the tightest pair sets the bound, so the estimate is the
    # lag less the shortest enqueue-to-start latency; the upper bound is
    # the lag and the time the runtime takes to see a program done
    assert paired == 3
    assert off == LAG - min(LATENCY)
    assert upper == LAG + 300 * US
    aligned = spans.shifted(rec, off)
    kernels = [s for n, s, _ in aligned["devices"][DEV]
               if n.startswith("%_assignment_call")]
    enqueued = [s for s, _ in rec["runtime"]["DoEnqueueProgram"]]
    assert all(k >= e for k, e in zip(kernels, enqueued))
    # a device clock ahead of the host's gives a negative offset
    assert spans.clock_offset(_rows_record(lag=-2 * MS))[0] == \
        -2 * MS - min(LATENCY)


def test_offset_needs_every_pair(bench_path):
    from lib import spans
    rec = _rows_record()
    runs = [[s, s + d] for s, d in rec["programs"][DEV]]
    enqueued = [[s, s + d] for s, d in rec["runtime"]["DoEnqueueProgram"]]
    assert spans.dispatch_bound(enqueued, runs[:-1]) is None
    assert spans.wait_bound([], runs) is None
    rec["programs"][DEV] = rec["programs"][DEV][:-1]   # one run missing
    assert spans.clock_offset(rec) == (None, None, 0)
    # a record as `lib.trace.record` makes it: no programs, no runtime
    bare = {k: rec[k] for k in ("window", "devices", "host")}
    assert spans.clock_offset(bare) == (None, None, 0)


# Two fits, each a seeding program and a solver program; the device's
# clock runs 5 ms behind the host's, far longer than any enqueue takes,
# so a loop that the device starts soon after the host's span opens
# reads, on the device's clock, before it.  The runtime enqueues the
# four programs 50, 100, 10 and 20 µs before they start (host clock) and
# sees them done 0.2, 0.4, 0.3 and 0.5 ms after they end.
FIT_LAG = 5 * MS
FIT_RUNS = ((10 * MS, 1 * MS, 50 * US, 200 * US),       # seeding
            (30 * MS, 40 * MS, 100 * US, 400 * US),     # solver
            (73 * MS, 1 * MS, 10 * US, 300 * US),
            (80 * MS, 15 * MS, 20 * US, 500 * US))
FIT = {"window": [0, 100 * MS],
       "devices": {DEV: [[name, s - FIT_LAG, d] for name, (s, d, _, _) in
                         zip(("%while.8", "%while.55") * 2, FIT_RUNS)]},
       "programs": {DEV: [[s - FIT_LAG, d] for s, d, _, _ in FIT_RUNS]},
       "runtime": {"DoEnqueueProgram": [[s - e, 30 * US]
                                        for s, _, e, _ in FIT_RUNS],
                   "tpu::System::Execute=>Done": [[s + d + w - 30 * US,
                                                   30 * US]
                                                  for s, d, _, w in FIT_RUNS]},
       "host": [["bench.window", 0, 100 * MS, 0],
                ["bench.fit", 0, 70 * MS, 0],
                ["repro.fit.seed", 9_900 * US, 200 * US, 0],
                ["repro.fit.solve", 12 * MS, 17 * MS, 0],
                ["repro.fit.result", 29 * MS, 43 * MS, 0],
                ["bench.fit", 70 * MS, 29 * MS, 0],
                ["repro.fit.seed", 72_900 * US, 200 * US, 0],
                ["repro.fit.solve", 73_200 * US, 4_800 * US, 0],
                ["repro.fit.result", 78 * MS, 18 * MS, 0]]}


def test_fit_offset_with_a_device_lag_past_dispatch(bench_path):
    from lib import spans
    # the loops lloyd_roofline reads, on the device's clock: the second
    # seeding loop starts inside the first fit's span there
    assert spans.solver_loops(FIT) == [[25 * MS, 65 * MS],
                                       [75 * MS, 90 * MS]]
    assert spans.clock_offset(FIT) == (FIT_LAG - 10 * US,
                                       FIT_LAG + 200 * US, 4)
    # on the host's clock the device is idle under the first solve, [12,
    # 29) ms, and under the second, [73.2, 78) ms, but for the second
    # seeding loop, [72.99, 73.99) ms
    rec = spans.shifted(FIT, FIT_LAG - 10 * US)
    assert spans.idle_under(rec, "repro.fit.solve") == pytest.approx(
        (17 + 4.8 - 0.79) * 1e-3)


def test_aligned_logs_the_offset_once(bench_path):
    from lib import spans
    run = _run(_rows_record())
    first = spans.aligned(run)
    assert spans.aligned(run) is first
    assert run.logged == [("clock", {"offset_ms": (LAG - 5 * US) / 1e6,
                                     "programs": 3,
                                     "upper_ms": (LAG + 300 * US) / 1e6})]


def test_chunk_copies_end_at_the_transfer(bench_path):
    from lib import spans
    rec = _rows_record()
    copies = spans.chunk_copies(rec)
    assert copies == [[r - 1_500 * US, r + COPIED] for r in RUNS]
    # the wait starts where the copy ends, inside the fetch span
    waits = spans.fetch_waits(rec)
    assert [w[0] for w in waits] == [r + COPIED for r in RUNS]
    assert [w[1] for w in waits] == [
        s + e for n, s, e, _ in rec["host"] if n == "repro.rows.fetch"]
    # a transfer that completes only after the next put started, or a
    # trace without the runtime's transfers, gives no copies
    late = _rows_record()
    late["runtime"]["tpu::System::TransferToDevice=>IssueEvent=>Done"][0] \
        = [RUNS[1] - 1_450 * US, 10 * US]
    assert spans.chunk_copies(late) is None
    assert spans.fetch_waits(late) is None
    late["runtime"] = {}
    assert spans.chunk_copies(late) is None


# The window is [1000, 11000); the device is busy [2000, 6000) and
# [9000, 10000); three host spans named "put" cover [0, 2500) (clipped
# to [1000, 2500) at the window's start), [5000, 8000) and [10500, 12000)
# (clipped to [10500, 11000) at its end).
IDLE = {
    "window": [1000, 11000],
    "devices": {DEV: [["%fusion.1", 2000, 4000], ["%copy", 9000, 1000]]},
    "host": [["bench.window", 1000, 10000, 0],
             ["put", 0, 2500, 0],
             ["put", 5000, 3000, 0],
             ["put", 10500, 1500, 0],
             ["fetch", 2000, 4000, 0]],
}


def test_idle_under_clips_and_attributes_overlap_only(bench_path):
    from lib import spans, trace
    # idle in [1000, 2000), [6000, 8000) and [10500, 11000)
    assert spans.idle_under(IDLE, "put") == pytest.approx(3.5e-6)
    assert spans.idle_under(IDLE, "fetch") == pytest.approx(0.0)
    assert spans.idle_under(IDLE, "no-such-span") is None
    assert spans.idle_in(IDLE, [[0, 2500], [5000, 8000]]) == \
        pytest.approx(3e-6)
    idle_s = trace.window_s(IDLE) * trace.idle_pct(IDLE) / 100
    assert spans.idle_under(IDLE, "put") + spans.idle_under(IDLE, "fetch") \
        <= idle_s + 1e-15
    no_ops = {**IDLE, "devices": {DEV: []}}
    assert spans.idle_under(no_ops, "put") is None


@pytest.mark.parametrize("span", ["repro.rows.put", "repro.rows.run",
                                  "repro.rows.fetch"])
def test_idle_under_never_exceeds_idle_pct(bench_path, span):
    from lib import spans, trace
    rec = spans.shifted(_rows_record(), LAG - min(LATENCY))
    window_idle = trace.window_s(rec) * trace.idle_pct(rec) / 100
    total = sum(spans.idle_under(rec, s) for s in (
        "repro.rows.put", "repro.rows.run", "repro.rows.fetch"))
    assert 0 <= spans.idle_under(rec, span) <= total <= window_idle + 1e-12
    # the copies and the waits after them split the chunks' time
    split = spans.idle_in(rec, spans.chunk_copies(rec)) + \
        spans.idle_in(rec, spans.fetch_waits(rec))
    assert total <= split <= window_idle + 1e-12


# One fit: a seeding loop, then the solver loop [100, 500) holding step
# and Anderson operations, a nested loop in the step scope (not a leaf),
# and operations outside the loop in either scope.
SCOPED = {
    "window": [0, 1000],
    "devices": {DEV: [
        ["%fusion.9", 20, 10],              # repro.step, before the loop
        ["%while.8", 40, 30],               # seeding loop
        ["%while.55", 100, 400],            # solver loop
        ["%fusion.1", 110, 200],            # repro.step
        ["%while.3", 320, 50],              # repro.step, a container
        ["%fusion.4", 330, 40],             # repro.step, inside %while.3
        ["%fusion.2", 400, 30],             # repro.aa
        ["%select.1", 440, 20],             # bookkeeping: no scope
        ["%fusion.7", 600, 50],             # repro.aa, after the loop
    ]},
    "scopes": {DEV: ["repro.step", None, None, "repro.step", "repro.step",
                     "repro.step", "repro.aa", None, "repro.aa"]},
    "host": [["bench.window", 0, 1000, 0], ["bench.fit", 0, 700, 0]],
}


def test_scope_shares_count_leaf_ops_inside_the_loop(bench_path):
    from lib import spans
    assert spans.solver_loops(SCOPED) == [[100, 500]]
    assert spans.loop_scope_pct(SCOPED, "repro.step") == pytest.approx(60.0)
    assert spans.loop_scope_pct(SCOPED, "repro.aa") == pytest.approx(7.5)
    assert spans.scope_seconds(SCOPED, "repro.aa", [[0, 1000]]) == \
        pytest.approx(8e-8)


def test_op_scope_is_the_innermost(bench_path):
    from lib import spans
    assert spans.op_scope("jit(f)/while/body/vmap(repro.aa)/div") == \
        "repro.aa"
    assert spans.op_scope("jit(f)/repro.step/jit(_where)/select_n") == \
        "repro.step"
    assert spans.op_scope("jit(f)/repro.step/repro.aa/add") == "repro.aa"
    assert spans.op_scope("jit(f)/while/body/add") is None
    assert spans.op_scope("") is None


def _xspace(spans):
    """A serialized XSpace: one TPU plane whose "XLA Modules" line runs
    one program and whose "XLA Ops" line runs five operations, the op
    names in their metadata's ``tf_op`` stat, and a host plane with two
    runtime events on two threads and one other event."""
    space = spans._xspace_class()()
    host = space.planes.add(name="/host:CPU")
    for key, name in ((1, "python-call"), (2, spans.ENQUEUE),
                      (3, spans.DONE)):
        host.event_metadata.add(key=key).value.name = name
    host.lines.add(name="python", timestamp_ns=100).events.add(
        metadata_id=1, offset_ps=0, duration_ps=9_000)
    host.lines.add(name="tasks", timestamp_ns=200).events.add(
        metadata_id=2, offset_ps=5_000, duration_ps=2_000)
    host.lines.add(name="worker", timestamp_ns=0).events.add(
        metadata_id=3, offset_ps=9_999, duration_ps=1_000)
    plane = space.planes.add(name=DEV)
    plane.stat_metadata.add(key=7).value.name = "tf_op"
    plane.stat_metadata.add(key=8).value.name = "hlo_category"
    for key, op_name in ((1, "jit(f)/while/body/vmap(repro.aa)/div:div"),
                         (2, "jit(f)/repro.step/dot_general:dot_general"),
                         (3, "jit(f)/while/body/add:add")):
        md = plane.event_metadata.add(key=key).value
        md.stats.add(metadata_id=8, str_value="loop fusion")
        md.stats.add(metadata_id=7, str_value=op_name)
    plane.event_metadata.add(key=4)              # an op with no op name
    plane.lines.add(name="XLA Modules", timestamp_ns=1_000).events.add(
        metadata_id=2, offset_ps=3_000_000, duration_ps=40_000)
    ops = plane.lines.add(name="XLA Ops")
    for mid in (2, 1, 3, 4, 1):
        ops.events.add(metadata_id=mid)
    return space.SerializeToString()


def test_device_scopes_follow_the_ops_line(bench_path):
    from lib import spans
    more = spans.extras(_xspace(spans))
    assert more["scopes"] == {DEV: [
        "repro.step", "repro.aa", None, None, "repro.aa"]}


def test_extras_read_programs_and_runtime_events(bench_path):
    from lib import spans
    more = spans.extras(_xspace(spans))
    # [line timestamp + offset, duration], in ns
    assert more["programs"] == {DEV: [[4_000, 40]]}
    assert more["runtime"] == {spans.ENQUEUE: [[205, 2]],
                               spans.DONE: [[9, 1]], spans.H2D_DONE: []}


def test_put_gbps_arithmetic(harness, bench_path):
    reader = harness.load_module("metrics", "put_gbps.assign")
    db = np.zeros((300, 128), np.float32)
    run = _run(_rows_record(), calls=[{"rows": 300}, {"rows": 300}], db=db)
    # 600 rows of 512 bytes over three copies of 1.5 + 0.6 ms
    assert reader.read(run) == pytest.approx(600 * 512 / 6.3e-3 / 1e9)


READERS = ["idle_solve_pct.fit", "step_pct.fit", "aa_pct.fit",
           "idle_put_pct.assign", "idle_fetch_pct.assign", "put_gbps.assign"]


def _without_program_spans(rec):
    return {**rec, "host": [h for h in rec["host"]
                            if not h[0].startswith("repro.")],
            "scopes": {dv: [None] * len(evs)
                       for dv, evs in rec["devices"].items()}}


@pytest.mark.parametrize("metric", READERS)
def test_readers_read_none_without_program_spans(harness, bench_path,
                                                 metric):
    reader = harness.load_module("metrics", metric)
    db = np.zeros((300, 128), np.float32)
    for rec in (_rows_record(), FIT, SCOPED):
        bare = _without_program_spans(rec)
        run = _run(bare, calls=[{"rows": 300}], db=db)
        assert reader.read(run) is None
        # a record as `lib.trace.record` makes it, with no scopes key
        run = _run({k: v for k, v in bare.items()
                    if k in ("window", "devices", "host")},
                   calls=[{"rows": 300}], db=db)
        assert reader.read(run) is None


@pytest.mark.parametrize("metric,rec", [
    ("idle_solve_pct.fit", "fit"), ("step_pct.fit", "scoped"),
    ("aa_pct.fit", "scoped"), ("idle_put_pct.assign", "rows"),
    ("idle_fetch_pct.assign", "rows"), ("put_gbps.assign", "rows")])
def test_readers_read_a_number_with_program_spans(harness, bench_path,
                                                  metric, rec):
    reader = harness.load_module("metrics", metric)
    rec = {"fit": FIT, "scoped": SCOPED, "rows": _rows_record()}[rec]
    run = _run(rec, calls=[{"rows": 300}],
               db=np.zeros((300, 128), np.float32))
    value = reader.read(run)
    assert value is not None and 0 < value


def test_spans_run_adds_each_cells_span_metrics(harness, bench_path):
    import spans_run
    for name, added in spans_run.SPAN_METRICS.items():
        workload, _ = spans_run.cell(name)
        base, _ = harness.cell(name)
        assert workload["per_layer"] == base["per_layer"] + added
        for metric in added:
            assert harness.load_module("metrics", metric).UNIT
    assert set(spans_run.SPAN_METRICS) <= {
        w["name"] for w in json.loads(
            (bench_path.parent / "BENCHMARK.json").read_text())["workloads"]}


def test_spans_run_rehearsal_is_the_harness_run(bench_path, capsys):
    import spans_run
    from lib import trace
    before = trace.record
    err = io.StringIO()
    with contextlib.redirect_stderr(err):
        rc = spans_run.main(["--workload", "ivf4096-sift128.assign",
                             "--seed", str(2**31 + 7), "--seconds", "0.5",
                             "--tiny"])
    assert rc == 3 and capsys.readouterr().out == ""
    line = json.loads([ln for ln in err.getvalue().splitlines()
                       if ln.startswith("{")][-1])
    assert line["correct"] is True
    # the CPU's trace has no device planes: the span metrics read None
    # and are left out of the line, as the harness leaves out any such
    assert not set(line["metrics"]) & set(
        spans_run.SPAN_METRICS["ivf4096-sift128.assign"])
    assert trace.record is before
