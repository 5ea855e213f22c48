"""The program's own spans and scopes in a profiler trace.

The program marks its host phases with ``repro.*`` profiler spans
(``repro.fit.seed``/``solve``/``result`` in `AAKMeans.fit`,
``repro.rows.put``/``run``/``fetch`` around each chunk of the chunked
inference path) and its device work with ``repro.*`` named scopes
(``repro.step`` on the backend step, ``repro.aa`` on the Anderson solve
and m-adjustment), which reach each HLO operation's ``op_name``.  This
module reads both, with a few events of the TPU runtime, from the
compact record of `lib.trace`:

- `extend` adds to `trace.record`'s record what `extras` reads from
  the same ``.xplane.pb``: each device operation's scope, each program
  run on the device, and the TPU runtime's host events named in
  ``RUNTIME`` (another runtime's trace holds none, and the readers that
  need them read None);
- `clock_offset` puts the device on the host's clock, by causality,
  before any device idle time is laid against a host interval;
- `chunk_copies` and `fetch_waits` split each chunk of the chunked
  inference path into the copy of its rows to the device and the wait
  that follows: ``repro.rows.put`` only stages a chunk on the host, and
  the runtime relayouts and copies it after the span has closed;
- `idle_in`, `scope_seconds` and `loop_scope_pct` are what the
  per-layer readers in ``bench/metrics/`` divide.

A record of a program without the spans or scopes (an older checkout)
reads None here, never 0.
"""

from __future__ import annotations

import bisect
import glob
import os
import re
from typing import Optional

from lib import trace

SCOPE = re.compile(r"repro\.\w+")
# an operation that holds others on the device's line: its time is its
# body's, so it is not a leaf
CONTAINERS = ("%while", "%conditional")
FIT_SPAN = "bench.fit"
PROGRAM_LINE = "XLA Modules"
# the TPU runtime's host events: a program's enqueue to the device, the
# host's handling of its completion, and the end of a host-to-device
# transfer
ENQUEUE = "DoEnqueueProgram"
DONE = "tpu::System::Execute=>Done"
H2D_DONE = "tpu::System::TransferToDevice=>IssueEvent=>Done"
RUNTIME = (ENQUEUE, DONE, H2D_DONE)


def op_scope(op_name: str) -> Optional[str]:
    """The innermost ``repro.*`` scope of an op name such as
    ``jit(f)/while/body/vmap(repro.aa)/div``, or None."""
    found = SCOPE.findall(op_name or "")
    return found[-1] if found else None


def _xspace_class():
    """A message class for the part of the profiler's ``XSpace`` proto
    read here (planes, their lines' events, event and stat metadata),
    declared in a private pool: field numbers as in the profiler's
    ``xplane.proto``; every other field is skipped on parsing."""
    from google.protobuf import descriptor_pb2, descriptor_pool
    from google.protobuf import message_factory
    fdp = descriptor_pb2.FieldDescriptorProto
    one, many = fdp.LABEL_OPTIONAL, fdp.LABEL_REPEATED
    i64, text, msg = fdp.TYPE_INT64, fdp.TYPE_STRING, fdp.TYPE_MESSAGE
    schema = {
        "XSpace": [("planes", 1, many, msg, "XPlane")],
        "XPlane": [("name", 2, one, text, None),
                   ("lines", 3, many, msg, "XLine"),
                   ("event_metadata", 4, many, msg, "EventMetadataEntry"),
                   ("stat_metadata", 5, many, msg, "StatMetadataEntry")],
        "EventMetadataEntry": [("key", 1, one, i64, None),
                               ("value", 2, one, msg, "XEventMetadata")],
        "StatMetadataEntry": [("key", 1, one, i64, None),
                              ("value", 2, one, msg, "XStatMetadata")],
        "XLine": [("name", 2, one, text, None),
                  ("timestamp_ns", 3, one, i64, None),
                  ("events", 4, many, msg, "XEvent")],
        "XEvent": [("metadata_id", 1, one, i64, None),
                   ("offset_ps", 2, one, i64, None),
                   ("duration_ps", 3, one, i64, None)],
        "XEventMetadata": [("name", 2, one, text, None),
                           ("stats", 5, many, msg, "XStat")],
        "XStat": [("metadata_id", 1, one, i64, None),
                  ("str_value", 5, one, text, None)],
        "XStatMetadata": [("name", 2, one, text, None)],
    }
    f = descriptor_pb2.FileDescriptorProto(
        name="bench_xplane.proto", package="bench_xplane", syntax="proto3")
    for name, fields in schema.items():
        m = f.message_type.add(name=name)
        for fname, number, label, kind, ref in fields:
            m.field.add(name=fname, number=number, label=label, type=kind,
                        type_name=f".bench_xplane.{ref}" if ref else None)
    pool = descriptor_pool.DescriptorPool()
    pool.Add(f)
    return message_factory.GetMessageClass(
        pool.FindMessageTypeByName("bench_xplane.XSpace"))


def _interval(line, ev) -> list:
    """[start, duration] (ns) of an event, on the clock of the record."""
    return [line.timestamp_ns + ev.offset_ps // 1000, ev.duration_ps // 1000]


def extras(xspace: bytes) -> dict:
    """What `trace.record` leaves out of a serialized ``XSpace``:

    - ``scopes``: ``{device plane: [scope or None, ...]}`` for the
      operations of each device's "XLA Ops" line, in the line's order (a
      TPU trace gives an operation's op name as the ``tf_op`` stat of the
      event's metadata);
    - ``programs``: ``{device plane: [[start, duration], ...]}``, each
      program run of the device's "XLA Modules" line;
    - ``runtime``: ``{name: [[start, duration], ...]}`` for the host
      events named in ``RUNTIME``, on every host thread."""
    scopes, programs = {}, {}
    runtime = {name: [] for name in RUNTIME}
    for plane in _xspace_class().FromString(xspace).planes:
        if plane.name.startswith("/host:CPU"):
            wanted = {e.key: e.value.name for e in plane.event_metadata
                      if e.value.name in runtime}
            for line in plane.lines:
                for ev in line.events:
                    if ev.metadata_id in wanted:
                        runtime[wanted[ev.metadata_id]].append(
                            _interval(line, ev))
        if not plane.name.startswith("/device:"):
            continue
        tf_op = {e.key for e in plane.stat_metadata
                 if e.value.name == "tf_op"}
        op_name = {e.key: next((s.str_value for s in e.value.stats
                                if s.metadata_id in tf_op), "")
                   for e in plane.event_metadata}
        for line in plane.lines:
            if line.name == trace.DEVICE_LINE:
                scopes[plane.name] = [op_scope(op_name.get(ev.metadata_id))
                                      for ev in line.events]
            elif line.name == PROGRAM_LINE:
                programs[plane.name] = [_interval(line, ev)
                                        for ev in line.events]
    return {"scopes": scopes, "programs": programs,
            "runtime": {k: sorted(v) for k, v in runtime.items()}}


def extend(rec: dict, profile_dir: str) -> dict:
    """``rec``, the `trace.record` of the newest trace under
    ``profile_dir``, with the keys of `extras`.  ``scopes`` is parallel
    to ``devices``, so that one HLO name in two programs cannot collide.
    `jax.profiler.ProfileData` does not expose an event's metadata stats,
    so the ``.xplane.pb`` is read a second time as a proto."""
    paths = sorted(glob.glob(os.path.join(profile_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    with open(paths[-1], "rb") as fh:
        more = extras(fh.read())
    for dv, ops in rec["devices"].items():
        if len(more["scopes"].get(dv, ())) != len(ops):
            raise ValueError(f"{dv}: the proto's {trace.DEVICE_LINE} line "
                             f"does not match the profile's")
    more["scopes"] = {dv: more["scopes"][dv] for dv in rec["devices"]}
    return {**rec, **more}


# -- host spans -------------------------------------------------------------

def host_spans(rec: dict, name: str) -> list:
    """[start, end] of each host span named ``name`` that starts inside
    the window, in start order."""
    w0, w1 = rec["window"]
    return sorted([s, s + d] for n, s, d, _ in rec["host"]
                  if n == name and w0 <= s < w1)


def chunk_copies(rec: dict) -> Optional[list]:
    """[start, end] of each chunk's copy to the device, for the window's
    ``repro.rows.put`` spans in order: from the span's start to the end
    of the first runtime transfer that completes after it (``H2D_DONE``).
    The span stages the rows on the host; the runtime then relayouts
    them into the device's tiled layout and copies them, while the host
    dispatches the chunk's program and waits in ``repro.rows.fetch``.
    None when the window holds no put, or a put's transfer does not
    complete before the next put starts."""
    puts = host_spans(rec, "repro.rows.put")
    ends = sorted(s + d for s, d in rec.get("runtime", {}).get(H2D_DONE, ()))
    if not puts or not ends:
        return None
    out = []
    for k, (s, _) in enumerate(puts):
        i = bisect.bisect_right(ends, s)
        if i == len(ends) or (k + 1 < len(puts) and ends[i] >= puts[k + 1][0]):
            return None
        out.append([s, ends[i]])
    return out


def fetch_waits(rec: dict) -> Optional[list]:
    """[start, end] of each chunk's wait for its program and its answer's
    copy back: the part of its ``repro.rows.fetch`` span after its rows'
    copy completed (`chunk_copies`).  None where `chunk_copies` is, or
    when the window's fetches and puts differ in number."""
    copies = chunk_copies(rec)
    fetches = host_spans(rec, "repro.rows.fetch")
    if copies is None or len(fetches) != len(copies):
        return None
    return [[max(s, c[1]), e] for (s, e), c in zip(fetches, copies)
            if e > max(s, c[1])]


# -- device operations ------------------------------------------------------

def _ops(rec: dict, match) -> list:
    """[start, end] of the first used device's operations whose name
    ``match`` accepts, over the whole trace, in start order."""
    used = sorted(dv for dv, evs in rec["devices"].items() if evs)
    if not used:
        return []
    return sorted([s, s + d] for n, s, d in rec["devices"][used[0]]
                  if match(n))


def is_solver_loop(name: str) -> bool:
    return name.startswith("%while")


def solver_loops(rec: dict) -> Optional[list]:
    """[start, end] of each fit's solver loop: the longest ``while``
    operation that starts inside its ``bench.fit`` span, the choice
    `metrics/lloyd_roofline.py` makes.  None when a fit's span holds no
    loop or the window holds no fit."""
    loops = _ops(rec, is_solver_loop)
    out = []
    for s, e in host_spans(rec, FIT_SPAN):
        inside = [op for op in loops if s <= op[0] < e]
        if not inside:
            return None
        out.append(max(inside, key=lambda op: op[1] - op[0]))
    return out or None


def scope_seconds(rec: dict, scope: str, intervals) -> Optional[float]:
    """Device seconds of the leaf operations (no ``while`` or other
    operation that holds a body) whose scope is ``scope`` and that start
    inside one of ``intervals`` (device clock), summed over devices.
    None when no operation of the record carries a ``repro.*`` scope."""
    if not any(sc for scs in rec.get("scopes", {}).values() for sc in scs):
        return None
    intervals = trace._merged(intervals)
    starts = [a for a, _ in intervals]
    total = 0
    for dv, evs in rec["devices"].items():
        for (name, s, d), sc in zip(evs, rec["scopes"].get(dv, ())):
            i = bisect.bisect_right(starts, s) - 1
            if sc == scope and i >= 0 and s < intervals[i][1] \
                    and not name.startswith(CONTAINERS):
                total += d
    return total / 1e9


# -- the two clocks ---------------------------------------------------------

def dispatch_bound(spans: list, ops: list) -> Optional[int]:
    """Lower bound (ns) on the device-to-host offset: the k-th host event
    of ``spans`` dispatched the k-th run of ``ops`` (both [start, end],
    host and device clock), and nothing runs before its dispatch.  None
    when the counts differ or are 0."""
    if not spans or len(spans) != len(ops):
        return None
    return max(h[0] - op[0] for h, op in zip(spans, ops))


def wait_bound(spans: list, ops: list) -> Optional[int]:
    """Upper bound (ns) on the offset: the k-th host event handled the
    end of the k-th run, which so ended before the event did."""
    if not spans or len(spans) != len(ops):
        return None
    return min(h[1] - op[1] for h, op in zip(spans, ops))


def clock_offset(rec: dict):
    """(offset ns, upper bound ns, programs paired), or (None, None, 0).

    The offset is the smallest shift that puts every program run of the
    first used device (its "XLA Modules" line) at or after the runtime's
    enqueue of it (``ENQUEUE``, the k-th with the k-th); the upper bound
    puts every run's end before the runtime's handling of its completion
    (``DONE``).  A program dispatched by the host waits there for its
    inputs' copies, so the host's own spans (``repro.rows.run``) give a
    bound looser by that copy.  None when the counts differ, as on a
    trace that cuts a program off or a run of several devices."""
    used = sorted(dv for dv, evs in rec["devices"].items() if evs)
    runs = sorted([s, s + d] for s, d in
                  rec.get("programs", {}).get(used[0], ())) if used else []
    runtime = rec.get("runtime", {})
    enqueued, done = ([[s, s + d] for s, d in runtime.get(name, ())]
                      for name in (ENQUEUE, DONE))
    lo = dispatch_bound(enqueued, runs)
    if lo is None:
        return None, None, 0
    return lo, wait_bound(done, runs), len(runs)


def shifted(rec: dict, offset_ns: int) -> dict:
    """The record with every device operation moved by ``offset_ns``."""
    return {**rec, "devices": {
        dv: [[n, s + offset_ns, d] for n, s, d in evs]
        for dv, evs in rec["devices"].items()}}


def aligned(run) -> Optional[dict]:
    """The run's record on the host's clock, once per run (the offset and
    its upper bound are logged on standard error); None when no pairing
    fixes the offset."""
    if not hasattr(run, "aligned_trace"):
        off, upper, paired = clock_offset(run.trace)
        run.aligned_trace = None if off is None else shifted(run.trace, off)
        if off is not None:
            run.log("clock", offset_ms=off / 1e6, programs=paired,
                    upper_ms=None if upper is None else upper / 1e6)
    return run.aligned_trace


# -- idle time under host intervals -----------------------------------------

def _overlap_ns(a: list, b: list) -> int:
    """Length of the intersection of two sorted, disjoint interval
    lists."""
    i = j = total = 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        total += max(hi - lo, 0)
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def idle_in(rec: dict, intervals) -> Optional[float]:
    """Seconds of the window in which the device was idle inside the
    host-clock ``intervals`` ([start, end]), averaged over the devices
    that ran any operation: the intervals, clipped to the window, less
    the busy time they overlap.  Give it a record on the host's clock
    (`aligned`).  None when no interval meets the window or no device
    operation ran."""
    used = [dv for dv, evs in rec["devices"].items() if evs]
    w0, w1 = rec["window"]
    spans = trace._merged([[max(s, w0), min(e, w1)] for s, e in intervals
                           if s < w1 and e > w0])
    if not used or not spans:
        return None
    covered = sum(e - s for s, e in spans)
    busy = sum(_overlap_ns(spans, trace.busy_intervals(rec, dv))
               for dv in used) / len(used)
    return (covered - busy) / 1e9


def idle_under(rec: dict, span: str) -> Optional[float]:
    """`idle_in` the host spans named ``span``."""
    return idle_in(rec, [[s, s + d] for n, s, d, _ in rec["host"]
                         if n == span])


# -- what the readers divide ------------------------------------------------

def idle_pct_in(run, intervals) -> Optional[float]:
    """100·(idle seconds inside the host-clock ``intervals``) / window,
    the device put on the host's clock; None for no intervals."""
    rec = aligned(run) if intervals else None
    idle = idle_in(rec, intervals) if rec is not None else None
    return None if idle is None else 100.0 * idle / trace.window_s(rec)


def loop_scope_pct(rec: dict, scope: str) -> Optional[float]:
    """100·(device time of ``scope``'s leaf operations inside the fits'
    solver loops) / the loops' device time."""
    loops = solver_loops(rec)
    inside = scope_seconds(rec, scope, loops) if loops else None
    if inside is None:
        return None
    return 100.0 * inside / (sum(e - s for s, e in loops) / 1e9)
