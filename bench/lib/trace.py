"""From a profiler trace to device busy time, kernel time and idle gaps.

`record` reads the ``.xplane.pb`` that ``jax.profiler`` writes and keeps
a compact record: the traced window, every device operation, and the
host spans.  Everything else here works on that record, so the CPU tests
check the reduction on a small recorded copy without a chip.

Record layout (times in ns from the start of the trace; a device
operation is named by its HLO instruction, ``%fusion.3``, without the
rest of its HLO text)::

    {"window": [start, end],
     "devices": {"/device:TPU:0": [[op_name, start, duration], ...]},
     "host": [[span_name, start, duration, line], ...]}

The window is the benchmark's own ``bench.window`` span.  Device
operations come from each device plane's "XLA Ops" line.  Host spans are
the benchmark's ``bench.*`` annotations plus every other host event on
the same thread, so an idle gap can be named by what the host was doing.
"""

from __future__ import annotations

import glob
import os
from typing import Optional

WINDOW_SPAN = "bench.window"
SPAN_PREFIX = "bench."
DEVICE_LINE = "XLA Ops"


def profile_options():
    """Host annotations and runtime events, without the Python tracer
    (which would record every Python call of the window)."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    return opts


def record(profile_dir: str) -> dict:
    """The compact record of the newest trace under ``profile_dir``."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(profile_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {profile_dir}")
    data = ProfileData.from_file(paths[-1])
    devices, host = {}, []
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                if line.name == DEVICE_LINE:
                    devices[plane.name] = [
                        [e.name.split(" = ", 1)[0], int(e.start_ns),
                         int(e.duration_ns)] for e in line.events]
        elif plane.name.startswith("/host:CPU"):
            for li, line in enumerate(plane.lines):
                events = [[e.name, int(e.start_ns), int(e.duration_ns), li]
                          for e in line.events]
                if any(e[0].startswith(SPAN_PREFIX) for e in events):
                    host.extend(events)
    win = [e for e in host if e[0] == WINDOW_SPAN]
    if not win:
        raise ValueError(f"the trace holds no {WINDOW_SPAN!r} span")
    start, dur = win[0][1], win[0][2]
    return {"window": [start, start + dur], "devices": devices,
            "host": host}


def _merged(intervals):
    """Union of [start, end) intervals, sorted and disjoint."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def busy_intervals(rec: dict, device: str):
    """Merged intervals in which an operation ran on ``device``, clipped
    to the window."""
    w0, w1 = rec["window"]
    clipped = [(max(s, w0), min(s + d, w1))
               for _, s, d in rec["devices"].get(device, [])]
    return _merged([[s, e] for s, e in clipped if e > s])


def window_s(rec: dict) -> float:
    w0, w1 = rec["window"]
    return (w1 - w0) / 1e9


def busy_s(rec: dict) -> float:
    """Seconds in which an operation ran, averaged over the devices that
    ran any; 0 when no device operation was traced."""
    used = [dv for dv, evs in rec["devices"].items() if evs]
    if not used:
        return 0.0
    total = sum(e - s for dv in used for s, e in busy_intervals(rec, dv))
    return total / len(used) / 1e9


def idle_pct(rec: dict) -> Optional[float]:
    """100·(1 − busy/window), or None when no device operation ran."""
    busy = busy_s(rec)
    if busy <= 0:
        return None
    return 100.0 * (1.0 - busy / window_s(rec))


def op_seconds(rec: dict) -> dict:
    """Device seconds per operation name inside the window, summed over
    devices."""
    w0, w1 = rec["window"]
    out: dict = {}
    for evs in rec["devices"].values():
        for name, s, d in evs:
            t = min(s + d, w1) - max(s, w0)
            if t > 0:
                out[name] = out.get(name, 0.0) + t / 1e9
    return out


def kernel_events(rec: dict, match) -> list:
    """Device durations (s) of the window's operations whose name
    ``match(name)`` accepts."""
    w0, w1 = rec["window"]
    return [d / 1e9 for evs in rec["devices"].values()
            for name, s, d in evs if match(name) and w0 <= s < w1]


def longest_in_spans(rec: dict, span: str, match) -> list:
    """For each host span named ``span`` in the window, the duration (s)
    of the longest device operation that ``match(name)`` accepts and
    that starts inside it; None for a span that holds no such
    operation."""
    w0, w1 = rec["window"]
    ops = [(s, d) for evs in rec["devices"].values()
           for name, s, d in evs if match(name)]
    out = []
    for name, s, d, _ in sorted(rec["host"], key=lambda h: h[1]):
        if name != span or not w0 <= s < w1:
            continue
        inside = [od for os_, od in ops if s <= os_ < s + d]
        out.append(max(inside) / 1e9 if inside else None)
    return out


def _innermost(host, t, line=None):
    """The shortest host span that covers time ``t`` (on ``line``)."""
    best = None
    for name, s, d, li in host:
        if s <= t < s + d and (line is None or li == line) \
                and (best is None or d < best[2]):
            best = (name, s, d, li)
    return best


def idle_gaps(rec: dict, top: int = 10) -> list:
    """The ``top`` longest idle gaps of the first device that ran any
    operation, each named by what the host was doing at its midpoint:
    the benchmark's innermost ``bench.*`` span, and the innermost other
    event on that span's thread."""
    used = sorted(dv for dv, evs in rec["devices"].items() if evs)
    if not used:
        return []
    w0, w1 = rec["window"]
    busy = busy_intervals(rec, used[0])
    edges = [w0] + [x for iv in busy for x in iv] + [w1]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    gaps.sort(key=lambda g: g[0] - g[1])
    ours = [h for h in rec["host"] if h[0].startswith(SPAN_PREFIX)
            and h[0] != WINDOW_SPAN]
    out = []
    for s, e in gaps[:top]:
        mid = (s + e) // 2
        span = _innermost(ours, mid)
        name = span[0] if span else "none"
        if span is not None:
            inner = _innermost([h for h in rec["host"]
                                if not h[0].startswith(SPAN_PREFIX)],
                               mid, line=span[3])
            if inner is not None:
                name = f"{name}/{inner[0]}"
        out.append([name, (e - s) / 1e9])
    return out


def top_ops(rec: dict, top: int = 10) -> list:
    """The ``top`` device operations by total time in the window."""
    ops = sorted(op_seconds(rec).items(), key=lambda kv: -kv[1])
    return [[name, sec] for name, sec in ops[:top]]


def breakdown(rec: dict) -> dict:
    return {"device_ops": top_ops(rec), "idle_gaps": idle_gaps(rec)}
