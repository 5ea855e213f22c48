"""On-chip benchmark of AA K-Means: one cell, one run, one result line.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>
    JAX_PLATFORMS=cpu python bench/run.py --workload <cell> --seed 1 \\
        --seconds 2 --trace 1 --tiny          # CPU rehearsal, never a result

Everything is found by name: the cell in ``bench/workloads/<cell>.json``,
its configuration in ``bench/configs/<config>.json``, its traffic module
in ``bench/traffic/<kind>.py`` and each per-layer metric's reader in
``bench/metrics/<metric>.py``.

A run enables the persistent compilation cache, refuses any platform but
a TPU with a ``device_kind`` in the peaks table, has the traffic module build the
cell's data on the device from ``--seed`` and warm up its shapes
(``setup_s`` is everything up to here, from process start), runs the
window, reads the chip's peak memory, frees the program's state, checks
the window's answers against the plain reference (`lib/reference.py`),
and prints one JSON line.  ``--trace 0`` reports the cell's end-to-end
metrics, ``--trace 1`` traces the window and reports its per-layer
metrics.  The numbers compared, each with its limit, are the last lines
on standard error and the last key (``checks``) of the result line.

Exit codes: 0 a result was printed; 1 the run failed; 2 the checkout or
the device cannot run this cell; 3 a ``--tiny`` rehearsal finished (it
never prints a result).
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse                                    # noqa: E402
import gc                                          # noqa: E402
import importlib.util                              # noqa: E402
import json                                        # noqa: E402
import math                                        # noqa: E402
import sys                                         # noqa: E402
import tempfile                                    # noqa: E402
import traceback                                   # noqa: E402
from pathlib import Path                           # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))


class Refused(RuntimeError):
    """This checkout, device or cell cannot give a result (exit 2)."""


def load_json(kind: str, name: str) -> dict:
    path = BENCH / kind / f"{name}.json"
    if not path.is_file():
        raise Refused(f"no {kind[:-1]} named {name!r} ({path} is missing)")
    return json.loads(path.read_text())


def load_module(kind: str, name: str):
    """``bench/<kind>/<name>.py`` as a module (names may hold dots)."""
    path = BENCH / kind / f"{name}.py"
    if not path.is_file():
        raise Refused(f"no {kind} module named {name!r} ({path} is missing)")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def cell(name: str, tiny: bool = False):
    """(workload, config) of a cell; ``tiny`` applies the configuration's
    and the workload's ``tiny`` overrides (CPU rehearsal sizes)."""
    workload = load_json("workloads", name)
    config = load_json("configs", workload["config"])
    if tiny:
        for doc in (config, workload):
            for block, over in doc.get("tiny", {}).items():
                doc[block] = {**doc.get(block, {}), **over}
    return workload, config


class Run:
    """What one run knows: its arguments, cell and device, and whatever
    the traffic module records for the metric readers and the check."""

    def __init__(self, args, workload, config):
        self.args = args
        self.seed = args.seed
        self.seconds = args.seconds
        self.workload = workload
        self.config = config
        self.params = workload.get("params", {})
        self.peaks = None
        self.device = None
        self.trace = None              # compact trace record (--trace 1)
        self.compile_spans = []        # (start, end) of tracing/compiling

    def log(self, what: str, **kv):
        """One line of facts on standard error, before the checks."""
        print(f"bench: {what} " + " ".join(f"{k}={v}" for k, v in kv.items()),
              file=sys.stderr, flush=True)


COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/jaxpr_to_mlir_module_duration",
                  "/jax/core/compile/backend_compile_duration")


def _device(args, workload):
    import jax
    from lib.peaks import UnknownDevice, peaks_for
    try:
        devices = jax.devices()
    except RuntimeError as e:
        raise Refused(f"JAX started no backend: {e}") from None
    dev = devices[0]
    if not args.tiny:
        if dev.platform != "tpu":
            raise Refused(f"JAX finds no TPU (platform {dev.platform!r})")
        if len(devices) < workload["chips"]:
            raise Refused(f"the cell needs {workload['chips']} chips; JAX "
                          f"finds {len(devices)}")
    try:
        peaks = peaks_for(dev.device_kind) if dev.platform == "tpu" else None
    except UnknownDevice as e:
        raise Refused(str(e)) from None
    used = devices[:workload["chips"]]
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(used)}, used, peaks


def _memory_peak(devices) -> int:
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks)


def _metric(value, unit):
    return {"value": value, "unit": unit}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--tiny", action="store_true",
                    help="CPU rehearsal at the configuration's tiny sizes; "
                         "never prints a result")
    args = ap.parse_args(argv)

    try:
        if not (ROOT / "src" / "repro").is_dir():
            raise Refused(f"no repro package under {ROOT / 'src'}: run from "
                          f"a checkout of the repository")
        workload, config = cell(args.workload, tiny=args.tiny)
        sys.path.insert(0, str(ROOT / "src"))
        device, devices, peaks = _device(args, workload)
    except Refused as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2

    import jax
    from lib import trace as tr
    from repro.runtime.compile_cache import enable_compile_cache
    if not args.tiny:
        enable_compile_cache()
        # every program goes to the persistent cache, so that a cell's
        # second run in a checkout compiles nothing
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)

    run = Run(args, workload, config)
    run.peaks, run.device = peaks, device
    traffic = load_module("traffic", workload["traffic"])
    try:
        run.log("start", import_s=round(time.perf_counter() - T_START, 3))
        traffic.setup(run)
        setup_s = time.perf_counter() - T_START
        # the benchmark's own set-up objects (requests, rows) leave the
        # collector's generations, so a collection in the window does
        # not walk them
        gc.freeze()

        def on_span(event, start, end, **_):
            if event in COMPILE_EVENTS:
                run.compile_spans.append((start, end))
        jax.monitoring.register_event_time_span_listener(on_span)
        with tempfile.TemporaryDirectory() as tmp:
            if args.trace:
                jax.profiler.start_trace(
                    tmp, profiler_options=tr.profile_options())
            try:
                with jax.profiler.TraceAnnotation(tr.WINDOW_SPAN):
                    traffic.window(run)
            finally:
                if args.trace:
                    jax.profiler.stop_trace()
            if args.trace:
                run.trace = tr.record(tmp)
        jax.monitoring.unregister_event_time_span_listener(on_span)
        device["memory_peak_bytes"] = _memory_peak(devices)
        e2e = traffic.end_to_end(run)
        if args.trace:
            layer = {}
            for name in workload["per_layer"]:
                reader = load_module("metrics", name)
                value = reader.read(run)
                if value is not None:
                    layer[name] = _metric(value, reader.UNIT)
            device["busy_s"] = tr.busy_s(run.trace)
            device["window_s"] = tr.window_s(run.trace)
        traffic.release(run)
        checks = traffic.check(run)
    except Exception:                # the run failed: no result line
        traceback.print_exc()
        print("bench: FAILED", file=sys.stderr)
        return 1

    correct = all(c["value"] <= c["limit"] for c in checks.values())
    for c in checks.values():            # JSON has no inf or nan
        if not math.isfinite(c["value"]):
            c["value"] = repr(c["value"])
    metrics = layer if args.trace else {
        **{k: _metric(v[0], v[1]) for k, v in e2e["metrics"].items()
           if k in workload["end_to_end"]},
        "setup_s": _metric(setup_s, "s")}
    for name, m in metrics.items():
        if isinstance(m["value"], float) and not math.isfinite(m["value"]):
            m["value"] = None
    line = {"correct": correct, "attempted": e2e["attempted"],
            "failed": e2e["failed"], "metrics": metrics, "device": device}
    if args.trace:
        line["breakdown"] = tr.breakdown(run.trace)
    line["checks"] = checks
    for name, c in checks.items():
        print(f"check {name}: {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    if args.tiny:
        print(json.dumps(line), file=sys.stderr)
        print("bench: --tiny rehearsal finished; not a chip result",
              file=sys.stderr)
        return 3
    print(json.dumps(line))
    return 0


if __name__ == "__main__":
    sys.exit(main())
