"""Serve traffic: open-loop requests to a ``KMeansServer``.

The server holds a K-row codebook drawn from the configuration's data
distribution (K rows sampled from X) and serves the configuration's
``server`` settings.  One client thread sends requests on an open-loop
schedule: arrivals of a Poisson process at ``params.rate_per_s``, and
request sizes of ``params.single_share`` single rows, the rest
log-uniform over ``params.batch_rows`` (which a mix of single rows
alone leaves out).  The multiset of gaps and sizes
is drawn once from ``params.traffic_seed`` (for the run's length), and
``--seed`` shuffles each and draws the rows, so every run offers the same
load in another order.  The rows are fresh draws of the distribution.

End to end: ``serve_p95_ms``, the 95th percentile over every request due
in the window of the time from when it was due to when its answer came;
a request that fails or never answers counts as infinitely late.  The
check holds every answer to the reference's nearest centroid
(``label_gap``) and counts requests without an answer (``unanswered``).
"""

from __future__ import annotations

import math
import threading
import time

import jax
import numpy as np

from lib import codebook, gen, reference

DRAIN_S = 60.0          # how long past the close to wait for answers


class Sink:
    """The server's ``metrics=`` sink: one record per micro-batch."""

    def __init__(self):
        self.records = []

    def log_scalars(self, step, scalars):
        self.records.append((time.perf_counter(), dict(scalars)))


def schedule(params: dict, seconds: float, seed: int):
    """(due times in s, request sizes) for one run: the fixed multiset of
    the traffic seed, shuffled by ``seed``, cut at ``seconds``."""
    rate = params["rate_per_s"]
    n = int(math.ceil(rate * seconds * 1.25)) + 16
    fixed = np.random.default_rng(params["traffic_seed"])
    gaps = fixed.exponential(1.0 / rate, n)
    lo, hi = params.get("batch_rows", (1, 1))
    sizes = np.where(fixed.random(n) < params["single_share"], 1,
                     np.floor(np.exp(fixed.uniform(np.log(lo),
                                                   np.log(hi + 1), n))))
    rng = np.random.default_rng(seed)
    due = np.cumsum(rng.permutation(gaps))
    sizes = rng.permutation(sizes.astype(np.int64))
    keep = due < seconds
    return due[keep], sizes[keep]


def setup(run):
    from repro.serving import KMeansServer
    t0 = time.perf_counter()
    run.centroids = codebook.centroids(run)
    run.due, run.sizes = schedule(run.params, run.seconds, run.seed)
    total = int(run.sizes.sum())
    rows = np.asarray(gen.fresh_rows(run.config["data"], run.seed,
                                     max(total, 1), stream=2))
    offsets = np.concatenate([[0], np.cumsum(run.sizes)])
    run.requests = [rows[offsets[i]:offsets[i + 1]]
                    for i in range(len(run.sizes))]
    t1 = time.perf_counter()
    run.sink = Sink()
    srv = run.config["server"]
    run.server = KMeansServer(
        codebook.model(run, run.centroids), batch_size=srv["batch_size"],
        flush_ms=srv["flush_ms"], approx=srv["approx"],
        metrics=run.sink).start()
    # the server compiled its one padded shape; one pass of each request
    # size class warms the host path too
    for m in (1, 16, srv["batch_size"] + 1):
        run.server.submit(rows[:m]).result(timeout=DRAIN_S)
    run.sink.records.clear()
    run.log("setup", data_s=round(t1 - t0, 3),
            server_warmup_s=round(time.perf_counter() - t1, 3),
            requests=len(run.due), rows=total)


def window(run):
    n = len(run.due)
    run.sent = np.full(n, np.nan)
    run.done = np.full(n, np.nan)
    run.answers = [None] * n
    run.failures = []
    lock = threading.Lock()
    pending = []
    t0 = time.perf_counter()

    def finished(i, fut):
        t = time.perf_counter() - t0
        try:
            answer = fut.result()
        except Exception as e:        # counted as failed, never as fast
            with lock:
                run.failures.append(f"request {i}: {e!r}")
            return
        run.answers[i] = answer
        run.done[i] = t

    for i in range(n):
        wait = run.due[i] - (time.perf_counter() - t0)
        if wait > 0:
            time.sleep(wait)
        run.sent[i] = time.perf_counter() - t0
        with jax.profiler.TraceAnnotation("bench.submit"):
            fut = run.server.submit(run.requests[i])
        fut.add_done_callback(lambda f, i=i: finished(i, f))
        pending.append(fut)
    deadline = time.perf_counter() + DRAIN_S
    with jax.profiler.TraceAnnotation("bench.drain"):
        for fut in pending:
            try:
                fut.result(timeout=max(deadline - time.perf_counter(), 0))
            except Exception:
                pass                  # recorded by the callback


def latencies_ms(run):
    lat = (run.done - run.due) * 1e3
    return np.where(np.isnan(lat), np.inf, lat)


def end_to_end(run):
    lat = latencies_ms(run)
    return {"metrics": {"serve_p95_ms": (float(np.percentile(lat, 95)),
                                         "ms")},
            "attempted": int(len(lat)),
            "failed": int(np.sum(~np.isfinite(lat)))}


def release(run):
    run.server.stop()
    run.server = None


def check(run):
    got = [a for a in run.answers if a is not None]
    rows = [r for r, a in zip(run.requests, run.answers) if a is not None]
    gap = 0.0
    if got:
        x = np.concatenate(rows)
        want, _ = reference.assign(x, run.centroids)
        gap = reference.label_gap(x, np.asarray(run.centroids),
                                  np.concatenate(got), want)
    limits = run.workload["limits"]
    return {"label_gap": {"value": gap, "limit": limits["label_gap"]},
            "unanswered": {"value": len(run.answers) - len(got),
                           "limit": 0}}
