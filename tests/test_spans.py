"""The program's profiler spans and device scopes.

Host spans (``repro.fit.*`` around `AAKMeans.fit`'s phases,
``repro.rows.*`` around each chunk of the chunked inference path) land
in a ``jax.profiler`` trace; device scopes (``repro.step`` on the
backend step, ``repro.aa`` on the Anderson solve and m-adjustment) reach
the compiled HLO's ``op_name`` metadata.  The trace is reduced with the
benchmark's own `bench/lib/trace.record`, as the on-chip benchmark does.
"""

import re
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.api import AAKMeans
from repro.core.backends import get_backend
from repro.core.kmeans import KMeansConfig, aa_kmeans, aa_kmeans_batched
from repro.core.minibatch import (MiniBatchConfig, minibatch_init,
                                  minibatch_iteration)
from repro.data.synthetic import make_blobs

BENCH = Path(__file__).resolve().parents[1] / "bench"


@pytest.fixture(scope="module")
def trace_lib():
    if str(BENCH) not in sys.path:
        sys.path.insert(0, str(BENCH))
    from lib import trace
    return trace


def _traced(trace_lib, tmp_path, fn):
    """Run ``fn`` under the profiler inside a ``bench.window`` span and
    return the names of the ``repro.*`` host spans in start order."""
    jax.profiler.start_trace(str(tmp_path),
                             profiler_options=trace_lib.profile_options())
    try:
        with jax.profiler.TraceAnnotation(trace_lib.WINDOW_SPAN):
            fn()
    finally:
        jax.profiler.stop_trace()
    rec = trace_lib.record(str(tmp_path))
    return [h[0] for h in sorted(rec["host"], key=lambda h: h[1])
            if h[0].startswith("repro.")]


def test_fit_emits_seed_solve_result_in_order(trace_lib, tmp_path):
    x = make_blobs(600, 4, 3, seed=0)
    AAKMeans(n_clusters=3, seed=1).fit(x)          # compile outside
    names = _traced(trace_lib, tmp_path,
                    lambda: AAKMeans(n_clusters=3, seed=1).fit(x))
    assert names == ["repro.fit.seed", "repro.fit.solve",
                     "repro.fit.result"]


def test_predict_emits_put_run_fetch_per_chunk(trace_lib, tmp_path):
    x = make_blobs(600, 4, 3, seed=0)
    model = AAKMeans(n_clusters=3, seed=1).fit(x)
    rows = np.asarray(x[:250])                     # 3 chunks, tail padded
    want = model.predict(rows, chunk_size=100)
    got = []
    names = _traced(trace_lib, tmp_path, lambda: got.append(
        model.predict(rows, chunk_size=100)))
    # one chunk ahead: chunk i+1 is put and dispatched before chunk i's
    # answer is fetched
    put, run, fetch = "repro.rows.put", "repro.rows.run", "repro.rows.fetch"
    assert names == [put, run, put, run, fetch, put, run, fetch, fetch]
    np.testing.assert_array_equal(got[0], want)
    assert got[0].shape == (250,)


def _hlo(fn, *args):
    return jax.jit(fn).lower(*args).compile().as_text()


def _scopes(hlo):
    """The program's scopes that reach ``op_name`` metadata in ``hlo``."""
    names = " ".join(re.findall(r'op_name="([^"]*)"', hlo))
    return {s for s in ("repro.step", "repro.aa") if s in names}


@pytest.fixture(scope="module")
def problem():
    x = jnp.asarray(make_blobs(256, 4, 3, seed=0))
    return x, x[:3], KMeansConfig(k=3, max_iter=20)


def test_batched_fit_program_carries_both_scopes(problem):
    x, c0, cfg = problem
    hlo = _hlo(lambda a, b: aa_kmeans_batched(a, b, cfg, backend="dense"),
               x, c0[None])
    assert _scopes(hlo) == {"repro.step", "repro.aa"}


def test_sequential_fit_program_carries_both_scopes(problem):
    x, c0, cfg = problem
    hlo = _hlo(lambda a, b: aa_kmeans(a, b, cfg, backend="dense"), x, c0)
    assert _scopes(hlo) == {"repro.step", "repro.aa"}


def test_minibatch_step_carries_both_scopes(problem):
    x, c0, _ = problem
    bk = get_backend("dense")
    cfg = MiniBatchConfig(k=3, chunk_size=128)
    state = minibatch_init(c0, cfg, bk)
    w = jnp.ones((128,), jnp.float32)
    hlo = _hlo(lambda xc, ww, xv, st: minibatch_iteration(
        xc, ww, xv, st, cfg, bk)[0], x[:128], w, x[128:], state)
    assert _scopes(hlo) == {"repro.step", "repro.aa"}
