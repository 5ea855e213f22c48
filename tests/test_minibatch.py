"""Streaming mini-batch engine tests (DESIGN.md §Streaming).

1. minibatch_step contract: native (dense/blocked) and fallback paths
   agree with the weighted oracle; zero-weight padding is inert (the
   per-backend sweep lives in test_conformance).
2. Driver behaviour: convergence to full-batch quality from the same
   seeds, determinism, backend-independence of the guard decisions,
   plain-Lloyd mode, epoch/chunk trace shapes.
3. Data layer: chunk_dataset masking/reshaping, split_validation,
   host_chunk_stream reshuffling.
4. Estimator: fit / partial_fit / finalize / predict / transform.
5. Streaming sweep smoke (slow): the benchmark's headline criterion.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import backends as B
from repro.core.api import MiniBatchAAKMeans
from repro.core.init_schemes import kmeanspp_init
from repro.core.kmeans import (KMeansConfig, aa_kmeans,
                               aa_kmeans_minibatch,
                               aa_kmeans_minibatch_streamed)
from repro.core.minibatch import (MiniBatchConfig, guard_pick,
                                  minibatch_init, minibatch_iteration)
from repro.data.streaming import (chunk_dataset, host_chunk_stream,
                                  split_validation, stream_chunks)
from repro.data.synthetic import make_blobs
from repro.kernels import ref

K = 8


@pytest.fixture(scope="module")
def problem():
    x = jnp.asarray(make_blobs(16000, 8, K, seed=0, spread=3.0))
    xt, xv = split_validation(x, 1024, jax.random.PRNGKey(7))
    c0 = kmeanspp_init(jax.random.PRNGKey(0), x[:4096], K)
    return x, xt, xv, c0


def _full_energy(x, c):
    res, _ = B.get_backend("dense").step(x, c, K, ())
    return float(res.energy)


# -- step contract ----------------------------------------------------------

def test_minibatch_step_native_matches_fallback_and_oracle(problem):
    x, _, _, c = (*problem[:3], problem[3])
    xc = x[:1000]
    w = jnp.concatenate([jnp.ones(800), jnp.zeros(200)])
    dense = B.get_backend("dense")
    assert dense.minibatch_step_fn is not None
    res_native, _ = dense.minibatch_step(xc, c, K, w, ())
    # strip the native slot to force the generic step_fn+reweight fallback
    import dataclasses
    fallback = dataclasses.replace(dense, minibatch_step_fn=None)
    res_fb, _ = fallback.minibatch_step(xc, c, K, w, ())
    want = ref.minibatch_ref(xc, c, w)
    for got in (res_native, res_fb):
        np.testing.assert_array_equal(got.labels, want[0])
        np.testing.assert_allclose(got.sums, want[2], rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(got.counts, want[3], rtol=0, atol=1e-6)
        np.testing.assert_allclose(float(got.energy), float(want[4]),
                                   rtol=1e-5)


def test_distributed_minibatch_step_psums_once(problem):
    """A distribute()-wrapped minibatch step on a 1-device mesh must equal
    the local step exactly (psum = identity); the multi-device version
    lives in test_distributed."""
    from jax.sharding import PartitionSpec as P
    x, _, _, c = (*problem[:3], problem[3])
    xc, w = x[:1024], jnp.ones(1024)
    dense = B.get_backend("dense")
    dist = B.distribute(dense, ("data",))
    mesh = jax.make_mesh((1,), ("data",),
                         axis_types=(jax.sharding.AxisType.Auto,))
    res = jax.shard_map(
        lambda a, b, ww: dist.minibatch_step(a, b, K, ww, ())[0],
        mesh=mesh, in_specs=(P("data"), P(), P("data")),
        out_specs=B.StepResult(labels=P("data"), min_sqdist=P("data"),
                               sums=P(), counts=P(), energy=P()))(xc, c, w)
    want, _ = dense.minibatch_step(xc, c, K, w, ())
    np.testing.assert_allclose(res.sums, want.sums, rtol=0, atol=0)
    np.testing.assert_allclose(float(res.energy), float(want.energy),
                               rtol=0)


def test_instrumented_backend_counts_chunk_passes(problem):
    x, _, xv, c0 = problem
    passes = []
    backend = B.instrument(B.get_backend("dense"), lambda: passes.append(1))
    xc, w = x[:2048], jnp.ones(2048)
    cfg = MiniBatchConfig(k=K, chunk_size=2048)
    state = minibatch_init(c0, cfg, backend)
    state, _ = minibatch_iteration(xc, w, xv, state, cfg, backend)
    jax.block_until_ready(state.c)
    jax.effects_barrier()
    # one guard pass (batched, R=2 over the val chunk) + one chunk pass
    assert len(passes) == 2, passes


# -- driver -----------------------------------------------------------------

def test_minibatch_reaches_full_batch_quality(problem):
    """From identical seed centroids, 5 mini-batch epochs must land within
    2% of the full-batch AA optimum's energy on the full dataset."""
    x, xt, xv, c0 = problem
    full = aa_kmeans(x, c0, KMeansConfig(k=K, max_iter=500))
    dc = chunk_dataset(xt, 2048)
    cfg = MiniBatchConfig(k=K, chunk_size=2048, epochs=5)
    res = jax.jit(lambda a, b, v, c: aa_kmeans_minibatch(
        a, b, v, c, cfg))(dc.chunks, dc.weights, xv, c0)
    e_mb = _full_energy(x, res.centroids)
    assert e_mb <= float(full.energy) * 1.02, (e_mb, float(full.energy))
    assert int(res.n_steps) == 5 * dc.chunks.shape[0]
    assert 0 < int(res.n_accepted) <= int(res.n_steps)


def test_minibatch_is_deterministic_and_backend_invariant(problem):
    """Same key -> identical result; the guard decisions (accept counts)
    must not depend on which backend computed the identical math."""
    _, xt, xv, c0 = problem
    dc = chunk_dataset(xt, 2048)
    cfg = MiniBatchConfig(k=K, chunk_size=2048, epochs=2)
    key = jax.random.PRNGKey(3)
    runs = {}
    for name in ("dense", "hamerly"):
        r1 = aa_kmeans_minibatch(dc.chunks, dc.weights, xv, c0, cfg,
                                 backend=name, key=key)
        r2 = aa_kmeans_minibatch(dc.chunks, dc.weights, xv, c0, cfg,
                                 backend=name, key=key)
        assert float(r1.energy) == float(r2.energy), name
        np.testing.assert_array_equal(np.asarray(r1.centroids),
                                      np.asarray(r2.centroids))
        runs[name] = r1
    assert int(runs["dense"].n_accepted) == int(runs["hamerly"].n_accepted)
    np.testing.assert_allclose(float(runs["dense"].energy),
                               float(runs["hamerly"].energy), rtol=1e-5)


def test_minibatch_plain_lloyd_mode(problem):
    """accelerated=False is plain mini-batch Lloyd: no candidate is ever
    accepted (c == c_au throughout) and quality is still sane."""
    x, xt, xv, c0 = problem
    dc = chunk_dataset(xt, 2048)
    cfg = MiniBatchConfig(k=K, chunk_size=2048, epochs=5,
                          accelerated=False)
    res = aa_kmeans_minibatch(dc.chunks, dc.weights, xv, c0, cfg)
    assert int(res.n_accepted) == 0
    full = aa_kmeans(x, c0, KMeansConfig(k=K, max_iter=500))
    assert _full_energy(x, res.centroids) <= float(full.energy) * 1.10


def test_minibatch_trace_shapes_and_validation(problem):
    _, xt, xv, c0 = problem
    dc = chunk_dataset(xt, 4096)
    cfg = MiniBatchConfig(k=K, chunk_size=4096, epochs=3)
    res, trace = aa_kmeans_minibatch(dc.chunks, dc.weights, xv, c0, cfg,
                                     return_trace=True)
    assert trace.e_val.shape == (3, dc.chunks.shape[0])
    assert trace.accepted.dtype == jnp.bool_
    assert float(res.energy) > 0
    with pytest.raises(ValueError, match="n_chunks"):
        aa_kmeans_minibatch(xt, dc.weights, xv, c0, cfg)
    with pytest.raises(ValueError, match="weights"):
        aa_kmeans_minibatch(dc.chunks, dc.weights[:, :-1], xv, c0, cfg)


def test_decayed_stats_keep_unseen_clusters_fixed():
    """S/W is invariant under pure decay: a cluster that no chunk touches
    must hold its centroid exactly, not shrink toward the origin (the
    update_from_sums max(counts,1) safe-divide would corrupt decayed
    weights < 1 — regression for _centroids_from_running)."""
    k, d = 4, 3
    bk = B.get_backend("dense")
    cfg = MiniBatchConfig(k=k, chunk_size=32, decay=0.5)
    c0 = jnp.asarray(np.float32([[0, 0, 0], [10, 0, 0], [0, 10, 0],
                                 [50, 50, 50]]))   # cluster 3: never seen
    rng = np.random.default_rng(0)
    xv = jnp.asarray(rng.normal(0, 0.1, (16, d)).astype(np.float32))
    state = minibatch_init(c0, cfg, bk)
    for step in range(8):
        xc = jnp.asarray(
            np.concatenate([rng.normal(0, .1, (10, d)),
                            rng.normal([10, 0, 0], .1, (11, d)),
                            rng.normal([0, 10, 0], .1, (11, d))])
            .astype(np.float32))
        state, _ = minibatch_iteration(xc, jnp.ones(32), xv, state, cfg, bk)
        # after 8 steps of decay 0.5, cluster-3 weight would be 0.5^8 if it
        # had ever been counted; it must still sit exactly at its seed
        np.testing.assert_array_equal(np.asarray(state.c_au[3]),
                                      np.float32([50, 50, 50]))


# -- data layer -------------------------------------------------------------

def test_chunk_dataset_masks_remainder():
    x = jnp.arange(10 * 3, dtype=jnp.float32).reshape(10, 3)
    dc = chunk_dataset(x, 4)
    assert dc.chunks.shape == (3, 4, 3) and dc.n == 10
    np.testing.assert_array_equal(
        np.asarray(dc.weights),
        [[1, 1, 1, 1], [1, 1, 1, 1], [1, 1, 0, 0]])
    # padding rows replicate the last sample
    np.testing.assert_array_equal(np.asarray(dc.chunks[2, 2]),
                                  np.asarray(x[-1]))
    with pytest.raises(ValueError, match="chunk_size"):
        chunk_dataset(x, 0)


def test_split_validation_partitions():
    x = jnp.arange(100 * 2, dtype=jnp.float32).reshape(100, 2)
    xt, xv = split_validation(x, 25, jax.random.PRNGKey(0))
    assert xt.shape == (75, 2) and xv.shape == (25, 2)
    merged = np.sort(np.concatenate([np.asarray(xt), np.asarray(xv)]),
                     axis=0)
    np.testing.assert_array_equal(merged, np.asarray(x))
    with pytest.raises(ValueError, match="val_size"):
        split_validation(x, 100, jax.random.PRNGKey(0))


def test_host_chunk_stream_reshuffles_per_epoch():
    x = np.arange(100, dtype=np.float32).reshape(100, 1)
    chunks = list(host_chunk_stream(x, 32, epochs=2, seed=0))
    assert len(chunks) == 8                      # 4 per epoch (tail = 4)
    assert [c.shape[0] for c in chunks[:4]] == [32, 32, 32, 4]
    e1 = np.concatenate([c.ravel() for c in chunks[:4]])
    e2 = np.concatenate([c.ravel() for c in chunks[4:]])
    np.testing.assert_array_equal(np.sort(e1), x.ravel())  # full coverage
    np.testing.assert_array_equal(np.sort(e2), x.ravel())
    assert not (e1 == e2).all()                  # reshuffled
    short = list(host_chunk_stream(x, 32, epochs=1, drop_remainder=True))
    assert [c.shape[0] for c in short] == [32, 32, 32]


# -- estimator --------------------------------------------------------------

def test_estimator_fit(problem):
    x = problem[0]
    m = MiniBatchAAKMeans(n_clusters=K, chunk_size=2048, epochs=4,
                          seed=0).fit(x)
    assert m.centroids_.shape == (K, 8)
    assert m.labels_.shape == (x.shape[0],)
    assert m.energy_ == m.inertia_ and m.energy_ > 0
    assert m.n_steps_ > 0
    # labels_ match a fresh predict, chunked at a different size
    np.testing.assert_array_equal(np.asarray(m.labels_),
                                  np.asarray(m.predict(x, chunk_size=1111)))
    assert m.transform(x[:100]).shape == (100, K)


def test_estimator_partial_fit_streams_host_chunks(problem):
    x = np.asarray(problem[0])
    m = MiniBatchAAKMeans(n_clusters=K, chunk_size=2048, seed=0)
    with pytest.raises(ValueError, match="partial_fit chunk"):
        m.partial_fit(x[:4])
    # documented held-out pattern: feed the first chunk once (it carves
    # the val rows), epoch only over the remainder
    m.partial_fit(x[:2048])
    for chunk in host_chunk_stream(x[2048:], 2048, epochs=3, seed=1,
                                   drop_remainder=True):
        m.partial_fit(chunk)
    assert m.n_steps_ == 1 + 3 * ((x.shape[0] - 2048) // 2048)
    e_fallback = m.energy_
    m.finalize()
    assert m.energy_ <= e_fallback * 1.001   # guard pick can only help
    # quality vs full-batch FROM THE SAME SEED CENTROIDS (reconstructed
    # the way partial_fit derives them) — single-restart k-means quality
    # under independent inits is luck, not a solver property
    from repro.data.streaming import split_validation
    k_val, k_init = jax.random.split(jax.random.PRNGKey(0))
    x0, _ = split_validation(jnp.asarray(x[:2048]), m._val_rows(2048),
                             k_val)
    c0 = kmeanspp_init(k_init, x0, K)
    full = aa_kmeans(jnp.asarray(x), c0, KMeansConfig(k=K, max_iter=500))
    e_stream = _full_energy(jnp.asarray(x), jnp.asarray(m.centroids_))
    assert e_stream <= float(full.energy) * 1.10
    assert m.predict(x[:100]).shape == (100,)


def test_estimator_fit_deterministic(problem):
    x = problem[0]
    a = MiniBatchAAKMeans(n_clusters=K, chunk_size=4096, epochs=2,
                          seed=5, compute_labels=False).fit(x)
    b = MiniBatchAAKMeans(n_clusters=K, chunk_size=4096, epochs=2,
                          seed=5, compute_labels=False).fit(x)
    assert a.energy_ == b.energy_
    np.testing.assert_array_equal(np.asarray(a.centroids_),
                                  np.asarray(b.centroids_))


def test_estimator_fit_supersedes_partial_fit_stream(problem):
    """fit() after partial_fit discards the stream: a later partial_fit
    starts fresh instead of advancing the abandoned stream over the
    fitted results, and finalize() refuses until a new stream exists."""
    x = np.asarray(problem[0])
    m = MiniBatchAAKMeans(n_clusters=K, chunk_size=2048, epochs=2, seed=0,
                          compute_labels=False)
    m.partial_fit(x[:2048])
    m.fit(x)
    with pytest.raises(ValueError, match="streaming state"):
        m.finalize()
    m.partial_fit(x[:2048])          # fresh stream, step count restarts
    assert int(m.n_steps_) == 1


def test_estimator_input_validation():
    with pytest.raises(ValueError, match="rows"):
        MiniBatchAAKMeans(n_clusters=8).fit(np.zeros((4, 2), np.float32))
    m = MiniBatchAAKMeans(n_clusters=2)
    # a REAL exception, not a bare assert: survives `python -O` (ISSUE 8)
    from repro.core.api import NotFittedError
    with pytest.raises(NotFittedError, match="fit"):
        m.predict(np.zeros((4, 2), np.float32))
    with pytest.raises(ValueError, match="streaming state"):
        m.finalize()


# -- streamed epoch driver + chunk locality ---------------------------------

def test_stream_chunks_sort_by_orders_rows(problem):
    """``sort_by`` re-orders each chunk's rows by nearest centroid without
    changing WHICH rows a chunk holds (the locality engine's streaming
    analogue: ordering shapes tile-skipping, never the numbers)."""
    x, xt, xv, c0 = problem
    xt_np = np.asarray(xt)[:4096]
    c_np = np.asarray(c0)
    plain = list(stream_chunks(xt_np, 1024, epochs=1, seed=5))
    srt = list(stream_chunks(xt_np, 1024, epochs=1, seed=5, sort_by=c_np))
    assert len(plain) == len(srt) == 4
    for p, s in zip(plain, srt):
        p, s = np.asarray(p), np.asarray(s)
        # same rows, re-ordered
        assert np.array_equal(np.sort(p, axis=0), np.sort(s, axis=0))
        d2 = (np.square(s).sum(-1)[:, None] - 2.0 * s @ c_np.T
              + np.square(c_np).sum(-1)[None, :])
        labels = np.argmin(d2, axis=1)
        assert np.all(np.diff(labels) >= 0)     # cluster-sorted
    # a callable provider is re-read per chunk (the streamed driver
    # passes its live centroids)
    reads = []

    def provider():
        reads.append(1)
        return c_np
    list(stream_chunks(xt_np, 1024, epochs=1, sort_by=provider))
    assert len(reads) == 4


def test_stream_chunks_device_source_rejects_sort_by(problem):
    x, xt, xv, c0 = problem
    dc = chunk_dataset(xt, 2048)
    with pytest.raises(ValueError, match="sort_by"):
        stream_chunks(dc, sort_by=np.asarray(c0))


def test_streamed_driver_matches_quality_and_counts(problem):
    """`aa_kmeans_minibatch_streamed` runs the same per-chunk state
    machine as the device-resident driver over a prefetched host stream;
    with ``sort_chunks`` it must still land within the quality bar, and
    the trace must cover every chunk of every epoch."""
    x, xt, xv, c0 = problem
    full = aa_kmeans(x, c0, KMeansConfig(k=K, max_iter=500))
    xt_np = np.asarray(xt)
    cfg = MiniBatchConfig(k=K, chunk_size=2048, epochs=3)
    n_chunks = -(-xt_np.shape[0] // 2048)
    for sort_chunks in (False, True):
        res, tr = aa_kmeans_minibatch_streamed(
            xt_np, xv, c0, cfg, sort_chunks=sort_chunks,
            return_trace=True)
        assert int(res.n_steps) == 3 * n_chunks
        assert tr.e_val.shape == (3 * n_chunks,)
        e = _full_energy(x, res.centroids)
        assert e <= float(full.energy) * 1.02, (sort_chunks, e)


def test_streamed_driver_iterator_source_and_meter(problem):
    """An explicit chunk-iterator source streams as-is, and the ingest
    meter observes the host→device transfers."""
    from repro.runtime.prefetch import IngestMeter
    x, xt, xv, c0 = problem
    xt_np = np.asarray(xt)[:6144]
    cfg = MiniBatchConfig(k=K, chunk_size=2048, epochs=1)
    meter = IngestMeter()
    it = host_chunk_stream(xt_np, 2048, epochs=1, seed=3)
    res = aa_kmeans_minibatch_streamed(it, xv, c0, cfg, meter=meter)
    assert int(res.n_steps) == 3
    assert meter.chunks == 3 and meter.bytes > 0


# -- benchmark smoke --------------------------------------------------------

@pytest.mark.slow
def test_streaming_sweep_smoke():
    """The benchmark's headline criterion at smoke scale: mini-batch AA
    reaches within 2% of the full-batch final energy reading <= 50% of
    the samples full-batch AA reads."""
    from benchmarks import streaming_sweep
    out = streaming_sweep.main(smoke=True, verbose=False)
    aa = out["quality"]["minibatch-aa"]
    assert aa["reached"], aa
    assert aa["ratio"] <= 0.5, aa
