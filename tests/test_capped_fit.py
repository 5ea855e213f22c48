"""A solve stopped at ``max_iter`` before converging returns one
consistent answer: centroids, the nearest-centroid labels of exactly
those centroids, and their energy (`kmeans._close`).  The loop exits
holding the next, never-evaluated iterate; the closing evaluation
assigns it, falls back to the Lloyd iterate where the energy guard
would reject it, and so never returns more energy than the loop's last
evaluated point.  Converged solves skip it and come out bit for bit as
the loop left them."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.api import AAKMeans
from repro.core.init_schemes import batched_init
from repro.core.kmeans import (KMeansConfig, _init_state, _run_segment,
                               _result_from_state, aa_kmeans,
                               aa_kmeans_batched, aa_kmeans_batched_loop,
                               resolve_backend)
from repro.data.synthetic import make_blobs
from repro.kernels.ref import assignment_ref

BK = resolve_backend("dense")


def _overlapping(seed=0, n=3000, d=8, k=6):
    """Overlapping blobs: many iterations to converge, so small caps bite."""
    return jnp.asarray(make_blobs(n, d, k, seed=seed, spread=1.5))


def _seeds(x, k, restarts, seed=0, init="random"):
    keys = jax.random.split(jax.random.PRNGKey(seed), restarts)
    return batched_init(init, keys, x, k)


def _unclosed(x, c0, cfg):
    """The solver loop's exit state, before any closing evaluation: the
    single-device program without `_close`."""
    return jax.jit(lambda x, c0: _run_segment(
        x, _init_state(x, c0, cfg, BK), cfg.max_iter, cfg, BK))(x, c0)


def _unclosed_batched(x, c0s, cfg):
    """The batched solver's exit state without `_close_batched`."""
    return jax.jit(lambda x, c0s: aa_kmeans_batched_loop(x, c0s, cfg, BK))(
        x, c0s)


def _assert_consistent(x, centroids, labels, energy, e_loop):
    want, mind = assignment_ref(x, jnp.asarray(centroids))
    np.testing.assert_array_equal(np.asarray(labels), np.asarray(want))
    e_ref = float(np.sum(np.asarray(mind), dtype=np.float64))
    assert abs(float(energy) - e_ref) <= 1e-5 * e_ref
    assert float(energy) <= float(e_loop)


@pytest.mark.parametrize("max_iter", [1, 2, 3, 5, 8])
def test_capped_single_device_fit_is_consistent(max_iter):
    x = _overlapping()
    c0 = _seeds(x, 6, 1)[0]
    cfg = KMeansConfig(k=6, max_iter=max_iter)
    res = jax.jit(lambda a, b: aa_kmeans(a, b, cfg))(x, c0)
    loop = _unclosed(x, c0, cfg)
    assert not bool(res.converged)
    # n_iter keeps its meaning: the closing evaluation is no iteration
    assert int(res.n_iter) == max_iter + 1
    assert int(res.n_accepted) == int(loop.n_acc)
    _assert_consistent(x, res.centroids, res.labels, res.energy,
                       loop.e_last)


def test_the_loop_alone_returns_an_unevaluated_iterate():
    """What the closing evaluation repairs: at the cap, the loop's
    centroids and labels do not belong together."""
    x = _overlapping()
    c0 = _seeds(x, 6, 1)[0]
    loop = _unclosed(x, c0, KMeansConfig(k=6, max_iter=3))
    want, _ = assignment_ref(x, loop.c)
    assert not np.array_equal(np.asarray(loop.labels), np.asarray(want))


def test_converged_single_device_fit_is_bitwise_unchanged():
    x = jnp.asarray(make_blobs(2000, 6, 5, seed=2, spread=4.0))
    c0 = _seeds(x, 5, 1, init="kmeans++")[0]
    cfg = KMeansConfig(k=5, max_iter=300)
    res = jax.jit(lambda a, b: aa_kmeans(a, b, cfg))(x, c0)
    old = _result_from_state(_unclosed(x, c0, cfg))
    assert bool(res.converged)
    for got, want in zip(res, old):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_batched_mix_of_capped_and_converged_problems():
    """Restart 0 starts at a converged solution and converges; the random
    restarts hit the cap.  Each capped restart is consistent, each
    converged one is the loop's state bit for bit."""
    x = _overlapping(seed=1)
    k = 6
    done = aa_kmeans(x, _seeds(x, k, 1, seed=1)[0],
                     KMeansConfig(k=k, max_iter=500)).centroids
    c0s = jnp.concatenate([done[None], _seeds(x, k, 3, seed=5)])
    cfg = KMeansConfig(k=k, max_iter=4)
    res = jax.jit(lambda a, b: aa_kmeans_batched(a, b, cfg))(x, c0s)
    loop = _unclosed_batched(x, c0s, cfg)
    old = _result_from_state(loop)
    conv = np.asarray(res.converged)
    assert conv[0] and not conv[1:].any()
    for got, want in zip(res, old):
        np.testing.assert_array_equal(np.asarray(got)[0], np.asarray(want)[0])
    for r in range(1, 4):
        assert int(res.n_iter[r]) == cfg.max_iter + 1
        _assert_consistent(x, res.centroids[r], res.labels[r],
                           res.energy[r], loop.e_last[r])


@pytest.mark.parametrize("every", [1, 3])
def test_capped_segmented_fit_is_consistent(tmp_path, every):
    """The host-loop drivers (checkpointed, single and batched) close
    their last segment the same way, and match the whole-loop answer."""
    x = _overlapping(seed=3)
    c0s = _seeds(x, 6, 2, seed=3)
    cfg = KMeansConfig(k=6, max_iter=5)
    seg = aa_kmeans(x, c0s[0], cfg, checkpoint_every=every)
    whole = jax.jit(lambda a, b: aa_kmeans(a, b, cfg))(x, c0s[0])
    loop = _unclosed(x, c0s[0], cfg)
    assert int(seg.n_iter) == int(whole.n_iter) == cfg.max_iter + 1
    np.testing.assert_array_equal(np.asarray(seg.labels),
                                  np.asarray(whole.labels))
    _assert_consistent(x, seg.centroids, seg.labels, seg.energy,
                       loop.e_last)
    bseg = aa_kmeans_batched(x, c0s, cfg, checkpoint_every=every,
                             checkpoint_dir=tmp_path)
    bloop = _unclosed_batched(x, c0s, cfg)
    for r in range(2):
        _assert_consistent(x, bseg.centroids[r], bseg.labels[r],
                           bseg.energy[r], bloop.e_last[r])


def test_capped_estimator_answers_belong_together():
    x = _overlapping(seed=4)
    m = AAKMeans(n_clusters=6, init="random", max_iter=3, n_init=2,
                 seed=4).fit(x)
    want, mind = assignment_ref(x, jnp.asarray(m.centroids_))
    np.testing.assert_array_equal(np.asarray(m.labels_), np.asarray(want))
    e_ref = float(np.sum(np.asarray(mind), dtype=np.float64))
    assert abs(m.energy_ - e_ref) <= 1e-5 * e_ref
    assert m.n_iter_ == 4
