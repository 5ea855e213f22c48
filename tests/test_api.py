"""Estimator-API tests + emergency-checkpoint behaviour."""

from pathlib import Path

import numpy as np
import pytest

from repro.core.api import AAKMeans
from repro.data.synthetic import make_blobs


def test_estimator_fit_predict():
    x = make_blobs(2000, 6, 5, seed=0, spread=4.0)
    m = AAKMeans(n_clusters=5, n_init=2, seed=1).fit(x)
    assert m.centroids_.shape == (5, 6)
    assert m.labels_.shape == (2000,)
    assert m.energy_ > 0 and m.n_iter_ >= 1
    labs = np.asarray(m.predict(x[:100]))
    assert labs.min() >= 0 and labs.max() < 5
    assert m.transform(x[:10]).shape == (10, 5)


def test_estimator_restarts_pick_best():
    x = make_blobs(1500, 4, 6, seed=2, spread=1.2)
    e1 = AAKMeans(n_clusters=6, n_init=1, init="random", seed=0).fit(x).energy_
    e5 = AAKMeans(n_clusters=6, n_init=5, init="random", seed=0).fit(x).energy_
    assert e5 <= e1 + 1e-3


def test_estimator_plain_lloyd_mode():
    x = make_blobs(800, 4, 4, seed=3, spread=4.0)
    maa = AAKMeans(n_clusters=4, accelerated=True, seed=4).fit(x)
    mll = AAKMeans(n_clusters=4, accelerated=False, seed=4).fit(x)
    assert abs(maa.energy_ - mll.energy_) / mll.energy_ < 0.02


def test_estimator_threshold_params_reach_aa_config():
    """eps1/eps2/ridge must thread through to AAConfig — they were
    silently dropped, making Table-2-style threshold sweeps through the
    public API no-ops."""
    m = AAKMeans(n_clusters=3, eps1=0.07, eps2=0.9, ridge=1e-8,
                 m0=4, mbar=12, dynamic_m=False)
    aa = m._config().aa
    assert aa.eps1 == 0.07 and aa.eps2 == 0.9 and aa.ridge == 1e-8
    assert aa.m0 == 4 and aa.mbar == 12 and aa.dynamic_m is False
    # and they must change solver behaviour end-to-end: an eps2 of -inf
    # grows m on every defined ratio, an eps1 above any ratio shrinks it;
    # both must still converge to the same quality
    x = make_blobs(600, 4, 4, seed=1, spread=4.0)
    e_grow = AAKMeans(n_clusters=4, eps2=-1e9, seed=0).fit(x).energy_
    e_shrink = AAKMeans(n_clusters=4, eps1=1e9, seed=0).fit(x).energy_
    assert abs(e_grow - e_shrink) / e_shrink < 0.02


def test_estimator_predict_uses_fitted_mesh():
    """Regression: predict/transform on a mesh-fitted model must route
    through the mesh (sharded rows, replicated centroids), not silently
    run a bare single-device assign — and must agree with the local
    result.  A 1-device mesh exercises the exact code path in-process;
    the multi-device behaviour rides the same shard_map contract as
    fit (tests/test_distributed.py)."""
    import jax

    mesh = jax.make_mesh((1,), ("data",),
                         axis_types=(jax.sharding.AxisType.Auto,))
    x = make_blobs(1000, 5, 4, seed=6, spread=3.0)
    mm = AAKMeans(n_clusters=4, n_init=2, seed=1, mesh=mesh).fit(x)
    ml = AAKMeans(n_clusters=4, n_init=2, seed=1).fit(x)
    np.testing.assert_allclose(float(mm.energy_), float(ml.energy_),
                               rtol=1e-5)
    # odd-length query exercises the padding-strip path too
    q = x[:333]
    np.testing.assert_array_equal(np.asarray(mm.predict(q)),
                                  np.asarray(ml.predict(q)))
    np.testing.assert_allclose(np.asarray(mm.transform(q)),
                               np.asarray(ml.transform(q)), rtol=1e-5)
    assert mm.predict(q).shape == (333,)
    assert mm.transform(q).shape == (333, 4)


def test_chunked_runner_traces_once_across_remainders():
    """Regression (ISSUE 8): `_chunked_rows_apply` used to retrace the
    jitted runner for every distinct final-chunk remainder shape — the
    exact varying-batch-size pattern a request queue produces.  The tail
    chunk is now padded to the fixed chunk size, so a serving loop over
    varying N compiles exactly once."""
    import jax.numpy as jnp
    from repro.core.api import _chunked_rows_apply
    from repro.core.lloyd import pairwise_sqdist

    x = make_blobs(700, 5, 4, seed=8, spread=4.0)
    m = AAKMeans(n_clusters=4, seed=0).fit(x)
    traced_shapes = []

    def spy(xl, c):
        traced_shapes.append(tuple(xl.shape))   # runs at TRACE time only
        return jnp.argmin(pairwise_sqdist(xl, c), axis=1).astype(jnp.int32)

    xh = np.asarray(x)
    for n in (257, 128, 300, 123, 512, 1):      # six distinct remainders
        out = _chunked_rows_apply(m, xh[:n], "spy", spy, np.int32,
                                  chunk_size=128)
        assert out.shape == (n,)
        # padding must not perturb the real rows' results
        np.testing.assert_array_equal(out, np.asarray(m.predict(xh[:n])))
    assert traced_shapes == [(128, 5)], \
        f"expected ONE trace at the padded chunk shape; got {traced_shapes}"


def _assert_matches_per_call_jit(model, x):
    """The fitted model equals, bit for bit, a fresh per-call ``jax.jit``
    of the same solve from the same seeds."""
    import jax
    import jax.numpy as jnp
    from repro.core.init_schemes import batched_init
    from repro.core.kmeans import aa_kmeans_batched, select_best

    x = jnp.asarray(x)
    cfg = model._config()
    keys = jax.random.split(jax.random.PRNGKey(model.seed),
                            max(model.n_init, 1))
    c0s = jnp.asarray(batched_init(model.init, keys, x, model.n_clusters))
    best = jax.jit(lambda a, b: select_best(aa_kmeans_batched(
        a, b, cfg, backend=model.backend)))(x, c0s)
    np.testing.assert_array_equal(np.asarray(model.centroids_),
                                  np.asarray(best.centroids))
    np.testing.assert_array_equal(np.asarray(model.labels_),
                                  np.asarray(best.labels[:x.shape[0]]))
    assert model.n_iter_ == int(best.n_iter)
    assert model.n_accepted_ == int(best.n_accepted)


def _traces_of_fit(model, x):
    from repro.core.api import fit_program_traces
    before = fit_program_traces()
    model.fit(x)
    return fit_program_traces() - before


def test_fit_program_traces_once_across_refits():
    """Fresh estimators with the same parameters on data of one shape
    share one traced fit program (the per-fit ``jax.jit`` rebuilt it on
    every fit), and each answer is the per-call jit's, bit for bit."""
    x = make_blobs(613, 5, 4, seed=11, spread=3.0)
    traces = []
    for s in (0, 1, 2):
        m = AAKMeans(n_clusters=4, seed=s)
        traces.append(_traces_of_fit(m, x))
        _assert_matches_per_call_jit(m, x)
    assert sum(traces) == 1 and traces[1:] == [0, 0], traces


@pytest.mark.parametrize("change", [{"max_iter": 37}, {"n_clusters": 3},
                                    {"n_init": 2}])
def test_fit_program_retraces_per_configuration(change):
    """A parameter that shapes the program (a static config field, K, or
    the number of restarts) gets a program of its own, traced once."""
    x = make_blobs(617, 5, 4, seed=12, spread=3.0)
    AAKMeans(n_clusters=4, seed=0).fit(x)
    params = {"n_clusters": 4, "seed": 0, **change}
    m = AAKMeans(**params)
    assert _traces_of_fit(m, x) == 1
    _assert_matches_per_call_jit(m, x)
    assert _traces_of_fit(AAKMeans(**{**params, "seed": 1}), x) == 0


def test_fit_program_backend_instance_traces_once():
    """A Backend instance built once and handed to two estimators keys one
    program; a different backend on the same data never shares it."""
    from repro.core.backends import get_backend

    x = make_blobs(619, 5, 4, seed=13, spread=3.0)
    blocked = get_backend("blocked", block_n=128)
    m0 = AAKMeans(n_clusters=4, seed=0, backend=blocked)
    m1 = AAKMeans(n_clusters=4, seed=1, backend=blocked)
    assert _traces_of_fit(m0, x) == 1
    assert _traces_of_fit(m1, x) == 0
    for m in (m0, m1):
        _assert_matches_per_call_jit(m, x)
    for other in ("dense", "hamerly"):
        m = AAKMeans(n_clusters=4, seed=0, backend=other)
        assert _traces_of_fit(m, x) == 1, other
        _assert_matches_per_call_jit(m, x)


def test_unfitted_inference_raises_not_fitted_error():
    from repro.core.api import MiniBatchAAKMeans, NotFittedError
    q = np.zeros((4, 3), np.float32)
    for m in (AAKMeans(n_clusters=3), MiniBatchAAKMeans(n_clusters=3)):
        for call in (m.predict, m.transform):
            with pytest.raises(NotFittedError):
                call(q)
        with pytest.raises(NotFittedError):
            m.save("unfitted.npz")      # checked before any file I/O
        with pytest.raises(NotFittedError):
            m.build_serving_index()


def test_assert_fitted_survives_python_O(tmp_path):
    """Regression (ISSUE 8): the fitted check was a bare ``assert``,
    which `python -O` strips — turning "call fit() first" into an opaque
    None-attribute crash inside the first jitted call.  Run the check in
    an optimized subprocess and require the REAL exception."""
    import os
    import subprocess
    import sys

    code = (
        "import numpy as np\n"
        "from repro.core.api import AAKMeans, NotFittedError\n"
        "try:\n"
        "    AAKMeans(n_clusters=3).predict(np.zeros((4, 2), np.float32))\n"
        "except NotFittedError:\n"
        "    print('NOT_FITTED_RAISED')\n"
    )
    env = dict(os.environ)
    src = str(Path(__file__).resolve().parents[1] / "src")
    env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
    env.setdefault("JAX_PLATFORMS", "cpu")
    out = subprocess.run([sys.executable, "-O", "-c", code], env=env,
                         capture_output=True, text=True, timeout=300)
    assert "NOT_FITTED_RAISED" in out.stdout, \
        f"stdout={out.stdout!r} stderr={out.stderr[-500:]!r}"
