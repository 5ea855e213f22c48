"""Backend-engine tests (DESIGN.md §Backends).

1. step() parity: every registered backend agrees with the dense oracle on
   labels, min-dist, cluster stats, energy and the resulting G(C).
2. Solver parity: aa_kmeans driven by each backend reaches the dense
   solver's trajectory (same iterations, energy to tolerance).
3. Pass-count regression: the driver performs exactly ONE
   assignment-equivalent pass over X per accepted iteration (counted on an
   instrumented backend through jit/while_loop/cond), two per revert.
4. distribute() combinator: the psum wrapping is semantics-preserving for
   any local backend (single-device shard_map check; the multi-device
   version lives in test_distributed).
5. Backend selection: a registry name, a Backend or None; anything else
   is refused.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.core import backends as B
from repro.core.distributed import shard_map
from repro.core.init_schemes import kmeanspp_init
from repro.core.kmeans import KMeansConfig, aa_kmeans, aa_kmeans_traced
from repro.data.synthetic import make_blobs

K = 7
# options that force the interesting code path at this fixture size
BACKEND_OPTS = {"blocked": dict(block_n=300)}


def _make(name):
    return B.get_backend(name, **BACKEND_OPTS.get(name, {}))


@pytest.fixture(scope="module")
def fixture():
    x = jnp.asarray(make_blobs(1200, 8, K, seed=0, spread=1.5))
    c0 = kmeanspp_init(jax.random.PRNGKey(0), x, K)
    return x, c0


def _step(backend, x, c):
    res, _ = backend.step(x, c, K, backend.init_carry(x, c, K))
    return res


@pytest.mark.parametrize("name", B.backend_names())
def test_step_parity_with_dense(name, fixture):
    x, c = fixture
    ref = _step(_make("dense"), x, c)
    res = _step(_make(name), x, c)
    assert (np.asarray(res.labels) == np.asarray(ref.labels)).all()
    np.testing.assert_allclose(res.min_sqdist, ref.min_sqdist,
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(res.sums, ref.sums, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(res.counts, ref.counts, rtol=0, atol=1e-6)
    np.testing.assert_allclose(float(res.energy), float(ref.energy),
                               rtol=1e-4)
    # the derived fixed-point image G(c) agrees too
    g_ref = _make("dense").centroids_from_step(x, ref, K, c)
    g_res = _make(name).centroids_from_step(x, res, K, c)
    np.testing.assert_allclose(g_res, g_ref, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("name", B.backend_names())
def test_solver_parity_with_dense(name, fixture):
    x, c0 = fixture
    cfg = KMeansConfig(k=K, max_iter=300)
    ref = aa_kmeans(x, c0, cfg)
    res = aa_kmeans(x, c0, cfg, backend=_make(name))
    assert bool(res.converged)
    assert int(res.n_iter) == int(ref.n_iter)
    np.testing.assert_allclose(float(res.energy), float(ref.energy),
                               rtol=1e-5)


@pytest.mark.parametrize("name", ["fused", "dense"])
def test_one_pass_per_accepted_iteration(name, fixture):
    """Regression for the Sec-2.1 cost model: counting *executed* steps
    (passes over X) through jit + lax.while_loop + lax.cond, the solver
    spends 1 pass on the init G(C^0), 1 per loop body, and 1 extra only
    when a body reverts — i.e. exactly one pass per accepted iteration."""
    x, c0 = fixture
    passes = []
    backend = B.instrument(_make(name), lambda: passes.append(1))
    cfg = KMeansConfig(k=K, max_iter=300)
    res = jax.jit(
        lambda a, b: aa_kmeans(a, b, cfg, backend=backend))(x, c0)
    jax.block_until_ready(res.centroids)
    jax.effects_barrier()
    assert bool(res.converged)
    t, n_acc = int(res.n_iter), int(res.n_accepted)
    # init (1) + full bodies (t-1) + one extra per reject (t-1-n_acc)
    # + the convergence-detect body (1)  ==  2t - n_acc
    assert len(passes) == 2 * t - n_acc, (len(passes), t, n_acc)


def test_pass_count_matches_acceptance_trace(fixture):
    """Cross-check against the instrumented python-loop driver: each
    recorded iteration costs 1 pass when accepted, 2 when reverted."""
    x, c0 = fixture
    passes = []
    backend = B.instrument(_make("dense"), lambda: passes.append(1))
    cfg = KMeansConfig(k=K, max_iter=300)
    tr = aa_kmeans_traced(x, c0, cfg, backend=backend)
    jax.effects_barrier()
    assert bool(tr.result.converged)
    expected = 1 + sum(1 if a else 2 for a in tr.accepted) + 1
    assert len(passes) == expected, (len(passes), tr.accepted)


@pytest.mark.parametrize("name", B.backend_names())
def test_distribute_combinator_single_device(name, fixture):
    """distribute(backend, axes) is semantics-preserving: under a 1-device
    shard_map the psum-wrapped step must equal the local step exactly."""
    x, c = fixture
    backend = _make(name)
    dist = B.distribute(backend, ("data",))
    mesh = jax.make_mesh((1,), ("data",),
                         axis_types=(jax.sharding.AxisType.Auto,))

    def run(xx, cc):
        res, _ = dist.step(xx, cc, K, dist.init_carry(xx, cc, K))
        return res

    res = shard_map(run, mesh=mesh, in_specs=(P("data"), P()),
                    out_specs=B.StepResult(
                        labels=P("data"), min_sqdist=P("data"),
                        sums=P(), counts=P(), energy=P()))(x, c)
    ref = _step(backend, x, c)
    assert (np.asarray(res.labels) == np.asarray(ref.labels)).all()
    np.testing.assert_allclose(res.sums, ref.sums, rtol=0, atol=0)
    np.testing.assert_allclose(float(res.energy), float(ref.energy), rtol=0)


def test_distributed_energy_op_reduces_once():
    """Regression: the derived energy() op of a distribute()-wrapped
    backend must psum exactly once — it previously composed a psum'd
    energy_fn with a psum reduce_scalar, inflating by the device count.
    A 1-device mesh cannot observe the inflation (psum is identity), so
    this only bites under test.sh's 8 virtual devices."""
    n_dev = len(jax.devices())
    if n_dev < 2:
        pytest.skip("needs >= 2 devices to observe a double reduction")
    x = jnp.asarray(make_blobs(400, 4, K, seed=1, spread=3.0))
    c = kmeanspp_init(jax.random.PRNGKey(0), x, K)
    dense = _make("dense")
    labels = dense.assign(x, c).labels
    e_ref = float(dense.energy(x, c, labels))
    dist = B.distribute(dense, ("data",))
    mesh = jax.make_mesh((n_dev,), ("data",),
                         axis_types=(jax.sharding.AxisType.Auto,))
    e = jax.shard_map(lambda xx, cc, ll: dist.energy(xx, cc, ll),
                      mesh=mesh, in_specs=(P("data"), P(), P("data")),
                      out_specs=P())(x, c, labels)
    np.testing.assert_allclose(float(e), e_ref, rtol=1e-5)


def test_resolve_backend_rejects_junk():
    """Only a registry name, a Backend or None names an engine: an object
    shaped like the old assign/update container is junk too."""
    from types import SimpleNamespace

    from repro.core.kmeans import resolve_backend
    ops_shaped = SimpleNamespace(
        assign_fn=lambda x, c: None, update_fn=lambda x, p, k, c: c,
        energy_fn=lambda x, c, p: 0.0, all_equal_fn=lambda a, b: True,
        reduce_scalar=lambda s: s)
    for junk in (object(), ops_shaped, 3):
        with pytest.raises(TypeError):
            resolve_backend(junk)


def test_reregistering_backend_invalidates_cache():
    marker = _make("dense")
    B.register_backend("tmp-test-backend", lambda: marker)
    assert B.get_backend("tmp-test-backend") is marker
    other = _make("hamerly")
    B.register_backend("tmp-test-backend", lambda: other)
    try:
        assert B.get_backend("tmp-test-backend") is other
    finally:
        from repro.core.backends import base as _base
        _base._REGISTRY.pop("tmp-test-backend", None)
        _base._INSTANCES.pop(("tmp-test-backend", ()), None)


def test_precision_policy(fixture):
    """bf16 compute / f32 accumulate: runs end-to-end and lands on the
    same clustering quality (exactness is not expected at bf16)."""
    x, c0 = fixture
    prec = B.Precision(compute=jnp.bfloat16)
    cfg = KMeansConfig(k=K, max_iter=300)
    ref = aa_kmeans(x, c0, cfg)
    res = aa_kmeans(x, c0, cfg,
                    backend=B.get_backend("dense", precision=prec))
    assert bool(jnp.isfinite(res.energy))
    assert abs(float(res.energy) - float(ref.energy)) / float(ref.energy) \
        < 0.02


def test_get_backend_registry():
    assert set(B.backend_names()) >= {"dense", "blocked", "pallas", "fused",
                                      "hamerly"}
    assert B.get_backend("dense") is B.get_backend("dense")  # cached
    with pytest.raises(KeyError):
        B.get_backend("no-such-backend")


def test_blocked_backend_handles_non_divisible_n(fixture):
    """Regression: block_n not dividing N must still take the row-blocked
    path (padded), not silently materialise the full (N, K) matrix — and
    the padded rows must not perturb the results."""
    x, c = fixture                    # N = 1200, not divisible by 500
    ref = _step(_make("dense"), x, c)
    res = _step(B.get_backend("blocked", block_n=500), x, c)
    assert (np.asarray(res.labels) == np.asarray(ref.labels)).all()
    np.testing.assert_allclose(res.sums, ref.sums, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(float(res.energy), float(ref.energy),
                               rtol=1e-5)


def test_resolve_backend_honours_block_n():
    from repro.core.kmeans import resolve_backend
    cfg = KMeansConfig(k=K, block_n=300)
    assert resolve_backend("blocked", cfg=cfg).name == "blocked300"
    assert resolve_backend("dense", cfg=cfg).name == "blocked300"
    assert resolve_backend(None, cfg=cfg).name == "blocked300"
    assert resolve_backend(None, block_n=600).name == "blocked600"
    assert resolve_backend("fused", cfg=cfg).name == "fused"  # not promoted


def test_distribute_rejects_double_wrapping():
    dist = B.distribute(_make("dense"), ("data",))
    assert dist.axes == ("data",)
    with pytest.raises(ValueError):
        B.distribute(dist, ("data",))


def test_make_distributed_accepts_prewrapped_backend():
    """An already distribute()-wrapped backend is used as-is (no double
    psum); mismatched axes are rejected."""
    from repro.core.distributed import make_distributed_kmeans
    mesh = jax.make_mesh((1,), ("data",),
                         axis_types=(jax.sharding.AxisType.Auto,))
    cfg = KMeansConfig(k=K, max_iter=50)
    wrapped = B.distribute(_make("dense"), ("data",))
    x = jnp.asarray(make_blobs(400, 4, K, seed=1, spread=3.0))
    c0 = kmeanspp_init(jax.random.PRNGKey(0), x, K)
    res = make_distributed_kmeans(mesh, cfg, ("data",), backend=wrapped)(x, c0)
    ref = aa_kmeans(x, c0, cfg)
    np.testing.assert_allclose(float(res.energy), float(ref.energy), rtol=0)
    with pytest.raises(ValueError):
        make_distributed_kmeans(mesh, cfg, ("pod", "data"), backend=wrapped)
