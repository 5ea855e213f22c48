"""Kernel-engine v2 tests (ISSUE 4): k-tiled single-pass fused kernel,
native weights, leading-R batching, VMEM-aware tile chooser.

Everything runs in interpret mode on this host; parity is against the
pure-jnp oracles in kernels/ref.py.  The pass-count tests count *kernel
executions* (a host callback stitched into the traced program fires per
run, through jit / lax.while_loop / lax.cond) — the physical-X-read
analogue of test_backends' step counting.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import backends as B
from repro.core.backends import pallas as P
from repro.core.init_schemes import kmeanspp_init
from repro.core.kmeans import KMeansConfig, aa_kmeans
from repro.data.synthetic import make_blobs
from repro.kernels import ref, tiles
from repro.kernels.assignment import assignment_pallas
from repro.kernels.fused_lloyd import fused_lloyd_pallas
from repro.kernels.update import update_pallas

# non-tile-multiple N/K/d; tiles forced small so every shape exercises a
# multi-tile (n_tiles, k_tiles) grid in interpret mode
SHAPES = [(97, 5, 33), (130, 17, 9), (64, 3, 70)]
TILES = dict(tn=16, tk=8)


def _mk(n, d, k, dtype=jnp.float32, seed=0):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.standard_normal((n, d)), dtype)
    c = jnp.asarray(rng.standard_normal((k, d)), dtype)
    return x, c


def _spy(monkeypatch, module, name):
    """Wrap module.name so executions (not traces) are counted: the
    callback is stitched into the traced program and fires per run."""
    calls = []
    real = getattr(module, name)

    def wrapper(*a, **kw):
        jax.debug.callback(lambda: calls.append(1))
        return real(*a, **kw)

    monkeypatch.setattr(module, name, wrapper)
    return calls


# -- parity ----------------------------------------------------------------

@pytest.mark.parametrize("n,d,k", SHAPES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_fused_ktiled_parity(n, d, k, dtype):
    x, c = _mk(n, d, k, dtype)
    lf, mf, sf, cf, ef = fused_lloyd_pallas(x, c, interpret=True, **TILES)
    lr, mr, sr, cr, er = ref.fused_lloyd_ref(x, c)
    assert (np.asarray(lf) == np.asarray(lr)).all()
    tol = dict(rtol=2e-5, atol=2e-5) if dtype == jnp.float32 \
        else dict(rtol=3e-2, atol=3e-2)
    np.testing.assert_allclose(mf, mr, **tol)
    # stats are exact for the assignment made, at the compute dtype
    sr2, cr2 = ref.update_ref(x, lf, k)
    np.testing.assert_allclose(sf, sr2, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(cf, cr2, rtol=0, atol=1e-6)
    np.testing.assert_allclose(float(ef), float(np.asarray(mf).sum()),
                               rtol=1e-4)


@pytest.mark.parametrize("n,d,k", SHAPES)
def test_fused_weighted_parity(n, d, k):
    x, c = _mk(n, d, k)
    rng = np.random.default_rng(7)
    w = jnp.asarray(rng.uniform(0.0, 2.0, n), jnp.float32).at[n // 2:].set(0)
    got = fused_lloyd_pallas(x, c, w, interpret=True, **TILES)
    want = ref.minibatch_ref(x, c, w)
    assert (np.asarray(got[0]) == np.asarray(want[0])).all()
    for g, wnt, tol in [(got[2], want[2], 1e-4), (got[3], want[3], 1e-5)]:
        np.testing.assert_allclose(g, wnt, rtol=tol, atol=tol)
    np.testing.assert_allclose(float(got[4]), float(want[4]), rtol=1e-4)


@pytest.mark.parametrize("x_batched", [False, True])
def test_fused_batched_parity(x_batched):
    n, d, k, r = 97, 5, 33, 3
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.standard_normal(
        (r, n, d) if x_batched else (n, d)), jnp.float32)
    cs = jnp.asarray(rng.standard_normal((r, k, d)), jnp.float32)
    lf, mf, sf, cf, ef = fused_lloyd_pallas(x, cs, interpret=True, **TILES)
    assert lf.shape == (r, n) and sf.shape == (r, k, d)
    for rr in range(r):
        xr = x[rr] if x_batched else x
        lr, mr, sr, cr, er = ref.fused_lloyd_ref(xr, cs[rr])
        assert (np.asarray(lf[rr]) == np.asarray(lr)).all(), rr
        np.testing.assert_allclose(sf[rr], sr, rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(float(ef[rr]), float(er), rtol=1e-4)


def test_assignment_batched_parity():
    n, d, k, r = 130, 17, 9, 2
    x, _ = _mk(n, d, k, seed=5)
    cs = jnp.stack([_mk(n, d, k, seed=s)[1] for s in (1, 2)])
    la, ma = assignment_pallas(x, cs, interpret=True, **TILES)
    for rr in range(r):
        lr, mr = ref.assignment_ref(x, cs[rr])
        assert (np.asarray(la[rr]) == np.asarray(lr)).all()
        np.testing.assert_allclose(ma[rr], mr, rtol=2e-5, atol=2e-5)


def test_update_weighted_and_batched_parity():
    n, d, k = 97, 5, 33
    x, _ = _mk(n, d, k)
    rng = np.random.default_rng(11)
    w = jnp.asarray(rng.uniform(0.0, 1.0, n), jnp.float32)
    labels = jnp.asarray(rng.integers(0, k, n), jnp.int32)
    sa, ca = update_pallas(x, labels, k, w=w, interpret=True, **TILES)
    sr, cr = ref.update_ref(x, labels, k, w=w)
    np.testing.assert_allclose(sa, sr, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(ca, cr, rtol=1e-5, atol=1e-5)
    lb = jnp.stack([labels, (labels + 1) % k])
    sb, cb = update_pallas(x, lb, k, interpret=True, **TILES)
    for rr in range(2):
        sr, cr = ref.update_ref(x, lb[rr], k)
        np.testing.assert_allclose(sb[rr], sr, rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(cb[rr], cr, rtol=0, atol=0)


def test_k_straddling_old_gate_stays_fused(monkeypatch):
    """A K*d block bigger than the (monkeypatched) budget k-tiles via the
    chooser and stays correct — v1 would have refused this shape.  K
    exceeds one 128-lane tile so the chooser can split it lane-legally."""
    n, d, k = 120, 6, 300
    x, c = _mk(n, d, k, seed=9)
    monkeypatch.setattr(tiles, "DEFAULT_VMEM_BUDGET", k * d * 4 - 1)
    tn, tk = tiles.choose_tiles(n, k, d, 4, kind="fused")
    assert tk < tiles.round_up(k, 8), "budget must force k-tiling"
    got = fused_lloyd_pallas(x, c, interpret=True)
    want = ref.fused_lloyd_ref(x, c)
    assert (np.asarray(got[0]) == np.asarray(want[0])).all()
    np.testing.assert_allclose(got[2], want[2], rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(float(got[4]), float(want[4]), rtol=1e-4)


# -- tile chooser ----------------------------------------------------------

def test_tile_chooser_fits_budget_and_floors():
    budget = 256 * 1024
    for kind in ("fused", "assignment", "update"):
        tn, tk = tiles.choose_tiles(100_000, 1000, 64, 4, kind=kind,
                                    vmem_bytes=budget)
        # tiles that split an axis are lane-legal multiples of 128
        assert tn % tiles.LANE == 0 and tk % tiles.LANE == 0
        kp = tiles.round_up(1000, tk)
        # tile-dependent cost fits what the (resident-capped) budget
        # leaves; the fused accumulator may irreducibly exceed its half
        charged = min(tiles._resident(kind, kp, 128), budget // 2)
        assert tiles._tile_cost(kind, tn, tk, 128, 4) + charged <= budget \
            or (tn == tiles.LANE and tk == tiles.LANE)
    # ample budget: full 512 tiles
    assert tiles.choose_tiles(100_000, 1000, 8, 4, kind="assignment",
                              vmem_bytes=64 << 20) == (512, 512)
    # tiny problems never exceed their own (padded) extent
    tn, tk = tiles.choose_tiles(3, 2, 2, 4)
    assert tn == 8 and tk == 8


def test_tile_chooser_respects_dtype():
    # bf16 halves the streamed bytes -> same budget affords wider tiles
    # (and the sublane floor doubles)
    args = dict(kind="assignment", vmem_bytes=600 * 1024)
    tn32, tk32 = tiles.choose_tiles(65_536, 4096, 256, 4, **args)
    tn16, tk16 = tiles.choose_tiles(65_536, 4096, 256, 2, **args)
    assert tn16 * tk16 >= tn32 * tk32
    assert tiles.sublane(2) == 16 and tiles.sublane(4) == 8


# -- pass counts (physical X reads) ----------------------------------------

@pytest.fixture()
def blobs():
    k = 24
    x = jnp.asarray(make_blobs(600, 6, k, seed=2, spread=3.0))
    c0 = kmeanspp_init(jax.random.PRNGKey(1), x, k)
    return x, c0, k


def test_large_k_fused_solver_is_single_pass(blobs, monkeypatch):
    """With K*d over the (monkeypatched) VMEM budget, the fused solver
    still executes exactly 2t - a fused-kernel runs — one physical X read
    per step, no two-kernel fallback (v1 split every step here: 2 reads).
    """
    x, c0, k = blobs
    monkeypatch.setattr(tiles, "DEFAULT_VMEM_BUDGET", k * x.shape[1] * 4 - 1)
    kernel_runs = _spy(monkeypatch, P, "fused_lloyd_pallas")
    split_runs = _spy(monkeypatch, P, "assignment_pallas")
    steps = []
    backend = B.instrument(B.get_backend("fused"),
                           lambda: steps.append(1))
    cfg = KMeansConfig(k=k, max_iter=300)
    res = jax.jit(lambda a, b: aa_kmeans(a, b, cfg, backend=backend))(x, c0)
    jax.block_until_ready(res.centroids)
    jax.effects_barrier()
    assert bool(res.converged)
    t, n_acc = int(res.n_iter), int(res.n_accepted)
    assert len(steps) == 2 * t - n_acc, (len(steps), t, n_acc)
    assert len(kernel_runs) == len(steps), "each step must be ONE fused run"
    assert not split_runs, "no fallback to the two-kernel path"


def test_native_minibatch_drops_segment_sum_pass(monkeypatch):
    """pallas/fused minibatch steps are native: the generic fallback's
    extra weighted segment-sum pass over the chunk must not run, and the
    fused chunk step must be ONE kernel execution."""
    from repro.core import lloyd as L
    x, c = _mk(257, 6, 11, seed=4)
    w = jnp.ones((257,), jnp.float32).at[200:].set(0.0)
    segsum_runs = _spy(monkeypatch, L, "weighted_cluster_sums")
    fused_runs = _spy(monkeypatch, P, "fused_lloyd_pallas")
    want = ref.minibatch_ref(x, c, w)
    for name in ("pallas", "fused"):
        backend = B.get_backend(name)
        assert backend.minibatch_step_fn is not None
        res, _ = backend.minibatch_step(x, c, 11, w, ())
        jax.block_until_ready(res.sums)
        jax.effects_barrier()
        np.testing.assert_allclose(res.sums, want[2], rtol=1e-4, atol=1e-4,
                                   err_msg=name)
        np.testing.assert_allclose(float(res.energy), float(want[4]),
                                   rtol=1e-4, err_msg=name)
    assert not segsum_runs, "native weighted kernels skip the extra pass"
    assert len(fused_runs) == 1, "fused chunk step is one kernel run"


def test_instrument_counts_native_slots_once():
    """instrument() must count a native batched/minibatch step as exactly
    one pass (the fallback path used to route through the counted step_fn
    — a native slot must not be double- or un-counted)."""
    x, c = _mk(64, 4, 5, seed=6)
    w = jnp.ones((64,), jnp.float32)
    cs = jnp.stack([c, c + 0.5])
    for name in ("pallas", "fused"):
        passes = []
        bk = B.instrument(B.get_backend(name), lambda: passes.append(1))
        bk.minibatch_step(x, c, 5, w, ())
        jax.effects_barrier()
        assert len(passes) == 1, (name, passes)
        bk.batched_step(x, cs, 5, ((), ()))
        jax.effects_barrier()
        assert len(passes) == 2, (name, passes)


def test_minibatch_guard_runs_native_batched_kernel(monkeypatch):
    """Wiring: one streaming iteration on the fused backend = the R=2
    validation guard plus the weighted chunk pass, BOTH as native fused
    kernel runs (v1 vmapped pl.pallas_call for the guard and paid the
    fallback's segment-sum for the chunk)."""
    from repro.core.minibatch import (MiniBatchConfig, minibatch_init,
                                      minibatch_iteration)
    k = 5
    x = jnp.asarray(make_blobs(512, 4, k, seed=3, spread=4.0))
    xc, xv = x[:384], x[384:]
    w = jnp.ones((384,), jnp.float32)
    c0 = kmeanspp_init(jax.random.PRNGKey(0), x, k)
    fused_runs = _spy(monkeypatch, P, "fused_lloyd_pallas")
    backend = B.get_backend("fused")
    cfg = MiniBatchConfig(k=k, chunk_size=384)
    state = minibatch_init(c0, cfg, backend)
    state, _ = minibatch_iteration(xc, w, xv, state, cfg, backend)
    jax.block_until_ready(state.c)
    jax.effects_barrier()
    assert len(fused_runs) == 2, fused_runs
