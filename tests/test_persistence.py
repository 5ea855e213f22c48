"""Persistence engine + bugfix-sweep regressions (DESIGN.md §Persistence).

The load-bearing guarantee under test: a solve checkpointed at iteration t
and resumed — same process, new process, same or different mesh — is
BIT-IDENTICAL to the uninterrupted run, because segmentation only
partitions the identical sequence of jit'd loop bodies.  Parity is
asserted against the stored golden trajectory (tests/golden/), so resume
correctness and numeric stability are pinned by the same artifact.

Also here: the satellite bug regressions this PR's sweep fixed —
bf16 count saturation in `lloyd.cluster_sums` (a bf16 count freezes at
256), NaN-blind `select_best` (argmin returns 0 on any NaN energy),
Hamerly's O(K log K) argsort full scan (now a top-2 min reduction), and
the eager/unchunked estimator serving path.
"""

import os
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).parent / "golden"))
import generate_golden as G  # noqa: E402

from repro.checkpoint import latest_snapshot, load_estimator, resume_point
from repro.core import serialize
from repro.core.api import AAKMeans, MiniBatchAAKMeans
from repro.core.backends import Precision, get_backend
from repro.core.backends.dense import dense_backend
from repro.core.init_schemes import kmeanspp_init
from repro.core.kmeans import (KMeansConfig, aa_kmeans, aa_kmeans_batched,
                               aa_kmeans_minibatch, aa_kmeans_traced,
                               select_best)
from repro.core.lloyd import assign, cluster_sums, weighted_cluster_sums
from repro.core.minibatch import MiniBatchConfig, minibatch_init
from repro.data.streaming import chunk_dataset, split_validation
from repro.data.synthetic import make_blobs

CPU = jax.default_backend() == "cpu"


def _bits_equal(a, b, err_msg=""):
    a, b = np.asarray(a), np.asarray(b)
    if CPU:
        np.testing.assert_array_equal(
            a.view(np.uint32) if a.dtype == np.float32 else a,
            b.view(np.uint32) if b.dtype == np.float32 else b,
            err_msg=err_msg)
    else:
        np.testing.assert_allclose(a, b, rtol=1e-5, err_msg=err_msg)


@pytest.fixture(scope="module")
def golden_problem():
    """The exact problem behind tests/golden/aa_dense_cpu.npz."""
    x = jnp.asarray(make_blobs(G.N, G.D, G.K, seed=G.SEED, spread=G.SPREAD))
    c0 = kmeanspp_init(jax.random.PRNGKey(G.SEED), x, G.K)
    return x, c0, KMeansConfig(k=G.K, max_iter=G.MAX_ITER)


@pytest.fixture(scope="module")
def golden():
    with np.load(G.GOLDEN_PATH) as z:
        return {k: z[k] for k in z.files}


# ---------------------------------------------------------------------------
# serialize.py — the artifact layer
# ---------------------------------------------------------------------------

def test_serialize_roundtrip_bit_exact(tmp_path):
    tree = {"c": jnp.arange(12, dtype=jnp.float32).reshape(3, 4) * np.pi,
            "w": {"m": jnp.ones((5,), jnp.bfloat16) * 1.5,
                  "t": jnp.array(7, jnp.int32)},
            "flag": jnp.array(True)}
    p = serialize.save(tmp_path / "s", tree, kind="unit", extra={"t": 3})
    assert p.suffix == ".npz" and p.exists()
    like = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), tree)
    out, meta = serialize.restore(p, like, expect_kind="unit")
    assert meta["t"] == 3 and meta["schema"] == serialize.SCHEMA_VERSION
    for a, b in zip(jax.tree_util.tree_leaves(tree),
                    jax.tree_util.tree_leaves(out)):
        assert b.dtype == a.dtype
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_serialize_refuses_newer_schema_and_wrong_kind(tmp_path, monkeypatch):
    tree = {"a": jnp.zeros((2,))}
    p = serialize.save(tmp_path / "s", tree, kind="unit")
    with pytest.raises(ValueError, match="expected 'other'"):
        serialize.load(p, expect_kind="other")
    monkeypatch.setattr(serialize, "SCHEMA_VERSION", 0)
    with pytest.raises(ValueError, match="newer"):
        serialize.load(p)


def test_restore_shape_mismatch_is_loud(tmp_path):
    p = serialize.save(tmp_path / "s", {"a": jnp.zeros((4, 2))}, kind="unit")
    with pytest.raises(ValueError, match="shape mismatch"):
        serialize.restore(p, {"a": jax.ShapeDtypeStruct((3, 2), np.float32)})
    with pytest.raises(ValueError, match="missing leaves"):
        serialize.restore(p, {"b": jax.ShapeDtypeStruct((4, 2), np.float32)})


def test_serialize_migration_chain(tmp_path, monkeypatch):
    """Older-schema artifacts are upgraded through registered per-kind
    migrations; a missing migration fails loudly instead of guessing."""
    tree_old = {"c": jnp.arange(6, dtype=jnp.float32).reshape(3, 2),
                "e": jnp.asarray(4.5)}     # hypothetical old leaf name
    p = serialize.save(tmp_path / "s", tree_old, kind=serialize.KIND_LOOP,
                       extra={"t": 5})
    monkeypatch.setattr(serialize, "SCHEMA_VERSION",
                        serialize.SCHEMA_VERSION + 1)
    with pytest.raises(ValueError, match="no migration is registered"):
        serialize.load(p)

    def mig(meta, by_path):      # schema bump renamed 'e' -> 'energy'
        by_path["energy"] = by_path.pop("e")
        for leaf in meta["leaves"]:
            if leaf["path"] == "e":
                leaf["path"] = "energy"
        return meta, by_path     # schema bump applied by the chain

    serialize.register_migration(serialize.KIND_LOOP,
                                 serialize.SCHEMA_VERSION - 1, mig)
    try:
        like = {"c": jax.ShapeDtypeStruct((3, 2), np.float32),
                "energy": jax.ShapeDtypeStruct((), np.float32)}
        out, meta = serialize.restore(p, like,
                                      expect_kind=serialize.KIND_LOOP)
        assert meta["schema"] == serialize.SCHEMA_VERSION
        assert meta["t"] == 5
        np.testing.assert_array_equal(np.asarray(tree_old["c"]), out["c"])
        assert float(out["energy"]) == 4.5
    finally:
        serialize.unregister_migration(serialize.KIND_LOOP,
                                       serialize.SCHEMA_VERSION - 1)


def test_migrated_loop_state_resumes_bit_identical(tmp_path, monkeypatch):
    """End-to-end schema evolution drill on the real driver: snapshot a
    run, rewrite the artifact as if saved before a (simulated)
    `_LoopState` field rename, bump SCHEMA_VERSION, register the
    migration — the segmented driver resumes from the migrated artifact
    and reproduces the uninterrupted solve bit for bit."""
    x = jnp.asarray(make_blobs(400, 4, 5, seed=0, spread=1.0))
    c0 = kmeanspp_init(jax.random.PRNGKey(0), x, 5)
    cfg = KMeansConfig(k=5, max_iter=30)
    ref = aa_kmeans(x, c0, cfg)
    aa_kmeans(x, c0, cfg, checkpoint_every=5, checkpoint_dir=tmp_path)
    p = latest_snapshot(tmp_path)
    meta, by_path = serialize.load(p)    # current layout, current schema

    # forge the pre-rename artifact: leaf 'e_last' used to be 'e_final'
    old = dict(by_path)
    old["e_final"] = old.pop("e_last")
    extra = {k: v for k, v in meta.items()
             if k not in ("schema", "kind", "leaves")}
    p_old = serialize.save(tmp_path / "old_schema", old,
                           kind=serialize.KIND_LOOP, extra=extra)

    monkeypatch.setattr(serialize, "SCHEMA_VERSION",
                        serialize.SCHEMA_VERSION + 1)

    def mig(m, bp):
        bp["e_last"] = bp.pop("e_final")
        for leaf in m["leaves"]:
            if leaf["path"] == "e_final":
                leaf["path"] = "e_last"
        return m, bp

    serialize.register_migration(serialize.KIND_LOOP,
                                 serialize.SCHEMA_VERSION - 1, mig)
    try:
        res = aa_kmeans(x, c0, cfg, resume_from=p_old)
    finally:
        serialize.unregister_migration(serialize.KIND_LOOP,
                                       serialize.SCHEMA_VERSION - 1)
    assert float(res.energy) == float(ref.energy)
    np.testing.assert_array_equal(np.asarray(res.centroids),
                                  np.asarray(ref.centroids))


# ---------------------------------------------------------------------------
# Segmented drivers — resume parity against the golden trajectory
# ---------------------------------------------------------------------------

def test_segmented_trajectory_matches_golden(golden_problem, golden,
                                             tmp_path):
    """checkpoint_every=1 visits every post-iteration state; its e_last /
    labels must be the golden per-iteration trajectory bit for bit —
    segmentation may not change a single loop body."""
    x, c0, cfg = golden_problem
    states = []
    aa_kmeans(x, c0, cfg, checkpoint_every=1,
              checkpoint_cb=lambda st, t: states.append(st))
    live = [st for st in states if not bool(st.converged)]
    assert len(live) == golden["energies"].shape[0]
    _bits_equal(np.stack([np.asarray(st.e_last) for st in live]),
                golden["energies"], "per-iteration energies drifted")
    np.testing.assert_array_equal(
        np.stack([np.asarray(st.labels) for st in live]), golden["labels"])
    _bits_equal(states[-1].c, golden["centroids"], "final centroids")


@pytest.mark.parametrize("resume_at", [1, 2])
def test_resume_is_bit_identical(golden_problem, golden, tmp_path,
                                 resume_at):
    """Kill the solve at a segment boundary, restore the artifact in what
    is effectively a fresh process (path in, state out), finish: energies,
    labels and centroids match the uninterrupted run — and hence the
    golden file — exactly."""
    x, c0, cfg = golden_problem
    ref = aa_kmeans(x, c0, cfg)
    d = tmp_path / "run"
    res_ck = aa_kmeans(x, c0, cfg, checkpoint_every=5, checkpoint_dir=d)
    snaps = sorted(d.glob("it_*.npz"))
    assert latest_snapshot(d) == snaps[-1]
    path, meta = resume_point(d)
    assert path == snaps[-1]
    assert bool(ref.converged) and meta["t"] == int(ref.n_iter)
    assert meta["k"] == G.K and meta["backend"] == "dense"
    res_rs = aa_kmeans(x, c0, cfg, resume_from=snaps[resume_at])
    for r in (res_ck, res_rs):
        _bits_equal(r.energy, ref.energy)
        np.testing.assert_array_equal(np.asarray(r.labels),
                                      np.asarray(ref.labels))
        _bits_equal(r.centroids, golden["centroids"])
        assert int(r.n_iter) == int(ref.n_iter)
        assert int(r.n_accepted) == int(ref.n_accepted)


def test_resume_meta_guard(golden_problem, tmp_path):
    x, c0, cfg = golden_problem
    d = tmp_path / "run"
    aa_kmeans(x, c0, cfg, checkpoint_every=5, checkpoint_dir=d)
    snap = latest_snapshot(d)
    with pytest.raises(ValueError, match="shape mismatch"):
        aa_kmeans(x, c0[:-1], KMeansConfig(k=G.K - 1, max_iter=10),
                  resume_from=snap)
    with pytest.raises(ValueError, match="backend"):
        aa_kmeans(x, c0, cfg, backend="hamerly", resume_from=snap)


def test_checkpointed_call_refuses_jit(golden_problem):
    x, c0, cfg = golden_problem
    with pytest.raises(ValueError, match="host-side segment loop"):
        jax.jit(lambda a, b: aa_kmeans(a, b, cfg, checkpoint_every=2))(x, c0)


@pytest.fixture(scope="module")
def rejecting_problem():
    """21 iterations, five of them rejected (two pairs back to back)."""
    x = jnp.asarray(make_blobs(500, 3, 8, seed=2, spread=1.2))
    c0 = kmeanspp_init(jax.random.PRNGKey(2), x, 8)
    return x, c0, KMeansConfig(k=8, max_iter=200)


def test_segments_end_on_completed_iterations(rejecting_problem, tmp_path):
    """A rejected iteration spans two trips of the loop, but a segment
    never ends between them: at checkpoint_every=1 every snapshot is one
    iteration on from the last, and a resume from the snapshot right
    after a rejected iteration finishes as the uninterrupted solve."""
    x, c0, cfg = rejecting_problem
    ref = aa_kmeans(x, c0, cfg)
    d = tmp_path / "run"
    states = []

    def record(st, t):
        # checked as the solve runs: a segment that stopped between a
        # rejected iterate and its fallback would never advance t again
        assert t == len(states) + 1, (t, len(states))
        states.append((t, st))

    aa_kmeans(x, c0, cfg, checkpoint_every=1, checkpoint_dir=d,
              sync_writes=True, checkpoint_cb=record)
    ts = [t for t, _ in states]
    assert [int(st.t) for _, st in states] == ts
    snaps = {int(p.stem.split("_")[1]): p for p in d.glob("it_*.npz")}
    assert sorted(snaps) == ts
    n_acc = [0] + [int(st.n_acc) for _, st in states]
    rejected = [t for t in ts if n_acc[t] == n_acc[t - 1]
                and not bool(states[t - 1][1].converged)]
    assert rejected, "the problem must reject some iterate"
    res = aa_kmeans(x, c0, cfg, resume_from=snaps[rejected[0]])
    _bits_equal(res.energy, ref.energy)
    np.testing.assert_array_equal(np.asarray(res.labels),
                                  np.asarray(ref.labels))
    _bits_equal(res.centroids, ref.centroids)
    assert int(res.n_iter) == int(ref.n_iter)
    assert int(res.n_accepted) == int(ref.n_accepted)


@pytest.mark.parametrize("backend", ["dense", "hamerly"])
def test_traced_and_segmented_walk_the_same_iterations(rejecting_problem,
                                                       backend):
    """The Python-loop driver records an iteration when a trip completes
    one: its energies are, bit for bit, the e_last of each segment of a
    checkpoint_every=1 solve, and its result is aa_kmeans's."""
    x, c0, cfg = rejecting_problem
    tr = aa_kmeans_traced(x, c0, cfg, backend=backend)
    assert not all(tr.accepted)
    states = []
    aa_kmeans(x, c0, cfg, backend=backend, checkpoint_every=1,
              checkpoint_cb=lambda st, t: states.append(st))
    live = [st for st in states if not bool(st.converged)]
    _bits_equal(np.asarray(tr.energies, np.float32),
                np.stack([np.asarray(st.e_last) for st in live]),
                "traced energies")
    assert tr.accepted == [bool(b - a) for a, b in zip(
        [0] + [int(st.n_acc) for st in live[:-1]],
        [int(st.n_acc) for st in live])]
    ref = aa_kmeans(x, c0, cfg, backend=backend)
    assert int(tr.result.n_iter) == int(ref.n_iter)
    assert int(tr.result.n_accepted) == int(ref.n_accepted)
    assert bool(tr.result.converged) == bool(ref.converged)
    _bits_equal(tr.result.energy, ref.energy)
    np.testing.assert_array_equal(np.asarray(tr.result.labels),
                                  np.asarray(ref.labels))
    _bits_equal(tr.result.centroids, ref.centroids)


def test_batched_resume_is_bit_identical(rng, tmp_path):
    x = jnp.asarray(make_blobs(512, 6, 8, seed=1, spread=1.2))
    keys = jax.random.split(jax.random.PRNGKey(0), 3)
    c0s = jnp.stack([kmeanspp_init(k, x, 8) for k in keys])
    cfg = KMeansConfig(k=8, max_iter=60)
    ref = aa_kmeans_batched(x, c0s, cfg)
    d = tmp_path / "runb"
    aa_kmeans_batched(x, c0s, cfg, checkpoint_every=7, checkpoint_dir=d)
    snaps = sorted(d.glob("it_*.npz"))
    assert len(snaps) >= 2
    res = aa_kmeans_batched(x, c0s, cfg, resume_from=snaps[0])
    _bits_equal(res.energy, ref.energy)
    np.testing.assert_array_equal(np.asarray(res.labels),
                                  np.asarray(ref.labels))
    np.testing.assert_array_equal(np.asarray(res.n_iter),
                                  np.asarray(ref.n_iter))
    best = select_best(res)
    _bits_equal(best.energy, select_best(ref).energy)


def test_minibatch_resume_is_bit_identical(tmp_path):
    x = jnp.asarray(make_blobs(512, 6, 8, seed=2, spread=1.5))
    key = jax.random.PRNGKey(3)
    x_val, x_train = split_validation(x, 64, key)
    dc = chunk_dataset(x_train, 64)
    c0 = kmeanspp_init(jax.random.PRNGKey(4), x, 8)
    cfg = MiniBatchConfig(k=8, epochs=4)
    ref = aa_kmeans_minibatch(dc.chunks, dc.weights, x_val, c0, cfg, key=key)
    d = tmp_path / "runm"
    aa_kmeans_minibatch(dc.chunks, dc.weights, x_val, c0, cfg, key=key,
                        checkpoint_every=1, checkpoint_dir=d)
    snaps = sorted(d.glob("it_*.npz"))
    assert len(snaps) == cfg.epochs
    res = aa_kmeans_minibatch(dc.chunks, dc.weights, x_val, c0, cfg,
                              key=key, resume_from=snaps[1])
    _bits_equal(res.energy, ref.energy)
    _bits_equal(res.centroids, ref.centroids)


# ---------------------------------------------------------------------------
# Estimator persistence
# ---------------------------------------------------------------------------

def test_aakmeans_save_load_roundtrip(rng, tmp_path):
    x = make_blobs(400, 5, 6, seed=5, spread=2.0)
    m = AAKMeans(n_clusters=6, max_iter=50, n_init=2, seed=0).fit(x)
    p = m.save(tmp_path / "model")
    for m2 in (AAKMeans.load(p), load_estimator(p)):
        assert isinstance(m2, AAKMeans)
        assert m2.energy_ == m.energy_ and m2.n_iter_ == m.n_iter_
        np.testing.assert_array_equal(np.asarray(m2.centroids_),
                                      np.asarray(m.centroids_))
        np.testing.assert_array_equal(m2.predict(x), m.predict(x))
        np.testing.assert_allclose(m2.transform(x), m.transform(x),
                                   rtol=1e-6)
    with pytest.raises(ValueError, match="not an estimator artifact"):
        serialize.save(tmp_path / "junk", {"a": jnp.zeros(2)}, kind="unit")
        load_estimator(tmp_path / "junk.npz")


def test_minibatch_estimator_midstream_roundtrip(tmp_path):
    """A partial_fit stream killed mid-flight and reloaded in a 'new
    process' must finish exactly like the process that never died."""
    x = make_blobs(640, 5, 4, seed=6, spread=2.0)
    kw = dict(n_clusters=4, chunk_size=64, epochs=2, seed=0)
    m = MiniBatchAAKMeans(**kw)
    for i in range(0, 320, 64):
        m.partial_fit(x[i:i + 64])
    p = m.save(tmp_path / "mid")
    m2 = MiniBatchAAKMeans.load(p)
    assert m2.n_steps_ == m.n_steps_
    for mm in (m, m2):
        for i in range(320, 640, 64):
            mm.partial_fit(x[i:i + 64])
        mm.finalize()
    assert m2.energy_ == m.energy_
    np.testing.assert_array_equal(np.asarray(m2.centroids_),
                                  np.asarray(m.centroids_))
    # a FITTED artifact roundtrips too (and serves)
    p2 = m.save(tmp_path / "done")
    m3 = load_estimator(p2)
    np.testing.assert_array_equal(m3.predict(x), m.predict(x))


def test_estimator_backend_roundtrip(tmp_path):
    """A Backend-instance backend must rebuild equivalently on load:
    recording bare `bk.name` either failed to resolve ('blocked4096' is
    no registry key) or silently dropped a custom precision."""
    from repro.core.backends import blocked_backend, get_backend
    x = make_blobs(300, 4, 3, seed=12, spread=2.0)
    m = AAKMeans(n_clusters=3, max_iter=30, seed=0,
                 backend=blocked_backend(128)).fit(x)
    m2 = AAKMeans.load(m.save(tmp_path / "blk"))
    assert m2.backend.name == "blocked128"
    np.testing.assert_array_equal(m2.predict(x), m.predict(x))
    mb = AAKMeans(n_clusters=3, max_iter=30, seed=0,
                  backend=dense_backend(
                      Precision(compute=jnp.bfloat16))).fit(x)
    mb2 = AAKMeans.load(mb.save(tmp_path / "bf16"))
    assert mb2.backend.precision.compute == jnp.bfloat16
    reg = AAKMeans(n_clusters=3, max_iter=30, seed=0,
                   backend=get_backend("hamerly")).fit(x)
    assert AAKMeans.load(reg.save(tmp_path / "ham")).backend.name == \
        "hamerly"


def test_minibatch_cb_state_resumes_without_rerunning_epochs(tmp_path):
    """The checkpoint_cb payload carries the epoch counter, so feeding it
    back as resume_from continues the run instead of stacking cfg.epochs
    MORE epochs onto already-advanced state."""
    x = jnp.asarray(make_blobs(512, 6, 8, seed=13, spread=1.5))
    key = jax.random.PRNGKey(13)
    x_val, x_train = split_validation(x, 64, key)
    dc = chunk_dataset(x_train, 64)
    c0 = kmeanspp_init(jax.random.PRNGKey(14), x, 8)
    cfg = MiniBatchConfig(k=8, epochs=4)
    ref = aa_kmeans_minibatch(dc.chunks, dc.weights, x_val, c0, cfg,
                              key=key)
    snaps = []
    aa_kmeans_minibatch(dc.chunks, dc.weights, x_val, c0, cfg, key=key,
                        checkpoint_every=1,
                        checkpoint_cb=lambda tree, e: snaps.append(tree))
    assert snaps[1]["epoch"] == 2
    res = aa_kmeans_minibatch(dc.chunks, dc.weights, x_val, c0, cfg,
                              key=key, resume_from=snaps[1])
    assert int(res.n_steps) == int(ref.n_steps)
    _bits_equal(res.energy, ref.energy)
    _bits_equal(res.centroids, ref.centroids)


def test_batched_accum_policy_floors_at_f32():
    """Backend slots obey the >= f32 stat-accumulation floor even under
    an explicit accum=bf16 policy — the batched one-hot path used to
    accumulate counts in bf16 and saturate past 256 members."""
    bk = dense_backend(Precision(compute=jnp.bfloat16,
                                 accum=jnp.bfloat16))
    n = 1000
    x = jnp.ones((n, 4), jnp.bfloat16)
    cs = jnp.stack([jnp.zeros((2, 4), jnp.bfloat16)] * 2).at[:, 1].set(9.0)
    res, _ = bk.batched_step_fn(x, cs, 2, ((), ()))
    assert res.counts.dtype == jnp.float32
    np.testing.assert_array_equal(np.asarray(res.counts),
                                  [[n, 0]] * 2)
    resw, _ = bk.minibatch_step_fn(x, cs[0], 2, jnp.ones((n,), jnp.bfloat16),
                                   ())
    np.testing.assert_array_equal(np.asarray(resw.counts), [n, 0])


def test_chunked_local_predict_transform(rng):
    """Local (no-mesh) predict/transform are chunked + host-resident: the
    output is numpy, chunk size does not change values, and the jitted
    runner is cached on the model (one entry per kind)."""
    x = make_blobs(500, 4, 3, seed=7, spread=2.0)
    m = AAKMeans(n_clusters=3, max_iter=30, seed=0).fit(x)
    lab = m.predict(x, chunk_size=128)
    dist = m.transform(x, chunk_size=96)
    assert isinstance(lab, np.ndarray) and isinstance(dist, np.ndarray)
    assert dist.shape == (500, 3)
    np.testing.assert_array_equal(lab, m.predict(x, chunk_size=499))
    np.testing.assert_allclose(dist, m.transform(x, chunk_size=500),
                               rtol=1e-6)
    assert len(m._local_runners) == 2   # predict + transform, cached
    np.testing.assert_array_equal(lab, np.argmin(dist, axis=1))


# ---------------------------------------------------------------------------
# Bugfix sweep regressions
# ---------------------------------------------------------------------------

def test_bf16_counts_do_not_saturate():
    """bf16 has 8 mantissa bits: pre-fix, a count accumulated in x.dtype
    froze at 256 (256 + 1 rounds to 256) and the cluster's centroid
    silently drifted.  Counts/sums must now accumulate >= f32."""
    n = 1000
    x = jnp.ones((n, 4), jnp.bfloat16)
    labels = jnp.zeros((n,), jnp.int32)
    sums, counts = cluster_sums(x, labels, 2)
    assert counts.dtype == jnp.float32 and sums.dtype == jnp.float32
    np.testing.assert_array_equal(np.asarray(counts), [n, 0])
    _, wcounts = weighted_cluster_sums(x, labels,
                                       jnp.ones((n,), jnp.bfloat16), 2)
    np.testing.assert_array_equal(np.asarray(wcounts), [n, 0])


def test_bf16_dense_solve_counts_match_f32_oracle():
    """Acceptance criterion: a bf16 dense solve whose clusters exceed 256
    members keeps exact counts — equal to the integer histogram of the
    assignment it actually made (the f32 oracle)."""
    x = jnp.asarray(make_blobs(2000, 4, 4, seed=8, spread=6.0))
    c0 = kmeanspp_init(jax.random.PRNGKey(8), x, 4)
    bk = dense_backend(Precision(compute=jnp.bfloat16, accum=jnp.bfloat16))
    res = aa_kmeans(x.astype(jnp.bfloat16), c0.astype(jnp.bfloat16),
                    KMeansConfig(k=4, max_iter=50), backend=bk)
    step, _ = bk.step(x.astype(jnp.bfloat16), res.centroids, 4, ())
    oracle = np.bincount(np.asarray(step.labels), minlength=4)
    assert oracle.max() > 256, "fixture must exercise the saturation range"
    np.testing.assert_array_equal(np.asarray(step.counts, np.float64),
                                  oracle)
    # the streaming engine floors its long-horizon accumulators the same way
    st = minibatch_init(c0, MiniBatchConfig(k=4), bk)
    assert st.counts.dtype == jnp.float32


def test_select_best_skips_nan_energies():
    """argmin returns index 0 as soon as ANY energy is NaN — a degenerate
    restart must never beat finite ones."""
    x = jnp.asarray(make_blobs(256, 4, 4, seed=9, spread=2.0))
    keys = jax.random.split(jax.random.PRNGKey(9), 3)
    c0s = jnp.stack([kmeanspp_init(k, x, 4) for k in keys])
    res = aa_kmeans_batched(x, c0s, KMeansConfig(k=4, max_iter=40))
    e = np.asarray(res.energy).copy()
    e[0] = np.nan                      # restart 0 "wins" under bare argmin
    poisoned = res._replace(energy=jnp.asarray(e))
    best = select_best(poisoned)
    assert float(best.energy) == np.nanmin(e)
    # all-NaN surfaces instead of silently crowning restart 0
    all_nan = res._replace(energy=jnp.full_like(res.energy, np.nan))
    assert not np.isfinite(float(select_best(all_nan).energy))


def test_fit_surfaces_all_nan_restarts():
    x = np.full((64, 3), np.nan, np.float32)
    with pytest.raises(FloatingPointError, match="non-finite"):
        AAKMeans(n_clusters=2, max_iter=5, n_init=2, seed=0).fit(x)


def test_hamerly_full_scan_top2_parity(rng):
    """The argsort full scan became two O(K) min reductions; (argmin, min,
    second-min) and the tie convention (first index wins) are unchanged —
    including duplicated centroids, where d2 == d1."""
    from repro.core.backends.hamerly import _full_scan as scan_bk
    from repro.core.hamerly import _full_scan as scan_legacy
    x = jnp.asarray(rng.normal(size=(300, 5)).astype(np.float32))
    c = jnp.asarray(rng.normal(size=(16, 5)).astype(np.float32))
    c = c.at[7].set(c[3])              # exact duplicate: tie on d1/d2
    d = np.linalg.norm(np.asarray(x)[:, None] - np.asarray(c)[None], axis=2)
    order = np.argsort(d, axis=1, kind="stable")
    for scan in (scan_bk, scan_legacy):
        lab, d1, d2 = scan(x, c)
        np.testing.assert_array_equal(np.asarray(lab), order[:, 0])
        np.testing.assert_allclose(np.asarray(d1),
                                   np.take_along_axis(
                                       d, order[:, :1], 1)[:, 0], rtol=1e-5)
        np.testing.assert_allclose(np.asarray(d2),
                                   np.take_along_axis(
                                       d, order[:, 1:2], 1)[:, 0], rtol=1e-5)


def test_hamerly_solver_parity_with_lloyd():
    """Assignment parity end to end: the hamerly backend's solve labels
    equal the dense (plain Lloyd assignment) labels."""
    x = jnp.asarray(make_blobs(600, 6, 6, seed=10, spread=4.0))
    c0 = kmeanspp_init(jax.random.PRNGKey(10), x, 6)
    cfg = KMeansConfig(k=6, max_iter=60)
    res_h = aa_kmeans(x, c0, cfg, backend=get_backend("hamerly"))
    res_d = aa_kmeans(x, c0, cfg, backend="dense")
    np.testing.assert_array_equal(np.asarray(res_h.labels),
                                  np.asarray(res_d.labels))
    ref = assign(x, res_h.centroids)
    np.testing.assert_array_equal(np.asarray(res_h.labels),
                                  np.asarray(ref.labels))


# ---------------------------------------------------------------------------
# Distributed: elastic (re-mesh) resume — subprocess, 8 virtual devices
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_distributed_elastic_resume(tmp_path):
    from test_distributed import _run
    _run(f"""
import jax, jax.numpy as jnp, numpy as np, os
from repro.core.distributed import make_distributed_kmeans
from repro.core.init_schemes import kmeanspp_init
from repro.core.kmeans import KMeansConfig, aa_kmeans
from repro.data.synthetic import make_blobs

d = {str(tmp_path)!r}
x = jnp.asarray(make_blobs(512, 8, 8, seed=11, spread=5.0))
c0 = kmeanspp_init(jax.random.PRNGKey(11), x, 8)
cfg = KMeansConfig(k=8, max_iter=100)

mesh8 = jax.make_mesh((8,), ("data",),
                      axis_types=(jax.sharding.AxisType.Auto,))
fit8 = make_distributed_kmeans(mesh8, cfg, checkpoint_every=1,
                               checkpoint_dir=d)
ref8 = fit8(x, c0)                      # uninterrupted (segments, ckpts)
snaps = sorted(p for p in os.listdir(d) if p.endswith(".npz"))
assert len(snaps) >= 2, snaps

# 1. same-mesh resume: bit-identical to the uninterrupted segmented run
res = make_distributed_kmeans(mesh8, cfg)(
    x, c0, resume_from=os.path.join(d, snaps[0]))
np.testing.assert_array_equal(
    np.float32(res.energy).view(np.uint32),
    np.float32(ref8.energy).view(np.uint32))
np.testing.assert_array_equal(np.asarray(res.labels),
                              np.asarray(ref8.labels))
assert int(res.n_iter) == int(ref8.n_iter)

# 2. elastic: the SAME artifact restores onto a different mesh geometry
#    and axes layout (2x2 over ("pod","data")); trajectory agrees with
#    the local oracle up to psum reduction order.
mesh22 = jax.make_mesh((2, 2), ("pod", "data"),
                       axis_types=(jax.sharding.AxisType.Auto,)*2)
fit22 = make_distributed_kmeans(mesh22, cfg, data_axes=("pod", "data"))
res22 = fit22(x, c0, resume_from=os.path.join(d, snaps[0]))
ref = aa_kmeans(x, c0, cfg)
assert bool(res22.converged)
np.testing.assert_allclose(float(res22.energy), float(ref.energy),
                           rtol=1e-5)
assert (np.asarray(res22.labels) == np.asarray(ref.labels)).mean() > 0.999
print("elastic resume OK")
""")
