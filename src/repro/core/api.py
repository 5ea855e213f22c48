"""Top-level estimator API for the paper's solver.

    from repro.core.api import AAKMeans, MiniBatchAAKMeans
    model = AAKMeans(n_clusters=10, init="kmeans++", n_init=3).fit(x)
    labels = model.predict(x_new)

    stream = MiniBatchAAKMeans(n_clusters=10, chunk_size=8192)
    stream.fit(x)                       # X on device, chunked epochs
    stream2 = MiniBatchAAKMeans(n_clusters=10, chunk_size=8192)
    stream2.partial_fit(x_big[:8192])   # seeds centroids + carves val rows
    for chunk in host_chunk_stream(x_big[8192:], 8192, epochs=3):
        stream2.partial_fit(chunk)      # X never fully on device
    stream2.finalize()
    # (streaming the FULL x_big for several epochs would re-feed the
    #  carved validation rows as training data from epoch 2 on — feed the
    #  first chunk once and epoch only over the remainder, as above)

Thin, sklearn-shaped wrappers: `AAKMeans` over the batched multi-restart
full-batch solver, `MiniBatchAAKMeans` over the streaming chunked solver
(DESIGN.md §Streaming).  All heavy work stays in the jit'd solvers, and a
mesh-fitted model keeps using its mesh for predict/transform.
"""

from __future__ import annotations

import dataclasses
import functools
import math
import re
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.core import serialize
from repro.core.anderson import AAConfig
from repro.core.distributed import (make_distributed_kmeans_batched,
                                    make_distributed_kmeans_minibatch,
                                    shard_dataset, shard_map)
from repro.core.init_schemes import batched_init, make_init
from repro.core.kmeans import (KMeansConfig, KMeansResult,
                               aa_kmeans_batched, aa_kmeans_minibatch,
                               minibatch_stream_like, resolve_backend,
                               select_best)
from repro.core.minibatch import (MiniBatchConfig, MiniBatchState,
                                  guard_pick, minibatch_init,
                                  minibatch_iteration)
from repro.data.streaming import (chunk_dataset, shard_count,
                                  split_validation)


_FIT_PROGRAM_TRACES = 0


@functools.partial(jax.jit, static_argnames=("cfg", "backend"))
def _fit_program(x, c0s, cfg: KMeansConfig, backend) -> KMeansResult:
    """`AAKMeans.fit`'s single-device program: the batched solve over the
    (R, K, d) seeds and the best restart picked on device.  One module-
    level jit, so a configuration (``cfg``, ``backend``, the shapes of
    ``x`` and ``c0s``) is traced and lowered once per process, not once
    per fit.  ``backend`` stays unresolved in the static key: a registry
    name or the user's Backend instance compares equal across fits, where
    an adapter built per call would not."""
    global _FIT_PROGRAM_TRACES
    _FIT_PROGRAM_TRACES += 1            # the body runs at trace time only
    return select_best(aa_kmeans_batched(x, c0s, cfg, backend=backend))


def fit_program_traces() -> int:
    """How many times `AAKMeans.fit`'s single-device program has been
    traced in this process; over the number of such fits, the share of
    fits that missed the jit cache."""
    return _FIT_PROGRAM_TRACES


class NotFittedError(RuntimeError):
    """Inference was requested on an estimator with no fitted state.

    A real exception, not a bare ``assert``: under ``python -O`` asserts
    are compiled away, which used to turn "call fit() first" into an
    opaque None-attribute crash inside the first jitted call."""


def _mesh_rows_apply(model, x, kind, fn, extras=()):
    """Run ``fn(x_local, centroids, *extras) -> per-row output`` under a
    fitted model's mesh: rows sharded over its data axes, centroids (and
    any extra operands, e.g. the closure index arrays) replicated,
    padding rows (added to match the shard count) stripped from the
    result.  The jitted shard_map program is cached on the model per
    (kind, mesh, axes, backend), so a serving loop pays compilation once
    and refitting with a different composition cannot reuse a stale
    program."""
    axes = tuple(model.data_axes)
    x_sh, _ = shard_dataset(x, model.mesh, model.data_axes)
    cache = model.__dict__.setdefault("_mesh_runners", {})
    cache_key = (kind, model.mesh, axes, model.backend)
    run = cache.get(cache_key)
    if run is None:
        run = cache[cache_key] = jax.jit(shard_map(
            fn, mesh=model.mesh,
            in_specs=(P(axes), P()) + (P(),) * len(extras),
            out_specs=P(axes)))
    out = run(x_sh, jnp.asarray(model.centroids_), *extras)
    return out[:x.shape[0]]


def _chunked_rows_apply(model, x, kind, fn, out_dtype, out_cols=None,
                        chunk_size=None, extras=()):
    """Run ``fn(x_chunk, centroids, *extras) -> per-row output`` jitted,
    chunk by chunk, into a HOST (numpy) array — the single-device serving
    path shared by both estimators.  The chunking bounds the device
    footprint for host-sized X (an (N, K) transform of such an X would
    not fit back on device either, hence the numpy result), and the
    jitted fn is cached on the model per (kind, backend) so a serving
    loop pays dispatch/tracing once instead of eager per-call overhead.

    Every chunk fed to the jitted fn has EXACTLY ``step`` rows: the tail
    chunk is padded with copies of its last row and the padding sliced
    off the output.  One compiled shape total — a serving loop over
    varying N used to retrace per distinct remainder (N % step), which
    is precisely the varying-batch-size pattern a request queue
    produces.  Extras are passed through to the fn unchanged, so index
    arrays can be swapped (same shapes) without invalidating the cache."""
    cache = model.__dict__.setdefault("_local_runners", {})
    run = cache.get((kind, model.backend))
    if run is None:
        run = cache[(kind, model.backend)] = jax.jit(fn)
    step = chunk_size or getattr(model, "chunk_size", 0) or 16384
    n = x.shape[0]
    c = jnp.asarray(model.centroids_)
    shape = (n,) if out_cols is None else (n, out_cols)
    out = np.empty(shape, out_dtype)
    for i in range(0, n, step):
        # put stages the chunk on the host; the runtime relayouts and
        # copies it to the device after the span closes, so that wait
        # falls inside fetch, with the program's run and the copy back
        with jax.profiler.TraceAnnotation("repro.rows.put"):
            xc = jnp.asarray(x[i:i + step])
            m = xc.shape[0]
            if m < step:
                xc = jnp.concatenate(
                    [xc, jnp.repeat(xc[-1:], step - m, axis=0)])
        with jax.profiler.TraceAnnotation("repro.rows.run"):
            yc = run(xc, c, *extras)
        with jax.profiler.TraceAnnotation("repro.rows.fetch"):
            out[i:i + m] = np.asarray(yc)[:m]
    return out


# -- shared inference paths (both estimators) --------------------------------

def _closure_extras(model):
    """(routers, candidates, candidate_table) when the model carries a
    serving index.  The (G, C, d) table is built once per inference call
    and threaded through as an operand so every chunk scans contiguous
    block rows instead of paying a scattered per-row centroid gather."""
    if getattr(model, "closure_routers_", None) is None:
        return None
    from repro.serving.closure import candidate_table
    candidates = jnp.asarray(model.closure_candidates_)
    return (jnp.asarray(model.closure_routers_), candidates,
            candidate_table(model.centroids_, candidates))


def _predict_rows(model, x, chunk_size=None, approx=False):
    """Nearest-centroid labels; ``approx=True`` routes through the
    cluster-closure candidate index (`repro.serving.closure`) when the
    model carries one — exact argmin over each row's candidate list,
    sublinear in K — and falls back to the full-K scan when it does not
    (legacy/index-less artifacts serve unchanged, just slower)."""
    model._assert_fitted()
    extras = _closure_extras(model) if approx else None
    if extras is not None:
        from repro.serving.closure import closure_assign
        fn = lambda xl, c, r, cd, t: closure_assign(  # noqa: E731
            xl, c, r, cd, t)[0]
        if model.mesh is not None:
            return _mesh_rows_apply(model, jnp.asarray(x),
                                    "predict_closure", fn, extras=extras)
        return _chunked_rows_apply(model, x, "predict_closure", fn,
                                   np.int32, chunk_size=chunk_size,
                                   extras=extras)
    bk = resolve_backend(model.backend)
    label_fn = lambda xl, c: bk.assign(xl, c).labels  # noqa: E731
    if model.mesh is not None:
        return _mesh_rows_apply(model, jnp.asarray(x), "predict", label_fn)
    return _chunked_rows_apply(model, x, "predict", label_fn, np.int32,
                               chunk_size=chunk_size)


def _transform_rows(model, x, chunk_size=None, approx=False):
    """Distances to each centroid (N, K).  ``approx=True`` with a fitted
    closure index prices only each row's candidate centroids — the other
    columns come back +inf (consistent with `closure_assign`'s argmin,
    and honest about not having been computed)."""
    from repro.core.lloyd import pairwise_sqdist
    model._assert_fitted()
    extras = _closure_extras(model) if approx else None
    if extras is not None:
        from repro.serving.closure import closure_sqdist
        fn = lambda xl, c, r, cd, t: jnp.sqrt(  # noqa: E731
            closure_sqdist(xl, c, r, cd, t))
        if model.mesh is not None:
            return _mesh_rows_apply(model, jnp.asarray(x),
                                    "transform_closure", fn, extras=extras)
        return _chunked_rows_apply(model, x, "transform_closure", fn,
                                   np.float32, out_cols=model.n_clusters,
                                   chunk_size=chunk_size, extras=extras)
    dist_fn = lambda xl, c: jnp.sqrt(pairwise_sqdist(xl, c))  # noqa: E731
    if model.mesh is not None:
        return _mesh_rows_apply(model, jnp.asarray(x), "transform",
                                dist_fn)
    return _chunked_rows_apply(model, x, "transform", dist_fn,
                               np.float32, out_cols=model.n_clusters,
                               chunk_size=chunk_size)


def _build_serving_index(model, n_candidates=None, n_groups=None, seed=0):
    """Build + attach the cluster-closure index (DESIGN.md §Serving) to a
    fitted model; persisted by ``save`` and restored by ``load``.

    A hierarchically-fitted model (`AAKMeans(hierarchical=True)`) gets
    its index FOR FREE: the solve's super-centroids are the routers and
    each group's codebook rows are its candidate list
    (`repro.serving.closure.hierarchy_closure_index`) — no codebook
    re-clustering.  Passing explicit ``n_candidates``/``n_groups`` opts
    back into the built-from-scratch index."""
    model._assert_fitted()
    if n_candidates is None and n_groups is None \
            and getattr(model, "hier_routers_", None) is not None:
        from repro.serving.closure import hierarchy_closure_index
        idx = hierarchy_closure_index(jnp.asarray(model.centroids_),
                                      jnp.asarray(model.hier_routers_),
                                      jnp.asarray(model.hier_offsets_))
    else:
        from repro.serving.closure import build_closure_index
        idx = build_closure_index(jnp.asarray(model.centroids_),
                                  n_candidates=n_candidates,
                                  n_groups=n_groups, seed=seed)
    model.closure_routers_ = idx.routers
    model.closure_candidates_ = idx.candidates
    return model


def _closure_index(model):
    """The model's `ClosureIndex`, or None when none was built."""
    if getattr(model, "closure_routers_", None) is None:
        return None
    from repro.serving.closure import ClosureIndex
    return ClosureIndex(jnp.asarray(model.closure_routers_),
                        jnp.asarray(model.closure_candidates_))


# -- estimator persistence (DESIGN.md §Persistence) -------------------------

def _encode_backend(bk):
    """Registry names pass through; a Backend instance is recorded by
    registry identity + precision policy so `load` can rebuild an
    EQUIVALENT engine — recording only `bk.name` would either fail to
    resolve ('blocked4096' is not a registry key) or silently drop a
    custom precision, serving at a different dtype than the fit."""
    if isinstance(bk, str):
        return bk
    enc = {"name": bk.name}
    prec = bk.precision
    if prec.compute is not None:
        enc["compute"] = np.dtype(prec.compute).name
    if prec.accum is not None:
        enc["accum"] = np.dtype(prec.accum).name
    return enc


def _decode_backend(enc, path):
    if isinstance(enc, str):
        return enc
    from repro.core.backends import Precision, backend_names, get_backend
    name = enc["name"].split("@")[0]   # the mesh wrap belongs to a process
    opts = {}
    m = re.fullmatch(r"blocked(\d+)", name)
    if m:
        name, opts["block_n"] = "blocked", int(m.group(1))
    if "compute" in enc or "accum" in enc:
        opts["precision"] = Precision(
            compute=np.dtype(enc["compute"]) if "compute" in enc else None,
            accum=np.dtype(enc["accum"]) if "accum" in enc else None)
    if name not in backend_names():
        raise ValueError(
            f"{path}: model was fitted with backend {enc['name']!r}, which "
            f"cannot be rebuilt from the registry "
            f"({sorted(backend_names())}); construct the engine yourself "
            f"and set model.backend on the loaded model before serving")
    return get_backend(name, **opts)


def _save_estimator(model, path, kind, arrays: dict, stream: dict,
                    scalars: dict):
    """One serialize.py artifact: fitted arrays + (optionally) streaming
    state as the tree, constructor params and scalar fitted stats in the
    meta block.  The mesh is deliberately NOT persisted — a mesh is a
    property of the process, not of the model; a loaded model is local
    until the caller assigns one."""
    params = {}
    for f in dataclasses.fields(model):
        if f.name.endswith("_") or f.name.startswith("_"):
            continue
        v = getattr(model, f.name)
        if f.name in ("mesh", "metrics"):
            # both are process properties, not model parameters: a mesh
            # belongs to the device topology, a metrics sink to whatever
            # log file/stream this process opened
            continue
        if f.name == "backend":
            v = _encode_backend(v)
        if f.name == "data_axes":
            v = list(v)
        params[f.name] = v
    tree = {"arrays": arrays}
    if stream:
        tree["stream"] = stream
    return serialize.save(
        path, tree, kind=kind,
        extra={"params": params, "scalars": scalars,
               "has": sorted(arrays), "has_stream": sorted(stream)})


def _load_estimator(cls, path, kind):
    meta, by_path = serialize.load(path, expect_kind=kind)
    params = dict(meta["params"])
    params["data_axes"] = tuple(params.get("data_axes", ("data",)))
    params["backend"] = _decode_backend(params.get("backend", "dense"), path)
    model = cls(**params)
    for name in meta["has"]:
        setattr(model, name, jnp.asarray(by_path[f"arrays/{name}"]))
    for name, val in meta["scalars"].items():
        setattr(model, name, val)
    return model, meta, by_path


@dataclasses.dataclass
class AAKMeans:
    n_clusters: int
    init: str = "kmeans++"
    n_init: int = 1
    max_iter: int = 500
    accelerated: bool = True
    m0: int = 2
    mbar: int = 30
    dynamic_m: bool = True
    # Paper's Algorithm-1 thresholds / stabilisation — exposed so Table-2
    # style eps sweeps run through the public estimator.
    eps1: float = 0.02
    eps2: float = 0.5
    ridge: float = 1e-12
    seed: int = 0
    mesh: Optional[jax.sharding.Mesh] = None      # distributed when set
    data_axes: tuple = ("data",)
    # local-compute engine: "dense" | "blocked" | "pallas" | "fused" |
    # "hamerly" or a Backend instance; composed with the mesh when set.
    backend: object = "dense"
    # runtime metrics sink (`repro.runtime.metrics`): None | "stdout" |
    # anything with log_scalars(step, dict).  Setting one routes the fit
    # through the segmented driver (per-segment host boundaries are where
    # the scalars materialise).  Not persisted by save().
    metrics: object = None
    # cluster-closure serving index (DESIGN.md §Serving): None = don't
    # build at fit time; True = build with default sizing; an int = build
    # with that candidate count.  `build_serving_index()` attaches one to
    # an already-fitted model either way.
    serving_index: object = None
    # two-level divide-and-conquer fit (DESIGN.md §Hierarchy): False =
    # flat batched solve; True = `aa_kmeans_hierarchical` with defaults
    # (G = divisor of K nearest √K); a dict = keyword overrides for the
    # hierarchy driver (n_groups=, n_reassign=, super_max_iter=, ...).
    # The million-cluster regime — flat assignment work is O(N·K·d),
    # hierarchical roughly O(N·(G + K/G)·d).
    hierarchical: object = False

    # fitted state
    centroids_: Optional[jax.Array] = None
    labels_: Optional[jax.Array] = None
    energy_: Optional[float] = None
    n_iter_: Optional[int] = None
    n_accepted_: Optional[int] = None
    closure_routers_: Optional[jax.Array] = None
    closure_candidates_: Optional[jax.Array] = None
    # hierarchical fit extras: level-1 routers + group-major codebook
    # offsets (the free serving index; see `_build_serving_index`)
    hier_routers_: Optional[jax.Array] = None
    hier_offsets_: Optional[jax.Array] = None

    def _config(self) -> KMeansConfig:
        return KMeansConfig(
            k=self.n_clusters, max_iter=self.max_iter,
            accelerated=self.accelerated,
            aa=AAConfig(m0=self.m0, mbar=self.mbar,
                        dynamic_m=self.dynamic_m,
                        eps1=self.eps1, eps2=self.eps2, ridge=self.ridge))

    def fit(self, x) -> "AAKMeans":
        x = jnp.asarray(x)
        n = x.shape[0]
        cfg = self._config()
        n_init = max(self.n_init, 1)
        if self.hierarchical:
            return self._fit_hierarchical(x, cfg, n_init)
        with jax.profiler.TraceAnnotation("repro.fit.seed"):
            keys = jax.random.split(jax.random.PRNGKey(self.seed), n_init)
            c0s = jnp.asarray(batched_init(self.init, keys, x,
                                           self.n_clusters))
        with jax.profiler.TraceAnnotation("repro.fit.solve"):
            if self.mesh is not None:
                fit_fn = make_distributed_kmeans_batched(
                    self.mesh, cfg, self.data_axes, backend=self.backend,
                    pick_best=True)
                x_in, _ = shard_dataset(x, self.mesh, self.data_axes)
            elif self.metrics is not None:
                # segmented (host-loop) driver: metrics need host boundaries
                fit_fn = lambda a, b: select_best(  # noqa: E731
                    aa_kmeans_batched(a, b, cfg, backend=self.backend,
                                      metrics=self.metrics))
                x_in = x
            else:
                fit_fn = functools.partial(_fit_program, cfg=cfg,
                                           backend=self.backend)
                x_in = x
            # ONE device program: R restarts solved in a batch, winner
            # picked on device — n_init no longer multiplies
            # dispatch/transfer cost.
            best: KMeansResult = fit_fn(x_in, c0s)
        with jax.profiler.TraceAnnotation("repro.fit.result"):
            energy = float(best.energy)
            n_iter, n_accepted = int(best.n_iter), int(best.n_accepted)
        if not math.isfinite(energy):
            # select_best skips non-finite restarts, so reaching here means
            # EVERY restart degenerated (NaN rows in X, exploded iterate).
            # Surfacing beats returning restart 0 with a NaN inertia that
            # every downstream comparison silently treats as "best".
            raise FloatingPointError(
                f"all {n_init} restarts produced non-finite energies "
                f"(E={energy}); check X for NaN/inf rows")
        self.centroids_ = best.centroids
        self.labels_ = best.labels[:n]
        self.energy_ = energy
        self.n_iter_ = n_iter
        self.n_accepted_ = n_accepted
        # fresh centroids invalidate any previous closure index (and any
        # previous hierarchical structure); rebuild when requested, never
        # serve a stale one
        self.closure_routers_ = self.closure_candidates_ = None
        self.hier_routers_ = self.hier_offsets_ = None
        if self.serving_index:
            self.build_serving_index(
                n_candidates=self.serving_index
                if isinstance(self.serving_index, int)
                and not isinstance(self.serving_index, bool) else None)
        return self

    def _fit_hierarchical(self, x, cfg, n_init) -> "AAKMeans":
        """k²-means divide-and-conquer fit (`repro.core.hierarchy`): the
        million-cluster path.  Keeps the flat fit's contract (centroids_,
        original-row-order labels_, finite-energy check) and additionally
        records the two-level routing structure, which ``save`` persists
        and the serving index reuses for free."""
        if self.mesh is not None:
            raise NotImplementedError(
                "hierarchical=True is a host-driven round loop; a "
                "mesh-distributed hierarchy is a ROADMAP follow-up — fit "
                "flat under the mesh or hierarchical on one device")
        from repro.core.hierarchy import aa_kmeans_hierarchical
        opts = dict(self.hierarchical) \
            if isinstance(self.hierarchical, dict) else {}
        res = aa_kmeans_hierarchical(
            x, self.n_clusters, cfg, backend=self.backend,
            n_init=n_init, init=self.init, seed=self.seed,
            metrics=self.metrics, **opts)
        energy = float(res.energy)
        if not math.isfinite(energy):
            raise FloatingPointError(
                f"hierarchical fit produced a non-finite energy "
                f"(E={energy}); check X for NaN/inf rows")
        self.centroids_ = res.centroids
        self.labels_ = res.labels
        self.energy_ = energy
        self.n_iter_ = int(res.n_rounds)
        self.n_accepted_ = None
        self.hier_routers_ = res.routers
        self.hier_offsets_ = res.group_offsets
        self.closure_routers_ = self.closure_candidates_ = None
        if self.serving_index:
            self.build_serving_index(
                n_candidates=self.serving_index
                if isinstance(self.serving_index, int)
                and not isinstance(self.serving_index, bool) else None)
        return self

    # -- inference --------------------------------------------------------

    def _assert_fitted(self):
        if self.centroids_ is None:
            raise NotFittedError(
                "this AAKMeans instance has no fitted centroids; call "
                "fit() (or load() a fitted artifact) first")

    def _mesh_apply(self, x, kind, fn):
        return _mesh_rows_apply(self, x, kind, fn)

    def build_serving_index(self, n_candidates: Optional[int] = None,
                            n_groups: Optional[int] = None,
                            seed: int = 0) -> "AAKMeans":
        """Attach a cluster-closure candidate index to the fitted
        centroids (`repro.serving.closure`); ``save`` persists it and
        ``load`` restores it, so the serving process never rebuilds."""
        return _build_serving_index(self, n_candidates=n_candidates,
                                    n_groups=n_groups, seed=seed)

    @property
    def closure_index_(self):
        """The fitted `ClosureIndex`, or None when none was built."""
        return _closure_index(self)

    def predict(self, x, chunk_size: Optional[int] = None,
                approx: bool = False):
        """Nearest-centroid labels.  A mesh-fitted model assigns under the
        same mesh/backend composition as ``fit`` — rows sharded over the
        data axes, centroids replicated — instead of silently falling back
        to a single-device pass over the full X (which defeats the point
        of a distributed fit and breaks once N exceeds one device).  The
        local path runs jitted and chunked into a host array
        (`_chunked_rows_apply`): a serving loop previously paid eager
        dispatch per call, and a host-sized X materialised (N, K) at once.
        ``approx=True`` scores only the closure index's candidate
        centroids per row (sublinear in K); without a fitted index it
        falls back to the exact full scan."""
        return _predict_rows(self, x, chunk_size=chunk_size, approx=approx)

    def transform(self, x, chunk_size: Optional[int] = None,
                  approx: bool = False):
        """Distances to each centroid (N, K); mesh-fitted models compute
        the row block on each shard's local rows (K is replicated), the
        local path is jitted + chunked like ``predict``.  ``approx=True``
        prices only the candidate centroids (+inf elsewhere)."""
        return _transform_rows(self, x, chunk_size=chunk_size,
                               approx=approx)

    @property
    def inertia_(self) -> float:
        return self.energy_

    # -- persistence ------------------------------------------------------

    def save(self, path):
        """Persist params + fitted state to one npz artifact (no pickle;
        `repro.core.serialize` schema) so a fitted model ships to a
        serving process.  A Backend instance is recorded by registry
        identity + precision and rebuilt on ``load``; the mesh is NOT
        persisted — assign one after ``load`` when distributed serving is
        wanted."""
        self._assert_fitted()
        arrays = {"centroids_": jnp.asarray(self.centroids_)}
        if self.labels_ is not None:
            arrays["labels_"] = jnp.asarray(self.labels_)
        if self.closure_routers_ is not None:
            arrays["closure_routers_"] = jnp.asarray(self.closure_routers_)
            arrays["closure_candidates_"] = \
                jnp.asarray(self.closure_candidates_)
        if self.hier_routers_ is not None:
            # the two-level structure rides the same npz schema: load()
            # restores every array named in meta["has"] generically
            arrays["hier_routers_"] = jnp.asarray(self.hier_routers_)
            arrays["hier_offsets_"] = jnp.asarray(self.hier_offsets_)
        scalars = {"energy_": self.energy_, "n_iter_": self.n_iter_,
                   "n_accepted_": self.n_accepted_}
        return _save_estimator(self, path, serialize.KIND_ESTIMATOR_AA,
                               arrays, {}, scalars)

    @classmethod
    def load(cls, path) -> "AAKMeans":
        """Rebuild a fitted estimator from ``save``'s artifact."""
        model, _, _ = _load_estimator(cls, path,
                                      serialize.KIND_ESTIMATOR_AA)
        return model


@dataclasses.dataclass
class MiniBatchAAKMeans:
    """Streaming mini-batch AA K-Means estimator (DESIGN.md §Streaming).

    Two consumption modes over the same chunk-step state machine:

      * ``fit(x, chunk_size=...)`` — X fits on device (or on the mesh):
        a random ``val_size`` validation chunk is held out for the energy
        guard, the rest is chunked, and one jit'd program runs every
        epoch (`kmeans.aa_kmeans_minibatch`; the distributed driver when
        ``mesh`` is set).
      * ``partial_fit(chunk)`` — X never fits on device: feed host chunks
        one at a time (`repro.data.streaming.host_chunk_stream`); the
        first call carves its leading rows into the validation chunk and
        seeds the centroids, each later call is one jit'd chunk step.
        Keep chunk lengths uniform to avoid re-jitting, and when making
        multiple epochs, re-stream only the rows AFTER the first chunk
        (see the module docstring) so the carved validation rows stay
        held out — re-feeding them would bias the guard energies
        optimistic.

    After ``fit``, ``centroids_`` is the final validation-guard-picked
    iterate and ``energy_`` its total *validation-chunk* energy (full-X
    energy is deliberately never computed — that is the point of the
    streaming solver).  During a ``partial_fit`` sequence, ``centroids_``
    tracks the running-stats fallback iterate (always safe) while
    ``energy_`` is the guard's most recent pricing — of the iterate that
    *entered* the last chunk step, i.e. one step behind ``centroids_``
    (the guard is the only val pass per step; pricing the exit iterate
    would cost a second).  ``finalize()`` reprices the current iterates
    and applies the guard pick, making the pair consistent.
    """
    n_clusters: int
    chunk_size: int = 4096
    epochs: int = 5
    decay: float = 0.9
    val_size: int = 1024
    init: str = "kmeans++"
    accelerated: bool = True
    m0: int = 2
    mbar: int = 30
    dynamic_m: bool = True
    eps1: float = 0.02
    eps2: float = 0.5
    ridge: float = 1e-12
    seed: int = 0
    compute_labels: bool = True      # fit() labels the input like sklearn
    mesh: Optional[jax.sharding.Mesh] = None
    data_axes: tuple = ("data",)
    backend: object = "dense"
    # runtime metrics sink (`repro.runtime.metrics`); fit() logs per
    # epoch, partial_fit per chunk.  Per-chunk logging float()s device
    # scalars — a host sync the stream otherwise avoids — so attach a
    # sink only when the diagnostics are worth it.  Not persisted.
    metrics: object = None

    # fitted state
    centroids_: Optional[jax.Array] = None
    labels_: Optional[jax.Array] = None
    energy_: Optional[float] = None
    n_steps_: Optional[int] = None
    n_accepted_: Optional[int] = None
    closure_routers_: Optional[jax.Array] = None
    closure_candidates_: Optional[jax.Array] = None

    # streaming state (partial_fit)
    _state: object = dataclasses.field(default=None, repr=False)
    _x_val: object = dataclasses.field(default=None, repr=False)
    _step_fn: object = dataclasses.field(default=None, repr=False)

    def _config(self, chunk_size: Optional[int] = None) -> MiniBatchConfig:
        return MiniBatchConfig(
            k=self.n_clusters,
            chunk_size=chunk_size or self.chunk_size,
            epochs=self.epochs, decay=self.decay,
            accelerated=self.accelerated,
            aa=AAConfig(m0=self.m0, mbar=self.mbar,
                        dynamic_m=self.dynamic_m,
                        eps1=self.eps1, eps2=self.eps2, ridge=self.ridge))

    def _val_rows(self, n: int) -> int:
        v = min(self.val_size, max(n // 4, self.n_clusters))
        if self.mesh is not None:
            v -= v % shard_count(self.mesh, self.data_axes)
        if v < 1:
            raise ValueError(
                f"cannot carve a validation chunk from N={n} rows "
                f"(val_size={self.val_size})")
        return v

    def fit(self, x, chunk_size: Optional[int] = None) -> "MiniBatchAAKMeans":
        x = jnp.asarray(x)
        cfg = self._config(chunk_size)
        if x.shape[0] < 2 * self.n_clusters:
            raise ValueError(f"need at least {2 * self.n_clusters} rows to "
                             f"fit k={self.n_clusters}; got {x.shape[0]}")
        # a fit supersedes any partial_fit stream in progress — otherwise a
        # later partial_fit/finalize would advance the abandoned stream and
        # silently overwrite this fit's results
        self._state = self._x_val = None
        k_val, k_init, k_run = jax.random.split(
            jax.random.PRNGKey(self.seed), 3)
        x_train, x_val = split_validation(x, self._val_rows(x.shape[0]),
                                          k_val)
        # split_validation permutes rows, so the head is a uniform sample.
        n_seed = min(x_train.shape[0], max(cfg.chunk_size, 4096))
        c0 = make_init(self.init)(k_init, x_train[:n_seed], self.n_clusters)
        dc = chunk_dataset(x_train, cfg.chunk_size, mesh=self.mesh,
                           data_axes=self.data_axes)
        if self.mesh is not None:
            fit_fn = make_distributed_kmeans_minibatch(
                self.mesh, cfg, self.data_axes, backend=self.backend)
            x_val, _ = shard_dataset(x_val, self.mesh, self.data_axes)
            res = fit_fn(dc.chunks, dc.weights, x_val, c0, k_run)
        elif self.metrics is not None:
            # epoch-segmented driver (host loop) so per-epoch scalars
            # have a host boundary to materialise at
            res = aa_kmeans_minibatch(dc.chunks, dc.weights, x_val, c0,
                                      cfg, backend=self.backend, key=k_run,
                                      metrics=self.metrics)
        else:
            run = jax.jit(lambda ch, w, xv, c, key: aa_kmeans_minibatch(
                ch, w, xv, c, cfg, backend=self.backend, key=key))
            res = run(dc.chunks, dc.weights, x_val, c0, k_run)
        self.centroids_ = res.centroids
        self.energy_ = float(res.energy)
        self.n_steps_ = int(res.n_steps)
        self.n_accepted_ = int(res.n_accepted)
        # new centroids: any previously built closure index is stale
        self.closure_routers_ = self.closure_candidates_ = None
        self.labels_ = self.predict(x) if self.compute_labels else None
        return self

    # -- streaming ---------------------------------------------------------

    def partial_fit(self, chunk) -> "MiniBatchAAKMeans":
        """One chunk step; device memory never holds more than this chunk
        plus the validation chunk.  Updates ``centroids_`` to the fresh
        running-stats iterate and ``energy_`` to the guard's pricing of
        the previous one (see the class docstring; ``finalize()`` makes
        them consistent)."""
        if self.mesh is not None:
            raise NotImplementedError(
                "partial_fit streams from one host; for mesh execution "
                "use fit() / make_distributed_kmeans_minibatch")
        x = jnp.asarray(chunk)
        cfg = self._config()
        bk = resolve_backend(self.backend)
        if self._state is None:
            if x.shape[0] < 2 * self.n_clusters:
                raise ValueError(
                    f"the first partial_fit chunk seeds the solver and "
                    f"must have >= {2 * self.n_clusters} rows; got "
                    f"{x.shape[0]}")
            # uniform carve (like fit's split_validation), not the raw
            # head: datasets are often stored sorted, and a val chunk
            # covering only the leading cluster would bias every guard
            # decision
            k_val, k_init = jax.random.split(jax.random.PRNGKey(self.seed))
            x, self._x_val = split_validation(
                x, self._val_rows(x.shape[0]), k_val)
            c0 = make_init(self.init)(k_init, x, self.n_clusters)
            self._state = minibatch_init(c0, cfg, bk)
        if self._step_fn is None:
            self._step_fn = jax.jit(minibatch_iteration,
                                    static_argnames=("cfg", "backend"))
        w = jnp.ones((x.shape[0],), jnp.float32)
        self._state, trace = self._step_fn(x, w, self._x_val, self._state,
                                           cfg=cfg, backend=bk)
        # device scalars, deliberately not float()/int()-converted: a host
        # sync per chunk would serialise the streaming loop (the next
        # chunk's H2D transfer could no longer overlap this step's
        # compute).  fit()/finalize() store Python floats.
        self.centroids_ = self._state.c_au
        self.energy_ = trace.e_val
        self.n_steps_ = self._state.t
        self.n_accepted_ = self._state.n_acc
        # centroids moved: a previously built closure index is stale
        self.closure_routers_ = self.closure_candidates_ = None
        if self.metrics is not None:
            # attaching a sink opts into the per-chunk host sync
            from repro.runtime.metrics import as_metrics
            as_metrics(self.metrics).log_scalars(
                int(self._state.t),
                {"e_val": float(trace.e_val),
                 "accepted": float(trace.accepted),
                 "n_accepted": float(self._state.n_acc),
                 "chunk_rows": float(x.shape[0])})
        return self

    def partial_fit_stream(self, chunks, prefetch: int = 2
                           ) -> "MiniBatchAAKMeans":
        """Consume an iterator of host chunks with overlapped
        host→device ingestion: chunk t+1's transfer is issued while
        chunk t's step computes (`repro.data.streaming.stream_chunks`
        over `repro.runtime.prefetch`).  Numerically identical to
        calling ``partial_fit`` per chunk — only transfer timing
        changes.  With a ``metrics`` sink attached, the final achieved
        ingest bytes/bandwidth are logged as ``ingest_*`` scalars."""
        from repro.data.streaming import stream_chunks
        from repro.runtime.metrics import as_metrics
        from repro.runtime.prefetch import IngestMeter
        meter = IngestMeter()
        for chunk in stream_chunks(iter(chunks), prefetch=prefetch,
                                   meter=meter):
            self.partial_fit(chunk)
        if self.metrics is not None and meter.chunks:
            as_metrics(self.metrics).log_scalars(int(self._state.t),
                                                 meter.scalars())
        return self

    def finalize(self) -> "MiniBatchAAKMeans":
        """Validation-guard pick between the accelerated candidate and the
        running-stats fallback after a partial_fit sequence (fit() applies
        it automatically)."""
        if self._state is None:
            raise ValueError("no streaming state; call partial_fit first")
        cfg = self._config()
        bk = resolve_backend(self.backend)
        c_fin, e_fin, _, _ = guard_pick(self._x_val, self._state, cfg, bk)
        self.centroids_ = c_fin
        self.energy_ = float(e_fin)
        self.closure_routers_ = self.closure_candidates_ = None
        return self

    # -- inference ---------------------------------------------------------

    def _assert_fitted(self):
        if self.centroids_ is None:
            raise NotFittedError(
                "this MiniBatchAAKMeans instance has no fitted centroids; "
                "call fit() or partial_fit() (or load() a fitted "
                "artifact) first")

    def _chunked_apply(self, x, kind, fn, out_dtype, out_cols=None,
                       chunk_size=None):
        """Jitted chunk-by-chunk apply into a host array — shared with
        AAKMeans via the module-level `_chunked_rows_apply`."""
        return _chunked_rows_apply(self, x, kind, fn, out_dtype,
                                   out_cols=out_cols, chunk_size=chunk_size)

    def build_serving_index(self, n_candidates: Optional[int] = None,
                            n_groups: Optional[int] = None,
                            seed: int = 0) -> "MiniBatchAAKMeans":
        """Attach a cluster-closure candidate index (`repro.serving`) to
        the current centroids.  For a ``partial_fit`` stream, call after
        ``finalize()`` — the index describes the centroids it was built
        from, and further chunks invalidate it."""
        return _build_serving_index(self, n_candidates=n_candidates,
                                    n_groups=n_groups, seed=seed)

    @property
    def closure_index_(self):
        """The fitted `ClosureIndex`, or None when none was built."""
        return _closure_index(self)

    # -- persistence ------------------------------------------------------

    def save(self, path):
        """Persist params + fitted state — INCLUDING an in-progress
        ``partial_fit`` stream (running S/W stats, Anderson window, guard
        energies, the carved validation chunk) — to one npz artifact.
        A loaded mid-stream model continues ``partial_fit`` exactly where
        this process stopped: the stream state is the whole trajectory
        state, so feeding the same remaining chunks reproduces the
        uninterrupted run bit for bit."""
        self._assert_fitted()
        arrays = {"centroids_": jnp.asarray(self.centroids_)}
        if self.labels_ is not None:
            arrays["labels_"] = jnp.asarray(self.labels_)
        if self.closure_routers_ is not None:
            arrays["closure_routers_"] = jnp.asarray(self.closure_routers_)
            arrays["closure_candidates_"] = \
                jnp.asarray(self.closure_candidates_)
        stream = {}
        if self._state is not None:
            stream = {"state": self._state,
                      "x_val": jnp.asarray(self._x_val)}
        # device scalars mid-stream (see partial_fit) -> host floats here
        scalars = {
            "energy_": None if self.energy_ is None else float(self.energy_),
            "n_steps_": None if self.n_steps_ is None else int(self.n_steps_),
            "n_accepted_": None if self.n_accepted_ is None
            else int(self.n_accepted_)}
        return _save_estimator(self, path, serialize.KIND_ESTIMATOR_MB,
                               arrays, stream, scalars)

    @classmethod
    def load(cls, path) -> "MiniBatchAAKMeans":
        """Rebuild from ``save``'s artifact; a saved mid-stream state is
        restored so the next ``partial_fit``/``finalize`` continues the
        stream."""
        model, meta, by_path = _load_estimator(
            cls, path, serialize.KIND_ESTIMATOR_MB)
        if meta["has_stream"]:
            like = minibatch_stream_like(
                by_path["stream/state/c"], model._config(), model.backend)
            state_paths, state_leaves, treedef = serialize.flatten_with_paths(
                like["state"])
            leaves = [jnp.asarray(np.asarray(by_path[f"stream/state/{p}"],
                                             dtype=l.dtype))
                      for p, l in zip(state_paths, state_leaves)]
            model._state = jax.tree_util.tree_unflatten(treedef, leaves)
            model._x_val = jnp.asarray(by_path["stream/x_val"])
        return model

    def predict(self, x, chunk_size: Optional[int] = None,
                approx: bool = False):
        """Nearest-centroid labels, computed chunk by chunk into a host
        array (bounded device footprint); mesh-fitted models assign under
        the fitted mesh instead.  ``approx=True`` uses the closure index
        when one is built, the exact full scan otherwise."""
        return _predict_rows(self, x, chunk_size=chunk_size, approx=approx)

    def transform(self, x, chunk_size: Optional[int] = None,
                  approx: bool = False):
        """Distances to each centroid (N, K), chunked like predict into
        a host array; ``approx=True`` prices only the candidate
        centroids (+inf elsewhere)."""
        return _transform_rows(self, x, chunk_size=chunk_size,
                               approx=approx)

    @property
    def inertia_(self) -> float:
        return self.energy_
