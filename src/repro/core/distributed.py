"""Distributed AA-KMeans: the paper's Algorithm 1 on a multi-pod TPU mesh.

Parallelisation layout (see DESIGN.md §Distribution):

  * Samples X (N, d) are sharded over the data axes — on the production
    meshes that is ("data",) for a single pod and ("pod", "data") across
    pods — so each of the 256/512 chips owns an N/devices slice.
  * Centroids C (K, d) are replicated: K*d is tiny (<= a few MB) next to X.
  * The assignment half of the step is embarrassingly parallel (local
    distances); the step's cluster stats are psum-reduced over the data
    axes — one (K*(d+1))-sized all-reduce per iteration, the *only*
    communication of the solver.
  * The energy check and the convergence test reduce one scalar each.
  * Anderson acceleration operates on the replicated centroids; every
    device solves the identical tiny (mbar x mbar) system, so no extra
    communication is introduced by the acceleration — the paper's overhead
    argument (Sec. 2.1) carries over unchanged to the distributed setting.

Distribution is the `distribute(backend, axes)` combinator over *any*
local backend (`repro.core.backends`): dense, blocked, the Pallas kernels,
the fused single-pass kernel, or Hamerly bounds all run under the same
shard_map wrapping — "fused Pallas + sharded mesh + mixed precision" is a
configuration, not a code path.  The *same* Algorithm-1 driver
(repro.core.kmeans.aa_kmeans) runs unchanged here.
"""

from __future__ import annotations

import functools
import os
from typing import Optional, Sequence, Union

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core import kmeans as KM
from repro.core import lloyd, serialize
from repro.core.backends import Backend, distribute
from repro.core.kmeans import (KMeansConfig, KMeansResult, aa_kmeans,
                               aa_kmeans_batched, aa_kmeans_minibatch,
                               resolve_backend, select_best)
from repro.core.lloyd import LloydOps
from repro.core.minibatch import MiniBatchConfig, MiniBatchResult
from repro.kernels import tiles


def shard_map(f, /, **kwargs):
    """`jax.shard_map` for the solver's programs.

    The varying-manual-axes checker stays on wherever the Pallas kernels
    compile (a TPU): their outputs declare how they vary
    (`kernels.tiles.out_struct`).  Off-TPU the kernels run in the Pallas
    interpreter, whose grid loop slices the sharded operands with
    replicated grid indices, a mix JAX's checker refuses; there the check
    is off.  The check changes no value, only what is verified."""
    kwargs.setdefault("check_vma", not tiles.interpret_default())
    return jax.shard_map(f, **kwargs)


def distributed_lloyd_ops(data_axes: Sequence[str],
                          block_n: int = 0) -> LloydOps:
    """DEPRECATED: LloydOps whose update/energy/convergence reduce over
    ``data_axes``.  Superseded by ``distribute(backend, axes)``; kept so
    legacy injection sites keep working.  Must be called *inside* shard_map
    with x as the local shard and c replicated."""
    axes = tuple(data_axes)

    def assign_fn(x, c):
        return lloyd.assign(x, c, block_n=block_n)

    def update_fn(x, labels, k, c_prev):
        sums, counts = lloyd.cluster_sums(x, labels, k)
        sums = jax.lax.psum(sums, axes)
        counts = jax.lax.psum(counts, axes)
        return lloyd.update_from_sums(sums, counts, c_prev)

    def energy_fn(x, c, labels):
        return jax.lax.psum(lloyd.energy(x, c, labels), axes)

    def all_equal_fn(a, b):
        neq = jnp.sum((a != b).astype(jnp.int32))
        return jax.lax.psum(neq, axes) == 0

    return LloydOps(assign_fn=assign_fn, update_fn=update_fn,
                    energy_fn=energy_fn, all_equal_fn=all_equal_fn,
                    reduce_scalar=lambda s: jax.lax.psum(s, axes))


def _mesh_shards(mesh, axes) -> int:
    n = 1
    for a in axes:
        n *= mesh.shape[a]
    return n


def _is_spec(s) -> bool:
    # PartitionSpec subclasses tuple, so tree_map would descend into it
    # without an explicit is_leaf.
    return isinstance(s, P)


def loop_state_specs(local_backend: Backend, cfg: KMeansConfig,
                     x_local, c0, axes: Sequence[str]):
    """PartitionSpec tree for a `_LoopState` under row sharding.

    Per-row leaves (labels, the previous assignment, and any per-row
    backend carry, recognised by a leading dim equal to the local row
    count) shard over ``axes``; centroids, energies, the Anderson window
    and the counters are replicated — exactly the layout the solver's
    shard_map maintains, reused here as both shard_map in/out specs and
    the device_put shardings of an elastic restore."""
    axes = tuple(axes)

    def shape_at(n_rows):
        return jax.eval_shape(
            lambda xx, cc: KM._init_state(xx, cc, cfg, local_backend),
            jax.ShapeDtypeStruct((n_rows, x_local.shape[1]), x_local.dtype),
            jax.ShapeDtypeStruct(c0.shape, c0.dtype))

    # Classify carry leaves by whether their leading dim tracks the row
    # count — probed by eval_shape at a second N, NOT by comparing shapes
    # against n_local (a centroid-shaped carry leaf, e.g. hamerly's
    # c_last (K, d), would collide whenever K == n_local and get sharded).
    like = shape_at(x_local.shape[0])
    probe = shape_at(x_local.shape[0] + 1)
    row, rep = P(axes), P()

    def carry_spec(leaf, probe_leaf):
        per_row = getattr(leaf, "ndim", 0) >= 1 and \
            leaf.shape[:1] != probe_leaf.shape[:1]
        return row if per_row else rep

    return KM._LoopState(
        c=rep, c_au=rep, p_prev=row, e_prev=rep, e_prev2=rep,
        aa=jax.tree_util.tree_map(lambda _: rep, like.aa),
        t=rep, n_acc=rep, converged=rep, labels=row, e_last=rep,
        carry=jax.tree_util.tree_map(carry_spec, like.carry, probe.carry))


def restore_distributed_loop_state(path, x, c0, cfg: KMeansConfig,
                                   local_backend: Backend,
                                   mesh: jax.sharding.Mesh,
                                   data_axes: Sequence[str] = ("data",)):
    """Elastic restore: place a solver snapshot onto ``mesh``.

    Snapshots store UNSHARDED host arrays (serialize.py), so restoring
    onto a different mesh or data-axes layout than the one the checkpoint
    was taken under is a `device_put` with the new shardings — the mesh
    geometry appears nowhere in the artifact.  ``x``/``c0`` supply the
    problem shapes (the like tree); the snapshot's backend identity is
    checked up to the '@axes' distribution suffix."""
    axes = tuple(data_axes)
    n_shards = _mesh_shards(mesh, axes)
    if x.shape[0] % n_shards:
        raise ValueError(
            f"N={x.shape[0]} must divide over the {n_shards} shards of "
            f"mesh axes {axes} to restore onto this mesh "
            f"(pad via shard_dataset first)")
    like = KM.loop_state_like(x, c0, cfg, local_backend)
    host_state, meta = serialize.restore(path, like,
                                         expect_kind=serialize.KIND_LOOP)
    KM._check_resume_meta(meta, cfg, local_backend, str(path))
    x_local = jax.ShapeDtypeStruct((x.shape[0] // n_shards, x.shape[1]),
                                   x.dtype)
    specs = loop_state_specs(local_backend, cfg, x_local, c0, axes)
    shardings = jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), specs, is_leaf=_is_spec)
    return jax.device_put(host_state, shardings), meta


def make_distributed_kmeans(mesh: jax.sharding.Mesh, cfg: KMeansConfig,
                            data_axes: Sequence[str] = ("data",),
                            block_n: int = 0,
                            backend: Union[str, Backend, None] = None,
                            checkpoint_every: int = 0,
                            checkpoint_dir=None):
    """Build the jitted multi-device solver.

    Returns ``fit(x, c0, resume_from=None) -> KMeansResult`` where x is
    (N, d) sharded (or shardable) over ``data_axes`` and c0 is (K, d)
    replicated.  N must be divisible by the product of the data-axis
    sizes.  ``backend`` picks the per-shard engine (any registry name or
    local Backend instance, wrapped here by ``distribute``); an already
    distribute()-wrapped backend is used as-is provided its axes match
    ``data_axes``.

    Persistence (DESIGN.md §Persistence): with ``checkpoint_every`` set
    (or ``resume_from`` passed to fit), the solve runs as a host loop over
    shard_map'd segments; snapshots gather to host via `jax.device_get`
    and are therefore mesh-free — a checkpoint taken here restores onto a
    DIFFERENT mesh or axes layout by building the new fit with that mesh
    and passing the same path (`restore_distributed_loop_state` reshards
    on device_put).  A resumed run is bit-identical to an uninterrupted
    run on the same mesh; across meshes the trajectory agrees up to psum
    reduction order.
    """
    axes = tuple(data_axes)
    ops = _resolve_distributed(backend, cfg, block_n, axes)
    x_spec = P(axes)           # shard rows over all data axes
    rep = P()

    @functools.partial(
        shard_map, mesh=mesh,
        in_specs=(x_spec, rep),
        out_specs=KMeansResult(centroids=rep, labels=x_spec, energy=rep,
                               n_iter=rep, n_accepted=rep, converged=rep))
    def _run(x_local, c0):
        return aa_kmeans(x_local, c0, cfg, backend=ops)

    x_sharding = NamedSharding(mesh, x_spec)
    rep_sharding = NamedSharding(mesh, rep)

    @jax.jit
    def _fit_whole(x, c0):
        x = jax.lax.with_sharding_constraint(x, x_sharding)
        c0 = jax.lax.with_sharding_constraint(c0, rep_sharding)
        return _run(x, c0)

    # -- segmented path (host loop over shard_map'd while_loop segments) --
    local = resolve_backend(backend, cfg=cfg, block_n=block_n) \
        if not isinstance(backend, Backend) or not backend.axes else None
    programs = {}   # (x shape/dtype, c0 shape/dtype) -> (init, seg, specs)

    def _segment_programs(x, c0):
        key = (x.shape, str(x.dtype), c0.shape, str(c0.dtype))
        built = programs.get(key)
        if built is not None:
            return built
        if local is None:
            raise ValueError(
                "checkpointed distributed solves need a local backend "
                "(registry name or un-distributed instance) so the state "
                "layout can be derived; got a pre-distributed backend")
        n_shards = _mesh_shards(mesh, axes)
        if x.shape[0] % n_shards:
            raise ValueError(f"N={x.shape[0]} must be divisible by the "
                             f"{n_shards} shards of {axes}")
        x_local = jax.ShapeDtypeStruct((x.shape[0] // n_shards, x.shape[1]),
                                       x.dtype)
        specs = loop_state_specs(local, cfg, x_local, c0, axes)
        init = jax.jit(shard_map(
            lambda xl, cc: KM._init_state(xl, cc, cfg, ops),
            mesh=mesh, in_specs=(x_spec, rep), out_specs=specs))
        seg = jax.jit(shard_map(
            lambda xl, st, end: KM._run_segment(xl, st, end, cfg=cfg,
                                                backend=ops),
            mesh=mesh, in_specs=(x_spec, specs, rep), out_specs=specs))
        built = programs[key] = (init, seg, specs)
        return built

    def _fit_segmented(x, c0, resume_from):
        KM._no_trace(x, "make_distributed_kmeans fit")
        every = int(checkpoint_every) if checkpoint_every else cfg.max_iter
        init, seg, _ = _segment_programs(x, c0)
        x = jax.device_put(x, x_sharding)
        c0 = jax.device_put(c0, rep_sharding)
        if resume_from is None:
            state = init(x, c0)
        elif isinstance(resume_from, (str, os.PathLike)):
            state, _ = restore_distributed_loop_state(
                resume_from, x, c0, cfg, local, mesh, axes)
        else:
            state = resume_from
        t = int(state.t)
        while not bool(state.converged) and t < cfg.max_iter:
            seg_end = min(t + every, cfg.max_iter)
            state = seg(x, state, jnp.asarray(seg_end, jnp.int32))
            t = int(state.t)
            if checkpoint_dir is not None:
                KM._snapshot(checkpoint_dir, state, serialize.KIND_LOOP,
                             t, cfg, ops,
                             extra={"mesh": dict(mesh.shape),
                                    "data_axes": list(axes)})
        return KM._result_from_state(state)

    def fit(x, c0, resume_from=None):
        if not checkpoint_every and checkpoint_dir is None \
                and resume_from is None:
            return _fit_whole(x, c0)
        return _fit_segmented(x, c0, resume_from)

    return fit


def _resolve_distributed(backend, cfg, block_n, axes):
    local = resolve_backend(backend, cfg=cfg, block_n=block_n)
    if local.axes:
        if local.axes != axes:
            raise ValueError(
                f"backend {local.name!r} is distributed over {local.axes} "
                f"but the solver reduces over {axes}")
        return local
    return distribute(local, axes)


def make_distributed_kmeans_batched(mesh: jax.sharding.Mesh,
                                    cfg: KMeansConfig,
                                    data_axes: Sequence[str] = ("data",),
                                    block_n: int = 0,
                                    backend: Union[str, Backend,
                                                   None] = None,
                                    pick_best: bool = False):
    """Batched multi-restart solver on a mesh: one program, R restarts.

    Returns ``fit(x, c0s) -> KMeansResult`` where x is (N, d) sharded over
    ``data_axes``, c0s is (R, K, d) replicated, and the result carries a
    leading R axis (labels: (R, N), rows sharded).  Inside shard_map the
    *batched* driver vmaps the distributed backend, so each loop body does
    one psum of (R, K, d+1)-sized stats — R restarts cost one collective,
    not R.  ``pick_best=True`` adds on-device best-of-R selection, making
    the whole multi-restart fit a single device program.
    """
    axes = tuple(data_axes)
    ops = _resolve_distributed(backend, cfg, block_n, axes)
    x_spec = P(axes)
    rep = P()
    lab_spec = P(None, axes)      # (R, N): restart axis replicated

    @functools.partial(
        shard_map, mesh=mesh,
        in_specs=(x_spec, rep),
        out_specs=KMeansResult(centroids=rep, labels=lab_spec, energy=rep,
                               n_iter=rep, n_accepted=rep, converged=rep))
    def _run(x_local, c0s):
        return aa_kmeans_batched(x_local, c0s, cfg, backend=ops)

    x_sharding = NamedSharding(mesh, x_spec)
    rep_sharding = NamedSharding(mesh, rep)

    @jax.jit
    def fit(x, c0s):
        x = jax.lax.with_sharding_constraint(x, x_sharding)
        c0s = jax.lax.with_sharding_constraint(c0s, rep_sharding)
        res = _run(x, c0s)
        return select_best(res) if pick_best else res

    return fit


def make_distributed_kmeans_minibatch(mesh: jax.sharding.Mesh,
                                      cfg: MiniBatchConfig,
                                      data_axes: Sequence[str] = ("data",),
                                      backend: Union[str, Backend,
                                                     None] = None):
    """Streaming mini-batch solver on a mesh: every host streams its shard.

    Returns ``fit(chunks, weights, x_val, c0, key=None) ->
    MiniBatchResult`` where ``chunks`` (n_chunks, B, d) and ``weights``
    (n_chunks, B) have their *row* dimension sharded over ``data_axes``
    (`repro.data.streaming.chunk_dataset(mesh=...)` lays them out) and
    ``x_val`` (V, d) is sharded likewise; centroids stay replicated.
    Inside shard_map each chunk step costs ONE (K,(d+1))-stat psum plus
    the guard's scalar energies — per-chunk communication is independent
    of both the chunk size and N (DESIGN.md §Streaming).  V and B must be
    divisible by the shard count of ``data_axes``.
    """
    axes = tuple(data_axes)
    ops = _resolve_distributed(backend, None, 0, axes)
    chunk_spec = P(None, axes)     # (n_chunks, B): chunk rows sharded
    val_spec = P(axes)
    rep = P()

    @functools.partial(
        shard_map, mesh=mesh,
        in_specs=(chunk_spec, chunk_spec, val_spec, rep, rep),
        out_specs=MiniBatchResult(centroids=rep, energy=rep, n_steps=rep,
                                  n_accepted=rep))
    def _run(chunks, weights, x_val, c0, key):
        return aa_kmeans_minibatch(chunks, weights, x_val, c0, cfg,
                                   backend=ops, key=key)

    chunk_sharding = NamedSharding(mesh, chunk_spec)
    val_sharding = NamedSharding(mesh, val_spec)
    rep_sharding = NamedSharding(mesh, rep)

    @jax.jit
    def _fit(chunks, weights, x_val, c0, key):
        chunks = jax.lax.with_sharding_constraint(chunks, chunk_sharding)
        weights = jax.lax.with_sharding_constraint(weights, chunk_sharding)
        x_val = jax.lax.with_sharding_constraint(x_val, val_sharding)
        c0 = jax.lax.with_sharding_constraint(c0, rep_sharding)
        return _run(chunks, weights, x_val, c0, key)

    def fit(chunks, weights, x_val, c0, key=None):
        if key is None:
            key = jax.random.PRNGKey(0)
        return _fit(chunks, weights, x_val, c0, key)

    return fit


def shard_dataset(x, mesh: jax.sharding.Mesh,
                  data_axes: Sequence[str] = ("data",)):
    """Place a host array on the mesh, padding N to the shard count.

    Padding rows replicate the final sample: duplicated points only bias the
    padded copy's cluster weighting, and callers that need exactness should
    pre-size N; the launcher reports when padding is applied."""
    import numpy as np
    n_shards = 1
    for a in data_axes:
        n_shards *= mesh.shape[a]
    n = x.shape[0]
    pad = (-n) % n_shards
    if pad:
        x = np.concatenate([x, np.repeat(x[-1:], pad, axis=0)], axis=0)
    sharding = NamedSharding(mesh, P(tuple(data_axes)))
    return jax.device_put(x, sharding), pad
