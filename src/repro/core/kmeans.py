"""Algorithm 1 of the paper: Anderson acceleration for the K-Means algorithm.

One loop body, `_complete_iteration`, completes an Algorithm-1 iteration
from one backend step; a rejected iterate leaves the iteration pending
and the next step, at the fallback C_AU^t, completes it (DESIGN.md
§Split-phase body).  The drivers over it:

  * ``aa_kmeans``        — one solve as jit-able ``lax.while_loop``
                           segments (`_run_segment`; runs unchanged under
                           shard_map distribution and with Pallas kernel
                           backends).
  * ``aa_kmeans_batched`` — R restarts / problems in one device program.
  * ``aa_kmeans_traced`` — Python-loop driver that records the per-iteration
                           statistics the paper reports (accepted / total
                           iterations, energy trace, m trace, wall time);
                           used by the Table 2 / Table 3 benchmarks.
  * ``aa_kmeans_minibatch`` — streaming epoch driver over chunked data
                           (state machine in repro.core.minibatch;
                           DESIGN.md §Streaming).

All consume a `Backend` (repro.core.backends) whose core op is the
single-pass ``step(x, c) -> StepResult``, so one *accepted* Algorithm-1
iteration costs exactly one pass over X (the paper's Sec-2.1 cost model):
the step's assignment doubles as the energy evaluation AND as the cluster
statistics from which the next fallback iterate C_AU follows without
re-reading X.  A *rejected* iteration takes one extra step — the fallback
must be re-assigned — and that second step's stats are reused the same way
(the legacy driver paid a third pass here).

Faithfulness notes (vs. the pseudo-code in the paper):

  * Convergence criterion: identical assignment between two consecutive
    iterations (line 4).  Because an accelerated iterate is only kept when it
    strictly decreases the energy, this is reached exactly when a fallback
    Lloyd iterate repeats the previous assignment — the classical criterion.
  * The energy check (lines 12-14) compares E(C^t) with E(C^{t-1}) and
    reverts to the *previous* un-accelerated iterate C_AU^t = G(C^{t-1})
    computed at line 16 of the previous iteration.
  * m-adjustment (lines 7-11) happens *before* the revert check, so a
    rejected iterate (negative decrease -> ratio < eps1) also shrinks m.
  * E^0 = +inf, and the ratio test only activates once E^{t-2} is finite.
"""

from __future__ import annotations

import dataclasses
import functools
import os
import time
from typing import Any, Callable, NamedTuple, Optional, Union

import jax
import jax.numpy as jnp

from repro.core import anderson, serialize
from repro.core.anderson import AAConfig, AAState
from repro.core.backends import Backend, get_backend
from repro.core.locality import maybe_reorder
from repro.core.minibatch import (MiniBatchConfig, MiniBatchResult,
                                  guard_pick, minibatch_init,
                                  minibatch_iteration, run_epoch)
from repro.runtime.metrics import as_metrics, should_stop as _metrics_stop
from repro.runtime.writer import CheckpointWriter, write_snapshot


@dataclasses.dataclass(frozen=True)
class KMeansConfig:
    k: int
    max_iter: int = 500
    aa: AAConfig = dataclasses.field(default_factory=AAConfig)
    accelerated: bool = True     # False -> plain Lloyd through the same driver
    block_n: int = 0             # row blocking for the assignment step


class KMeansResult(NamedTuple):
    centroids: jax.Array   # (K, d)
    labels: jax.Array      # (N,)
    energy: jax.Array      # scalar, final E
    n_iter: jax.Array      # total iterations (paper's "b" in a/b)
    n_accepted: jax.Array  # iterations whose accelerated iterate was kept
    converged: jax.Array   # bool


class _LoopState(NamedTuple):
    c: jax.Array           # C^t               (K, d)
    c_au: jax.Array        # C_AU^t = G(C^{t-1})  fallback iterate
    p_prev: jax.Array      # P^{t-1}           (N,)
    e_prev: jax.Array      # E^{t-1}
    e_prev2: jax.Array     # E^{t-2}
    aa: AAState
    t: jax.Array
    n_acc: jax.Array
    converged: jax.Array
    labels: jax.Array      # last P^t (valid on exit)
    e_last: jax.Array
    carry: Any             # opaque backend carry (e.g. Hamerly bounds)


BackendLike = Union[str, Backend, None]


def resolve_backend(backend: BackendLike,
                    cfg: Optional[KMeansConfig] = None,
                    block_n: int = 0) -> Backend:
    """Resolve the ``backend=`` the solver entry points accept — the single
    backend-selection policy for both the local and the distributed
    drivers.

    A Backend instance is used as-is; a registry name is looked up (with
    "dense"/"blocked" promoted to the row-blocked engine when a block size
    is configured — via ``block_n`` or ``cfg.block_n``); None is the dense
    engine, row-blocked under the same rule."""
    block_n = block_n or (cfg.block_n if cfg is not None else 0)
    if isinstance(backend, Backend):
        return backend
    if isinstance(backend, str):
        if backend in ("dense", "blocked") and block_n:
            return get_backend("blocked", block_n=block_n)
        return get_backend(backend)
    if backend is not None:
        raise TypeError(
            f"backend= expects a registry name or a Backend; got "
            f"{type(backend).__name__}")
    if block_n:
        return get_backend("blocked", block_n=block_n)
    return get_backend("dense")


def _init_state(x, c0, cfg: KMeansConfig, backend: Backend,
                w=None) -> _LoopState:
    k = cfg.k
    # Line 1:  C^1 = C_AU^1 = G(C^0);  F^0 = C^1 - C^0;  E^0 = +inf
    # — one step: the same pass yields E(C^0), P^0 and the stats of G(C^0).
    # ``w`` (N,) routes the init through the weighted slot — the hierarchy
    # driver's padded rows (w = 0) must vanish from the seed stats too.
    carry = backend.init_carry(x, c0, k)
    if w is None:
        res0, carry = backend.step(x, c0, k, carry)
    else:
        res0, carry = backend.minibatch_step(x, c0, k, w, carry)
    c1 = backend.centroids_from_step(x, res0, k, c0)
    aa_state = anderson.aa_init(k * x.shape[1], cfg.aa, x.dtype)
    aa_state = anderson.aa_seed(aa_state, (c1 - c0).reshape(-1),
                                c1.reshape(-1))
    inf = jnp.array(jnp.inf, res0.energy.dtype)
    return _LoopState(
        c=c1, c_au=c1, p_prev=res0.labels,
        e_prev=inf, e_prev2=inf,
        aa=aa_state,
        t=jnp.array(0, jnp.int32), n_acc=jnp.array(0, jnp.int32),
        converged=jnp.array(False),
        labels=res0.labels,
        # E(C^0) as the placeholder "last energy" — overwritten by the first
        # loop body; already reduced across shards by the backend.
        e_last=res0.energy,
        carry=carry)


class _BatchedState(NamedTuple):
    """A trip's loop carry: the single solve's (`_trip`) and, with a
    leading R axis on every leaf, the batched solve's."""
    inner: _LoopState
    # True while an Algorithm-1 iteration is half-done: the accelerated
    # iterate was rejected and the fallback step has not run yet.
    pending: jax.Array


def _tree_where(flag, on_true, on_false):
    """Leaf-wise select on a scalar flag (broadcasts over any leaf shape)."""
    return jax.tree_util.tree_map(
        lambda a, b: jnp.where(flag, a, b), on_true, on_false)


def _complete_iteration(x, res, carry, bst: _BatchedState,
                        cfg: KMeansConfig, backend: Backend,
                        w=None) -> _BatchedState:
    """Everything in Algorithm 1's loop body *after* the backend step, on
    one problem's (unbatched) state: the single solve's `_trip` calls it
    as is, the batched `_batched_body` vmaps it."""
    st, pending = bst.inner, bst.pending
    k = cfg.k
    c_eval = jnp.where(pending, st.c_au, st.c)

    # Line 4 (phase A only): the revert step never checks convergence.
    # Under per-problem weights the check is MASKED: a padding row (w = 0)
    # never holds up convergence — its label chases centroids it does not
    # influence, so it may flip forever on ties while the real rows are
    # long settled (DESIGN.md §Hierarchy).
    if w is None:
        lab_now, lab_prev = res.labels, st.p_prev
    else:
        live = w > 0
        lab_now = jnp.where(live, res.labels, 0)
        lab_prev = jnp.where(live, st.p_prev, 0)
    conv_now = jnp.logical_and(~pending,
                               backend.all_equal(lab_now, lab_prev))
    # Lines 7-11 (phase A only): m adjusts before the revert decision.
    aa_adj = anderson.adjust_m(st.aa, res.energy, st.e_prev, st.e_prev2,
                               cfg.aa)
    accepted = jnp.logical_and(~pending, res.energy < st.e_prev)
    complete = jnp.logical_or(pending, accepted)

    # Iteration completion (phase-A-accepted or phase-B): lines 16-19 from
    # the step's stats.  In phase B the window was already adjusted when
    # the iterate was rejected, so push into the stored state.
    aa_for_push = _tree_where(pending, st.aa, aa_adj)
    c_au_next = backend.centroids_from_step(x, res, k, c_eval)
    g_flat = c_au_next.reshape(-1)
    f_flat = g_flat - c_eval.reshape(-1)
    if cfg.accelerated:
        aa_pushed, c_next_flat, _, _ = anderson.aa_push_and_solve(
            aa_for_push, f_flat, g_flat, cfg.aa)
        c_next = c_next_flat.reshape(st.c.shape)
    else:
        aa_pushed, c_next = aa_for_push, c_au_next

    st_complete = _LoopState(
        c=c_next, c_au=c_au_next, p_prev=res.labels,
        e_prev=res.energy, e_prev2=st.e_prev, aa=aa_pushed,
        t=st.t + 1,
        n_acc=st.n_acc + accepted.astype(jnp.int32),
        converged=jnp.array(False), labels=res.labels, e_last=res.energy,
        carry=carry)
    st_pending = st._replace(aa=aa_adj, carry=carry)
    st_conv = st._replace(converged=jnp.array(True), labels=res.labels,
                          e_last=res.energy, t=st.t + 1, carry=carry)

    new_inner = _tree_where(conv_now, st_conv,
                            _tree_where(complete, st_complete, st_pending))
    new_pending = jnp.logical_and(~conv_now, ~complete)
    return _BatchedState(new_inner, new_pending)


def _trip(x, bst: _BatchedState, cfg: KMeansConfig,
          backend: Backend) -> _BatchedState:
    """One trip of a single solve: one backend step — at C^t, or at the
    fallback C_AU^t while a rejected iteration is pending — and the
    completion it allows.  An accepted iteration costs one trip, a
    rejected one two; ``t`` advances only when an iteration completes."""
    st = bst.inner
    res, carry = backend.step(x, jnp.where(bst.pending, st.c_au, st.c),
                              cfg.k, st.carry)
    return _complete_iteration(x, res, carry, bst, cfg, backend)


# ---------------------------------------------------------------------------
# Segmented execution & persistence (DESIGN.md §Persistence)
# ---------------------------------------------------------------------------
#
# A checkpointable solve runs as a HOST loop over jit'd `lax.while_loop`
# segments: each segment runs the identical `_trip` until a traced
# boundary (`state.t < seg_end`).  A rejected iteration does not advance
# ``t`` while it is pending, so every segment ends on a completed
# iteration, pausing never enters the jit trace, and the sequence of
# trips — hence every bit of the trajectory — is exactly that of the
# uninterrupted run.  Snapshots are the raw loop-state pytree via
# `repro.core.serialize`; the "like" trees below derive the expected
# structure from the init functions themselves (eval_shape), so the
# snapshot schema cannot drift from the code.  tests/test_persistence.py
# proves resume parity.

@functools.partial(jax.jit, static_argnames=("cfg", "backend"))
def _run_segment(x, state: _LoopState, seg_end, cfg: KMeansConfig,
                 backend: Backend) -> _LoopState:
    """Run Algorithm-1 iterations until convergence or t == seg_end.
    ``seg_end`` is a traced scalar, so every segment of a solve reuses one
    compiled program."""
    def cond(bst: _BatchedState):
        return jnp.logical_and(~bst.inner.converged, bst.inner.t < seg_end)

    def body(bst: _BatchedState):
        return _trip(x, bst, cfg, backend)

    return jax.lax.while_loop(
        cond, body, _BatchedState(state, jnp.array(False))).inner


_init_state_jit = jax.jit(_init_state, static_argnames=("cfg", "backend"))
_trip_jit = jax.jit(_trip, static_argnames=("cfg", "backend"))


def loop_state_like(x, c0, cfg: KMeansConfig, backend: BackendLike = None):
    """ShapeDtypeStruct tree of `_LoopState` for this problem/backend —
    the restore target for `serialize.restore` (no compute, no copies)."""
    bk = resolve_backend(backend, cfg=cfg)
    return jax.eval_shape(lambda xx, cc: _init_state(xx, cc, cfg, bk),
                          jax.ShapeDtypeStruct(x.shape, x.dtype),
                          jax.ShapeDtypeStruct(c0.shape, c0.dtype))


def _backend_base(name: str) -> str:
    """Mesh-layout-free backend identity: `distribute()` suffixes the name
    with '@axes', which must not block an elastic (re-mesh) restore."""
    return name.split("@")[0]


def _check_resume_meta(meta: dict, cfg, backend: Backend, what: str):
    if meta.get("k") is not None and meta["k"] != cfg.k:
        raise ValueError(f"{what}: snapshot was taken at k={meta['k']}, "
                         f"resuming with k={cfg.k}")
    snap_bk = meta.get("backend")
    if snap_bk and _backend_base(snap_bk) != _backend_base(backend.name):
        raise ValueError(
            f"{what}: snapshot was taken on backend {snap_bk!r} but the "
            f"resume uses {backend.name!r}; the per-backend carry (and on "
            f"some backends the reduction order) differs, so the resumed "
            f"trajectory would not match — resume on the same engine")


def _resolve_resume(resume_from, like, kind: str, cfg, backend: Backend):
    """Accept a state pytree (used as-is) or an artifact path (restored
    into ``like``); returns host/device state ready to enter a segment."""
    if resume_from is None:
        return None
    if isinstance(resume_from, (str, os.PathLike)):
        state, meta = serialize.restore(resume_from, like, expect_kind=kind)
        _check_resume_meta(meta, cfg, backend, str(resume_from))
        return state
    return resume_from


def _snapshot_meta(step: int, cfg, backend: Backend,
                   extra: Optional[dict] = None) -> dict:
    return {"t": step, "k": cfg.k, "backend": backend.name,
            **(extra or {})}


def _snapshot(checkpoint_dir, state, kind: str, step: int, cfg,
              backend: Backend, extra: Optional[dict] = None,
              keep_last_n: int = 0, keep_every_m: int = 0):
    """Synchronous boundary snapshot: atomic artifact + manifest +
    retention (`repro.runtime.writer.write_snapshot`).  The segmented
    drivers route the same call through a `CheckpointWriter` thread; the
    distributed driver and the sync-write benchmark arm call this
    directly."""
    return write_snapshot(checkpoint_dir, state, kind=kind, step=step,
                          extra=_snapshot_meta(step, cfg, backend, extra),
                          keep_last_n=keep_last_n, keep_every_m=keep_every_m)


def _make_writer(checkpoint_dir, kind: str, keep_last_n: int,
                 keep_every_m: int, metrics, sync_writes: bool):
    if checkpoint_dir is None or sync_writes:
        return None
    return CheckpointWriter(checkpoint_dir, kind=kind,
                            keep_last_n=keep_last_n,
                            keep_every_m=keep_every_m, metrics=metrics)


def _bound_scalars(carry) -> dict:
    from repro.core.backends.bounds import extract_stats
    bs = extract_stats(carry)
    if bs is None:
        return {}
    return {"eliminated_frac": float(bs.eliminated_frac),
            "skipped_frac": float(bs.skipped_frac)}


def _no_trace(x, who: str):
    if isinstance(x, jax.core.Tracer):
        raise ValueError(
            f"{who} with checkpoint_every/resume_from runs a host-side "
            f"segment loop and cannot itself be jit-traced; jit only the "
            f"plain (checkpoint-free) call, or let the driver's internal "
            f"per-segment jit do the compiling")


def _result_from_state(state: _LoopState) -> KMeansResult:
    # Iteration count convention of the paper's "a/b": b counts the initial
    # C^1 = G(C^0) plus every fully-executed loop body; the body that merely
    # *detects* convergence (line 4-5 early return) is not counted.  A
    # capped state must have been through `_close` (or `_close_batched`).
    n_iter = state.t + jnp.where(state.converged, 0, 1)
    return KMeansResult(state.c, state.labels, state.e_last,
                        n_iter, state.n_acc, state.converged)


def _close(x, state: _LoopState, cfg: KMeansConfig,
           backend: Backend) -> _LoopState:
    """End a solve that stopped without converging (``max_iter``, or an
    early-stop hook) on a point it has evaluated.

    Such a loop exits holding C^{t+1}, the next Anderson (or Lloyd)
    iterate, beside the labels and energy of the point before it.  The
    closing evaluation does what the next loop body would begin with:
    assign C^{t+1}; if the energy guard would reject it, assign the
    fallback C_AU^{t+1} instead.  The state then carries that point with
    its own labels and energy.  It is not an iteration: ``t`` and the
    counters stay.  A converged state passes through untouched, and
    under ``lax.cond`` pays no step."""
    k = cfg.k

    def evaluate(st: _LoopState) -> _LoopState:
        with jax.named_scope("repro.fit.final"):
            res, carry = backend.step(x, st.c, k, st.carry)

            def keep(carry):
                return st.c, res.labels, res.energy

            def revert(carry):
                res_f, _ = backend.step(x, st.c_au, k, carry)
                return st.c_au, res_f.labels, res_f.energy

            c, labels, e = jax.lax.cond(res.energy < st.e_prev, keep,
                                        revert, carry)
        return st._replace(c=c, labels=labels, e_last=e)

    return jax.lax.cond(state.converged, lambda st: st, evaluate, state)


def _close_batched(x, state: _LoopState, cfg: KMeansConfig,
                   backend: Backend, x_batched: bool,
                   w=None) -> _LoopState:
    """`_close` for a batched state (leading R axis): one batched step at
    every problem's C^{t+1}, one more at C_AU^{t+1} only when some open
    problem's guard rejects, each problem keeping what its own guard
    picks.  Converged problems keep their state bit for bit, and when
    all have converged neither step runs."""
    k = cfg.k
    open_ = ~state.converged                                    # (R,)

    def evaluate(st: _LoopState) -> _LoopState:
        with jax.named_scope("repro.fit.final"):
            res, carry = backend.batched_step(x, st.c, k, st.carry,
                                              x_batched=x_batched, w=w)
            keep = res.energy < st.e_prev

            def revert(carry):
                res_f, _ = backend.batched_step(x, st.c_au, k, carry,
                                                x_batched=x_batched, w=w)
                return res_f.labels, res_f.energy

            lab_f, e_f = jax.lax.cond(
                jnp.any(open_ & ~keep), revert,
                lambda carry: (res.labels, res.energy), carry)
            closed = st._replace(
                c=_tree_select_rows(keep, st.c, st.c_au),
                labels=_tree_select_rows(keep, res.labels, lab_f),
                e_last=jnp.where(keep, res.energy, e_f))
        return _tree_select_rows(open_, closed, st)

    return jax.lax.cond(jnp.any(open_), evaluate, lambda st: st, state)


_close_jit = jax.jit(_close, static_argnames=("cfg", "backend"))
_close_batched_jit = jax.jit(_close_batched,
                             static_argnames=("cfg", "backend", "x_batched"))


def _aa_kmeans_segmented(x, c0, cfg: KMeansConfig, bk: Backend,
                         checkpoint_every: int, checkpoint_dir,
                         resume_from, checkpoint_cb,
                         keep_last_n: int = 0, keep_every_m: int = 0,
                         metrics=None,
                         sync_writes: bool = False) -> KMeansResult:
    _no_trace(x, "aa_kmeans")
    mx = as_metrics(metrics)
    every = int(checkpoint_every) if checkpoint_every else cfg.max_iter
    like = loop_state_like(x, c0, cfg, bk)
    state = _resolve_resume(resume_from, like, serialize.KIND_LOOP, cfg, bk)
    if state is None:
        state = _init_state_jit(x, c0, cfg, bk)
    t = int(state.t)
    writer = _make_writer(checkpoint_dir, serialize.KIND_LOOP, keep_last_n,
                          keep_every_m, mx, sync_writes)
    try:
        while not bool(state.converged) and t < cfg.max_iter:
            seg_end = min(t + every, cfg.max_iter)
            t0 = time.perf_counter()
            state = _run_segment(x, state, jnp.asarray(seg_end, jnp.int32),
                                 cfg, bk)
            t = int(state.t)   # host sync: the segment is fully computed
            seg_s = time.perf_counter() - t0
            if writer is not None:
                # the device_get here IS the snapshot point — taken
                # synchronously at the boundary, so the artifact content
                # is exactly the sync path's; only the write is deferred
                writer.submit(jax.device_get(state), t,
                              _snapshot_meta(t, cfg, bk))
            elif checkpoint_dir is not None:
                _snapshot(checkpoint_dir, state, serialize.KIND_LOOP, t,
                          cfg, bk, keep_last_n=keep_last_n,
                          keep_every_m=keep_every_m)
            if checkpoint_cb is not None:
                checkpoint_cb(state, t)
            mx.log_scalars(t, {
                "energy": float(state.e_last),
                "n_accepted": float(int(state.n_acc)),
                "converged": float(bool(state.converged)),
                "segment_s": seg_s, **_bound_scalars(state.carry)})
            if _metrics_stop(mx):
                break   # EarlyStopHook: improvement per segment stalled
    finally:
        if writer is not None:
            writer.close()   # drain + join; a failed write fails the run
    return _result_from_state(_close_jit(x, state, cfg, bk))


def aa_kmeans(x: jax.Array, c0: jax.Array, cfg: KMeansConfig,
              backend: BackendLike = None, *,
              checkpoint_every: int = 0,
              checkpoint_dir=None,
              resume_from=None,
              checkpoint_cb: Optional[Callable] = None,
              keep_last_n: int = 0,
              keep_every_m: int = 0,
              metrics=None,
              sync_writes: bool = False,
              reorder=False) -> KMeansResult:
    """Jit-able Algorithm 1.  ``cfg`` is static; x (N,d); c0 (K,d).

    A solve that stops at ``max_iter`` unconverged returns a point it
    evaluated, with that point's labels and energy (`_close`).

    ``backend`` selects the engine ("dense" | "blocked" | "pallas" |
    "fused" | "hamerly" | "elkan" | "yinyang" | "fused_bounds", a Backend
    instance, or a distribute()-wrapped one).  The bound family (the last
    four) threads triangle-inequality bounds through the loop carry —
    valid across accepted AA jumps and reverts (DESIGN.md §Bounds).

    Persistence (DESIGN.md §Persistence): ``checkpoint_every=s`` runs the
    solve as a host loop over jit'd s-iteration segments, snapshotting the
    loop state after each segment — to ``checkpoint_dir`` (one
    ``it_<t>.npz`` artifact per boundary, `repro.core.serialize` format)
    and/or a ``checkpoint_cb(state, t)`` callback.  ``resume_from`` (a
    snapshot path or a restored ``_LoopState``) continues a previous solve;
    the resumed trajectory is bit-identical to the uninterrupted one
    because segment boundaries only partition the identical sequence of
    loop bodies.  The checkpoint parameters require host execution — do
    not wrap the call itself in jit (each segment is jitted internally).

    Runtime (DESIGN.md §Runtime): artifact writes run on a background
    `CheckpointWriter` thread (the state snapshot itself is taken
    synchronously at the boundary, so resume stays bit-identical; set
    ``sync_writes=True`` to force in-line writes), with
    ``keep_last_n``/``keep_every_m`` retention and a ``manifest.json``
    per run directory.  ``metrics`` is any ``log_scalars(step, dict)``
    sink (`repro.runtime.metrics`); each segment boundary emits energy,
    accept counts, bound-skip fractions and wall time, and the writer
    emits per-snapshot write latency.

    Locality (DESIGN.md §Locality): ``reorder=True`` (or a
    `repro.core.locality.ReorderConfig`) wraps a bound backend in the
    churn-triggered row-reordering engine — the kernel sees cluster-sorted
    rows once assignments stabilise, while emitted labels/energies stay
    bit-identical to the unpermuted solve.  The permutation rides the
    backend carry, so checkpoint/resume persists it automatically."""
    bk = maybe_reorder(resolve_backend(backend, cfg=cfg), reorder)
    if checkpoint_every or checkpoint_dir is not None \
            or resume_from is not None or checkpoint_cb is not None \
            or metrics is not None:
        return _aa_kmeans_segmented(x, c0, cfg, bk, checkpoint_every,
                                    checkpoint_dir, resume_from,
                                    checkpoint_cb, keep_last_n,
                                    keep_every_m, metrics, sync_writes)

    state = _run_segment(x, _init_state(x, c0, cfg, bk),
                         jnp.asarray(cfg.max_iter, jnp.int32), cfg, bk)
    return _result_from_state(_close(x, state, cfg, bk))


# ---------------------------------------------------------------------------
# Batched driver (many restarts / problems in ONE device program)
# ---------------------------------------------------------------------------

def _tree_select_rows(mask, on_true, on_false):
    """Leaf-wise per-row select: mask (R,) against leaves of shape (R, ...)."""
    return jax.tree_util.tree_map(
        lambda a, b: jnp.where(mask.reshape(mask.shape + (1,) * (a.ndim - 1)),
                               a, b), on_true, on_false)


def _is_active(state: _LoopState, max_iter: int):
    return jnp.logical_and(~state.converged, state.t < max_iter)


def _batched_body(x, bst: _BatchedState, cfg: KMeansConfig,
                  backend: Backend, x_batched: bool,
                  w=None) -> _BatchedState:
    """One *backend step* of Algorithm 1 for the whole batch: the
    single solve's `_trip` with the step batched and the completion
    vmapped.

    Under vmap, ``lax.cond`` lowers to a select that executes both
    branches, so a body hiding the revert step in a cond would cost two
    passes over X per loop body for *every* restart, accepted or not.
    This body instead performs exactly one step and carries an explicit
    per-restart ``pending`` flag:

      phase A (pending=False): step at C^t.  Converged -> finish.
        Accepted (E^t < E^{t-1}) -> the same step's stats complete the
        iteration.  Rejected -> record the adjusted window and flip to
        pending; the iteration completes next body.
      phase B (pending=True): step at C_AU^t (the fallback), completing
        the rejected iteration.

    The sequence of backend steps, window pushes and m-adjustments per
    restart is the single solve's, so trajectories match step-for-step.
    The step itself runs through ``backend.batched_step`` — natively
    batched when the backend provides it (one shared-X einsum + matmul
    stats for dense), vmapped otherwise; only the cheap completion logic
    is always vmapped.
    """
    st = bst.inner
    c_eval = jnp.where(bst.pending[:, None, None], st.c_au, st.c)
    res, carry = backend.batched_step(x, c_eval, cfg.k, st.carry,
                                      x_batched=x_batched, w=w)
    if w is None:
        return jax.vmap(
            lambda xx, r, cr, ob: _complete_iteration(
                xx, r, cr, ob, cfg, backend),
            in_axes=(0 if x_batched else None, 0, 0, 0))(x, res, carry, bst)
    return jax.vmap(
        lambda xx, r, cr, ob, ww: _complete_iteration(
            xx, r, cr, ob, cfg, backend, w=ww),
        in_axes=(0 if x_batched else None, 0, 0, 0, 0))(x, res, carry, bst,
                                                        w)


@functools.partial(jax.jit, static_argnames=("cfg", "backend", "x_batched"))
def _run_batched_segment(x, bst: _BatchedState, max_trips, cfg: KMeansConfig,
                         backend: Backend, x_batched: bool,
                         w=None) -> _BatchedState:
    """Run up to ``max_trips`` batched loop trips (one backend step each).

    Restarts' iteration counters drift apart (a rejected iteration spans
    two trips), so segments are bounded by the TRIP count, which is the
    unit the shared while_loop actually executes: pausing at a trip
    boundary partitions the uninterrupted trip sequence exactly, which is
    what makes a resumed batched solve bit-identical."""
    def cond(carry):
        b, i = carry
        return jnp.logical_and(jnp.any(_is_active(b.inner, cfg.max_iter)),
                               i < max_trips)

    def body(carry):
        b, i = carry
        new_b = _batched_body(x, b, cfg, backend, x_batched=x_batched, w=w)
        new_b = _tree_select_rows(_is_active(b.inner, cfg.max_iter),
                                  new_b, b)
        return new_b, i + 1

    bst, _ = jax.lax.while_loop(cond, body,
                                (bst, jnp.array(0, jnp.int32)))
    return bst


def batched_state_like(x, c0s, cfg: KMeansConfig,
                       backend: BackendLike = None):
    """ShapeDtypeStruct tree of `_BatchedState` for this problem — the
    restore target for a batched-solver snapshot."""
    bk = resolve_backend(backend, cfg=cfg)
    x_axis = 0 if x.ndim == 3 else None

    def build(xx, cc):
        inner = jax.vmap(lambda xr, cr: _init_state(xr, cr, cfg, bk),
                         in_axes=(x_axis, 0))(xx, cc)
        return _BatchedState(inner, jnp.zeros((cc.shape[0],), bool))

    return jax.eval_shape(build, jax.ShapeDtypeStruct(x.shape, x.dtype),
                          jax.ShapeDtypeStruct(c0s.shape, c0s.dtype))


def aa_kmeans_batched(x: jax.Array, c0s: jax.Array, cfg: KMeansConfig,
                      backend: BackendLike = None, *,
                      checkpoint_every: int = 0,
                      checkpoint_dir=None,
                      resume_from=None,
                      checkpoint_cb: Optional[Callable] = None,
                      keep_last_n: int = 0,
                      keep_every_m: int = 0,
                      metrics=None,
                      sync_writes: bool = False,
                      reorder=False,
                      weights=None) -> KMeansResult:
    """Batched Algorithm 1: R independent solves in one device program.

    ``c0s`` is (R, K, d) — one seed set per restart/problem.  ``x`` is
    either (N, d), shared by every restart (the multi-restart case), or
    (R, N, d), one dataset per problem (the grid / per-layer-codebook
    case; all problems must share N, d and K).

    ``weights`` (R, N) >= 0, when given, scales each row's contribution
    to the per-problem cluster stats and energy — the hierarchy engine
    passes its padding mask here (w = 0 rows vanish exactly from stats,
    energy AND the convergence check; DESIGN.md §Hierarchy).  Labels are
    still emitted for every row, weighted or not.

    The loop body is ``_batched_body``: one (natively batched or vmapped)
    backend step plus the vmapped completion logic — every backend's
    step, its carry, and the Anderson window batch cleanly because all
    loop state lives in fixed-shape arrays (DESIGN.md §Batching).
    Per-restart convergence is handled by *masking*, not by stopping: the
    shared ``lax.while_loop`` runs until every restart is done, and a
    restart that has converged (or hit max_iter) keeps its frozen state
    while the others continue — its trajectory is therefore identical to
    what the sequential driver would have produced.

    Returns a ``KMeansResult`` whose every leaf carries a leading R axis.
    Use ``select_best`` for on-device best-of-R selection.

    ``checkpoint_every=s`` segments the solve every s loop TRIPS (one
    batched backend step each; a rejected iteration spans two trips) and
    snapshots the whole per-restart state — see ``aa_kmeans`` for the
    checkpoint/resume contract and the runtime parameters
    (``keep_last_n``/``keep_every_m``/``metrics``/``sync_writes``), which
    carry over verbatim.  ``reorder=`` wraps a bound backend in the
    locality engine with per-restart permutations (DESIGN.md §Locality;
    each restart's rows sort by its own labels, gathered as (R, N, d)).
    """
    if c0s.ndim != 3:
        raise ValueError(f"c0s must be (R, K, d); got shape {c0s.shape}")
    if x.ndim not in (2, 3):
        raise ValueError(f"x must be (N, d) or (R, N, d); got {x.shape}")
    if x.ndim == 3 and x.shape[0] != c0s.shape[0]:
        raise ValueError(
            f"batched x has {x.shape[0]} problems but c0s has "
            f"{c0s.shape[0]} seed sets")
    if weights is not None and weights.shape != \
            (c0s.shape[0], x.shape[-2]):
        raise ValueError(
            f"weights must be (R, N) = ({c0s.shape[0]}, {x.shape[-2]}); "
            f"got {weights.shape}")
    bk = maybe_reorder(resolve_backend(backend, cfg=cfg), reorder)
    x_axis = 0 if x.ndim == 3 else None

    if checkpoint_every or checkpoint_dir is not None \
            or resume_from is not None or checkpoint_cb is not None \
            or metrics is not None:
        return _aa_kmeans_batched_segmented(
            x, c0s, cfg, bk, x_axis, checkpoint_every, checkpoint_dir,
            resume_from, checkpoint_cb, keep_last_n, keep_every_m,
            metrics, sync_writes, weights=weights)

    return aa_kmeans_batched_close(
        x, aa_kmeans_batched_loop(x, c0s, cfg, bk, weights), cfg, bk,
        weights)


def aa_kmeans_batched_loop(x: jax.Array, c0s: jax.Array, cfg: KMeansConfig,
                           backend: BackendLike = None,
                           weights=None) -> _LoopState:
    """The first half of `aa_kmeans_batched` (no checkpointing): the
    batched solve's loop, returning the R-batched state it exits with.
    `aa_kmeans_batched_close` turns that state into the result.  Jitted
    as two programs, the loop compiles without the closing evaluation's
    buffers beside it."""
    bk = resolve_backend(backend, cfg=cfg)
    x_axis = 0 if x.ndim == 3 else None
    states = _init_batched_state(x, c0s, cfg, bk, x_axis, w=weights)

    def active(bst: _BatchedState):
        # A pending restart never has t == max_iter (completion is what
        # advances t), so the sequential loop guard carries over as-is.
        return _is_active(bst.inner, cfg.max_iter)

    def cond(bst):
        return jnp.any(active(bst))

    def body(bst):
        new_bst = _batched_body(x, bst, cfg, bk, x_batched=(x_axis == 0),
                                w=weights)
        # Masked iteration: a finished restart is a no-op — its state is
        # frozen row-wise, so the shared loop cannot perturb it.
        return _tree_select_rows(active(bst), new_bst, bst)

    return jax.lax.while_loop(cond, body, states).inner


def aa_kmeans_batched_close(x: jax.Array, states: _LoopState,
                            cfg: KMeansConfig, backend: BackendLike = None,
                            weights=None) -> KMeansResult:
    """The second half of `aa_kmeans_batched`: the closing evaluation of
    the problems that stopped at ``max_iter`` (`_close_batched`), and the
    result, every leaf with its leading R axis."""
    bk = resolve_backend(backend, cfg=cfg)
    return _result_from_state(_close_batched(
        x, states, cfg, bk, x_batched=(x.ndim == 3), w=weights))


@functools.partial(jax.jit, static_argnames=("cfg", "backend", "x_axis"))
def _init_batched_state(x, c0s, cfg: KMeansConfig, backend: Backend,
                        x_axis, w=None) -> _BatchedState:
    if w is None:
        inner0 = jax.vmap(lambda xx, cc: _init_state(xx, cc, cfg, backend),
                          in_axes=(x_axis, 0))(x, c0s)
    else:
        inner0 = jax.vmap(
            lambda xx, cc, ww: _init_state(xx, cc, cfg, backend, w=ww),
            in_axes=(x_axis, 0, 0))(x, c0s, w)
    return _BatchedState(inner0, jnp.zeros((c0s.shape[0],), bool))


def _aa_kmeans_batched_segmented(x, c0s, cfg: KMeansConfig, bk: Backend,
                                 x_axis, checkpoint_every, checkpoint_dir,
                                 resume_from, checkpoint_cb,
                                 keep_last_n: int = 0, keep_every_m: int = 0,
                                 metrics=None,
                                 sync_writes: bool = False,
                                 weights=None) -> KMeansResult:
    _no_trace(x, "aa_kmeans_batched")
    mx = as_metrics(metrics)
    # Worst case every Algorithm-1 iteration rejects, costing two trips.
    every = int(checkpoint_every) if checkpoint_every \
        else 2 * cfg.max_iter + 1
    like = batched_state_like(x, c0s, cfg, bk)
    trips = 0
    if isinstance(resume_from, (str, os.PathLike)):
        bst, meta = serialize.restore(resume_from, like,
                                      expect_kind=serialize.KIND_BATCHED)
        _check_resume_meta(meta, cfg, bk, str(resume_from))
        trips = int(meta.get("t", 0))
    elif resume_from is not None:
        bst = resume_from
        trips = int(jnp.max(resume_from.inner.t))   # snapshot naming only
    else:
        bst = _init_batched_state(x, c0s, cfg, bk, x_axis, w=weights)
    writer = _make_writer(checkpoint_dir, serialize.KIND_BATCHED,
                          keep_last_n, keep_every_m, mx, sync_writes)
    try:
        while bool(jnp.any(_is_active(bst.inner, cfg.max_iter))):
            t0 = time.perf_counter()
            bst = _run_batched_segment(x, bst, jnp.asarray(every, jnp.int32),
                                       cfg, bk, x_batched=(x_axis == 0),
                                       w=weights)
            trips += every   # upper bound on the final segment; monotone
            n_active = int(jnp.sum(_is_active(bst.inner, cfg.max_iter)))
            seg_s = time.perf_counter() - t0
            if writer is not None:
                writer.submit(jax.device_get(bst), trips,
                              _snapshot_meta(trips, cfg, bk))
            elif checkpoint_dir is not None:
                _snapshot(checkpoint_dir, bst, serialize.KIND_BATCHED,
                          trips, cfg, bk, keep_last_n=keep_last_n,
                          keep_every_m=keep_every_m)
            if checkpoint_cb is not None:
                checkpoint_cb(bst, trips)
            e = bst.inner.e_last
            e_best = jnp.min(jnp.where(jnp.isfinite(e), e, jnp.inf))
            mx.log_scalars(trips, {
                "energy_best": float(e_best),
                "n_active": float(n_active),
                "n_accepted_total": float(int(jnp.sum(bst.inner.n_acc))),
                "segment_s": seg_s})
            if _metrics_stop(mx):
                break   # EarlyStopHook: improvement per segment stalled
    finally:
        if writer is not None:
            writer.close()
    return _result_from_state(_close_batched_jit(
        x, bst.inner, cfg, bk, x_batched=(x_axis == 0), w=weights))


def select_best(results: KMeansResult, groups=None,
                n_groups: Optional[int] = None) -> KMeansResult:
    """On-device best-of-R selection: the restart with the lowest final
    energy, as an unbatched KMeansResult.  Ties break toward the lower
    index — the same winner the sequential strict-< loop keeps.

    A NaN final energy (degenerate restart: NaN rows in X, numerically
    exploded iterate) never wins: `argmin` alone returns index 0 as soon
    as ANY energy is NaN, silently crowning a broken restart.  Non-finite
    energies are excluded from the comparison; if every restart is
    non-finite, the returned result keeps its NaN energy so the failure
    surfaces at the caller (the estimator raises on it) instead of being
    masked by a plausible-looking winner.

    ``groups`` (R,) int32 generalises the selection to PER-PROBLEM masked
    energies: restart r competes only within problem groups[r] (the
    hierarchy driver runs G sub-problems x n_init seeds as one batch),
    and the result keeps a leading axis of ``n_groups`` — row g is group
    g's winner.  Per-group masking uses the same finite-energy rule; a
    group whose every restart is non-finite surfaces its energy at row g.
    """
    e = results.energy
    masked = jnp.where(jnp.isfinite(e), e, jnp.inf)
    if groups is None:
        best = jnp.argmin(masked)
        return jax.tree_util.tree_map(lambda a: a[best], results)
    if n_groups is None:
        raise ValueError("select_best(groups=...) needs a static n_groups")
    gid = jnp.arange(n_groups, dtype=jnp.int32)
    emat = jnp.where(groups.astype(jnp.int32)[None, :] == gid[:, None],
                     masked[None, :], jnp.inf)               # (G, R)
    best = jnp.argmin(emat, axis=1)                          # (G,)
    return jax.tree_util.tree_map(lambda a: a[best], results)


# ---------------------------------------------------------------------------
# Streaming mini-batch driver (chunked X; DESIGN.md §Streaming)
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("cfg", "backend"))
def _run_minibatch_epoch(chunks, weights, x_val, state, key,
                         cfg: MiniBatchConfig, backend: Backend):
    """One epoch as a standalone program: the exact body of the scan-path
    ``epoch_step`` (same key-split order), so epoch-granular segmentation
    partitions the scan's computation without changing a bit of it."""
    key, sub = jax.random.split(key)
    state, trace = run_epoch(chunks, weights, x_val, state, cfg, backend,
                             sub)
    return state, key, trace


def minibatch_stream_like(c0, cfg: MiniBatchConfig,
                          backend: BackendLike = None, key=None):
    """ShapeDtypeStruct tree of a streaming-solver snapshot: the
    `MiniBatchState` plus the epoch-shuffle key (trajectory state the
    `lax.scan` carry holds alongside the solver state)."""
    bk = resolve_backend(backend)
    key_sds = jax.ShapeDtypeStruct((2,), jnp.uint32) if key is None else \
        jax.ShapeDtypeStruct(key.shape, key.dtype)
    state_sds = jax.eval_shape(
        lambda cc: minibatch_init(cc, cfg, bk),
        jax.ShapeDtypeStruct(c0.shape, c0.dtype))
    return {"state": state_sds, "key": key_sds}


def _aa_kmeans_minibatch_segmented(chunks, weights, x_val, c0,
                                   cfg: MiniBatchConfig, bk: Backend, key,
                                   checkpoint_every, checkpoint_dir,
                                   resume_from, checkpoint_cb,
                                   return_trace: bool,
                                   keep_last_n: int = 0,
                                   keep_every_m: int = 0,
                                   metrics=None,
                                   sync_writes: bool = False):
    _no_trace(chunks, "aa_kmeans_minibatch")
    mx = as_metrics(metrics)
    every = max(1, int(checkpoint_every)) if checkpoint_every else 1
    like = minibatch_stream_like(c0, cfg, bk, key)
    epoch = 0
    if isinstance(resume_from, (str, os.PathLike)):
        tree, meta = serialize.restore(resume_from, like,
                                       expect_kind=serialize.KIND_MINIBATCH)
        _check_resume_meta(meta, cfg, bk, str(resume_from))
        state, key = tree["state"], jnp.asarray(tree["key"])
        epoch = int(meta.get("epoch", 0))
    elif resume_from is not None:
        state, key = resume_from["state"], resume_from["key"]
        epoch = int(resume_from.get("epoch", 0))
    else:
        state = minibatch_init(c0, cfg, bk)
    traces = []
    writer = _make_writer(checkpoint_dir, serialize.KIND_MINIBATCH,
                          keep_last_n, keep_every_m, mx, sync_writes)
    try:
        while epoch < cfg.epochs:
            t0 = time.perf_counter()
            state, key, trace = _run_minibatch_epoch(chunks, weights, x_val,
                                                     state, key, cfg, bk)
            epoch += 1
            n_acc_epoch = int(jnp.sum(trace.accepted))   # host sync
            epoch_s = time.perf_counter() - t0
            if return_trace:
                traces.append(trace)
            if checkpoint_dir is not None and \
                    (epoch % every == 0 or epoch == cfg.epochs):
                meta = _snapshot_meta(epoch, cfg, bk,
                                      extra={"epoch": epoch})
                if writer is not None:
                    writer.submit(
                        jax.device_get({"state": state, "key": key}),
                        epoch, meta)
                else:
                    _snapshot(checkpoint_dir, {"state": state, "key": key},
                              serialize.KIND_MINIBATCH, epoch, cfg, bk,
                              extra={"epoch": epoch},
                              keep_last_n=keep_last_n,
                              keep_every_m=keep_every_m)
            if checkpoint_cb is not None:
                # "epoch" rides in the payload so the dict round-trips
                # through resume_from= without losing the counter (a
                # path-based resume reads it from the artifact's meta)
                checkpoint_cb({"state": state, "key": key, "epoch": epoch},
                              epoch)
            mx.log_scalars(epoch, {
                "e_val": float(trace.e_val[-1]),
                "e_cand": float(trace.e_cand[-1]),
                "e_fallback": float(trace.e_fallback[-1]),
                "n_accepted_epoch": float(n_acc_epoch),
                "epoch_s": epoch_s})
            if _metrics_stop(mx):
                break   # EarlyStopHook: improvement per epoch stalled
    finally:
        if writer is not None:
            writer.close()
    c_fin, e_fin, _, _ = guard_pick(x_val, state, cfg, bk)
    result = MiniBatchResult(c_fin, e_fin, state.t, state.n_acc)
    if not return_trace:
        return result
    # epochs run in THIS process only — a resumed run's trace covers the
    # epochs since the snapshot, like any log that restarts with a process
    trace = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *traces) \
        if traces else None
    return result, trace


def aa_kmeans_minibatch(chunks: jax.Array, weights: jax.Array,
                        x_val: jax.Array, c0: jax.Array,
                        cfg: MiniBatchConfig,
                        backend: BackendLike = None,
                        key: Optional[jax.Array] = None,
                        return_trace: bool = False, *,
                        checkpoint_every: int = 0,
                        checkpoint_dir=None,
                        resume_from=None,
                        checkpoint_cb: Optional[Callable] = None,
                        keep_last_n: int = 0,
                        keep_every_m: int = 0,
                        metrics=None,
                        sync_writes: bool = False):
    """Streaming Algorithm 1 over chunked data — fully jit-able.

    ``chunks`` is (n_chunks, B, d) with row-weight mask ``weights``
    (n_chunks, B) (`repro.data.streaming.chunk_dataset` builds both),
    ``x_val`` (V, d) is the held-out validation chunk the energy guard
    runs on, and ``c0`` (K, d) the seed centroids.  Runs ``cfg.epochs``
    epochs; the chunk order is reshuffled per epoch from ``key``.

    Each chunk step shares Algorithm 1's accept/revert skeleton with the
    full-batch driver — guard, dynamic-m, one weighted backend pass,
    Anderson push/solve (`minibatch.minibatch_iteration`) — and the whole
    run is a `lax.scan` over epochs of a `lax.scan` over chunks, so the
    program dispatches once regardless of epochs x chunks.  Runs
    unchanged under shard_map with a `distribute()`-wrapped backend: one
    stat-psum per chunk (`make_distributed_kmeans_minibatch`).

    Returns a `MiniBatchResult` whose centroids are the final
    guard-picked iterate; with ``return_trace=True`` also returns a
    `MiniBatchTrace` with leaves of shape (epochs, n_chunks).

    ``checkpoint_every=e`` segments the run at EPOCH granularity (a host
    loop over the jit'd epoch program, snapshotting state + shuffle key
    every e epochs); see ``aa_kmeans`` for the checkpoint/resume contract.
    The runtime knobs (``keep_last_n=`` / ``keep_every_m=`` retention,
    ``metrics=`` sink, ``sync_writes=``) carry over verbatim; metrics are
    emitted once per epoch.
    """
    if chunks.ndim != 3:
        raise ValueError(f"chunks must be (n_chunks, B, d); got "
                         f"{chunks.shape}")
    if weights.shape != chunks.shape[:2]:
        raise ValueError(f"weights {weights.shape} must match chunks' "
                         f"leading dims {chunks.shape[:2]}")
    bk = resolve_backend(backend)
    if key is None:
        key = jax.random.PRNGKey(0)
    if checkpoint_every or checkpoint_dir is not None \
            or resume_from is not None or checkpoint_cb is not None \
            or metrics is not None:
        return _aa_kmeans_minibatch_segmented(
            chunks, weights, x_val, c0, cfg, bk, key, checkpoint_every,
            checkpoint_dir, resume_from, checkpoint_cb, return_trace,
            keep_last_n=keep_last_n, keep_every_m=keep_every_m,
            metrics=metrics, sync_writes=sync_writes)
    state = minibatch_init(c0, cfg, bk)

    def epoch_step(carry, _):
        st, k2 = carry
        k2, sub = jax.random.split(k2)
        st, trace = run_epoch(chunks, weights, x_val, st, cfg, bk, sub)
        return (st, k2), trace

    (state, _), trace = jax.lax.scan(epoch_step, (state, key), None,
                                     length=cfg.epochs)
    c_fin, e_fin, _, _ = guard_pick(x_val, state, cfg, bk)
    result = MiniBatchResult(c_fin, e_fin, state.t, state.n_acc)
    return (result, trace) if return_trace else result


def aa_kmeans_minibatch_streamed(source, x_val: jax.Array, c0: jax.Array,
                                 cfg: MiniBatchConfig,
                                 backend: BackendLike = None, *,
                                 chunk_size: Optional[int] = None,
                                 seed: int = 0,
                                 prefetch: int = 2,
                                 drop_remainder: bool = False,
                                 sort_chunks: bool = False,
                                 mesh=None, data_axes=("data",),
                                 meter=None, metrics=None,
                                 return_trace: bool = False):
    """Streaming Algorithm 1 over a host-resident source, with transfer
    overlap: the `stream_chunks` prefetcher threaded under the epoch
    driver (DESIGN.md §Runtime — previously only `partial_fit_stream` and
    the ``--big`` benchmark overlapped host→device copies).

    ``source`` is a host array (chunked/shuffled per epoch by
    `host_chunk_stream`; ``chunk_size`` defaults to ``cfg.chunk_size``) or
    any iterator of host chunks (``chunk_size``/``seed`` ignored; the
    caller owns ordering and ``cfg.epochs`` must be baked into the
    iterator).  Each chunk runs one jitted `minibatch_iteration` — the
    same per-chunk state machine as `aa_kmeans_minibatch` — while chunk
    t+1's copy is in flight, so the device never waits on ingest.  For a
    device-resident `DeviceChunks` use `aa_kmeans_minibatch`, whose
    scan-over-gathers needs no transfers at all.

    ``sort_chunks=True`` assembles each chunk cluster-sorted
    (`stream_chunks(sort_by=...)` with the driver's current centroids —
    stale by the prefetch depth, which affects locality only, never the
    numbers) so the weighted backend pass sees locality-ordered rows.
    ``meter`` (an `IngestMeter`) and ``metrics`` observe ingest bandwidth
    and per-chunk guard decisions; note a ``metrics`` sink synchronises on
    every chunk, serialising the very overlap this driver exists for —
    leave it None on the hot path.  Uniform chunk lengths avoid re-jitting
    (``drop_remainder=True`` guarantees them for an array source).

    Returns a `MiniBatchResult` (with ``return_trace=True``, also a
    `MiniBatchTrace` stacked over all chunk steps).
    """
    from repro.data.streaming import stream_chunks

    bk = resolve_backend(backend)
    state = minibatch_init(c0, cfg, bk)
    holder = [state]
    sort_by = (lambda: jax.device_get(holder[0].c)) if sort_chunks else None
    step = jax.jit(minibatch_iteration, static_argnames=("cfg", "backend"))
    mx = as_metrics(metrics)
    traces = []
    chunk_iter = stream_chunks(
        source, None if hasattr(source, "__next__") else
        (chunk_size or cfg.chunk_size),
        epochs=cfg.epochs, seed=seed, drop_remainder=drop_remainder,
        prefetch=prefetch, mesh=mesh, data_axes=tuple(data_axes),
        meter=meter, sort_by=sort_by)
    for xc in chunk_iter:
        w = jnp.ones((xc.shape[0],), jnp.float32)
        holder[0], tr = step(xc, w, x_val, holder[0], cfg, bk)
        if return_trace:
            traces.append(tr)
        if metrics is not None:
            mx.log_scalars(int(holder[0].t),
                           {"e_val": float(tr.e_val),
                            "accepted": float(tr.accepted)})
    state = holder[0]
    c_fin, e_fin, _, _ = guard_pick(x_val, state, cfg, bk)
    result = MiniBatchResult(c_fin, e_fin, state.t, state.n_acc)
    if not return_trace:
        return result
    trace = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *traces) \
        if traces else None
    return result, trace


# ---------------------------------------------------------------------------
# Instrumented Python driver (benchmark parity with the paper's tables)
# ---------------------------------------------------------------------------

class KMeansTrace(NamedTuple):
    result: KMeansResult
    energies: list          # E^t per iteration (post-revert)
    m_values: list          # m after adjustment, per iteration
    accepted: list          # bool per iteration
    wall_time_s: float
    mse: float              # final E / N — the paper's reported MSE
    # per-iteration {"eliminated_frac", "skipped_frac"} dicts for bound
    # backends (hamerly/elkan/yinyang/fused_bounds), read off the carry's
    # BoundStats; [] for stateless backends.  Shows how the elimination
    # ramps from 0 (first full scan) toward the converged-phase plateau.
    bound_stats: tuple = ()
    # per-phase means of bound_stats, split at the FIRST ACCEPTED AA
    # iteration: {"pre_accept": {...}, "post_accept": {...}}, each with
    # n_iters + the mean fracs (None when the phase is empty).  The flat
    # bound_stats average mixes the warm-up iterations — where skipping is
    # structurally ~0 because bounds have not tightened — into the
    # converged plateau, understating the engine by 2-3x on short runs;
    # BENCH consumers must read post_accept (see split_bound_phases).
    bound_phases: Optional[dict] = None


def split_bound_phases(accepted, bound_stats):
    """Split per-iteration bound stats at the first accepted iteration.

    The early iterations run on slack bounds (first scan has upper = +inf;
    drift updates need a few steps to tighten), so their elimination/skip
    fractions sit near 0 regardless of the engine's quality — averaging
    them into the converged tail dilutes every reported fraction.  The
    first *accepted* AA iteration is the natural phase boundary: the energy
    has started decreasing monotonically and the bounds are live.

    Returns {} when there are no bound stats; otherwise a dict with
    "pre_accept" / "post_accept" entries of {n_iters, <mean of each stat
    key>} — empty phases report n_iters = 0 and None means.
    """
    bound_stats = list(bound_stats)
    if not bound_stats:
        return {}
    accepted = list(accepted)[:len(bound_stats)]
    first = next((i for i, a in enumerate(accepted) if a), len(bound_stats))
    keys = sorted(bound_stats[0])

    def phase(rows):
        out = {"n_iters": len(rows)}
        for key in keys:
            out[key] = (sum(r[key] for r in rows) / len(rows)) if rows \
                else None
        return out

    return {"pre_accept": phase(bound_stats[:first]),
            "post_accept": phase(bound_stats[first:])}


def aa_kmeans_traced(x: jax.Array, c0: jax.Array, cfg: KMeansConfig,
                     backend: BackendLike = None,
                     warmup: bool = False,
                     metrics=None,
                     reorder=False) -> KMeansTrace:
    """Python-loop driver recording the statistics of Tables 2 and 3.

    ``metrics=`` accepts any `repro.runtime.metrics` sink; each iteration
    emits {energy, m, accepted} plus bound-elimination fractions for
    bound backends — the same numbers the returned trace accumulates,
    streamed live instead of collected at the end.

    ``warmup=True`` compiles the init and trip computations on a throwaway
    run before the timer starts, so ``wall_time_s`` measures steady-state
    execution rather than jit compilation — the quantity the paper's
    Table 3 wall-times report.  (Both jitted functions are keyed on static
    (cfg, backend) and the argument shapes, so the warm-up populates
    exactly the cache the timed loop hits.)

    The loop runs `aa_kmeans`'s trip (`_trip`) from Python and records an
    iteration when a trip completes one: a rejected iteration's first
    trip leaves it pending and records nothing.

    ``reorder=`` enables the locality engine exactly as in ``aa_kmeans``;
    the trace's ``bound_phases`` then shows the converged-phase skip the
    reordering unlocked (the flat average would dilute it — see
    `split_bound_phases`).
    """
    bk = maybe_reorder(resolve_backend(backend, cfg=cfg), reorder)

    def start():
        return _BatchedState(_init_state_jit(x, c0, cfg, bk),
                             jnp.array(False))

    if warmup:
        jax.block_until_ready(_trip_jit(x, start(), cfg, bk).inner.c)

    from repro.core.backends.bounds import extract_stats

    mx = as_metrics(metrics)
    t0 = time.perf_counter()
    bst = start()
    energies, m_vals, acc, bstats = [], [], [], []
    while not bool(bst.inner.converged) and int(bst.inner.t) < cfg.max_iter:
        was_pending = bool(bst.pending)
        bst = _trip_jit(x, bst, cfg, bk)
        if bool(bst.inner.converged) or bool(bst.pending):
            continue
        state = bst.inner
        energies.append(float(state.e_last))
        m_vals.append(int(state.aa.m))
        acc.append(not was_pending)
        scalars = {"energy": energies[-1], "m": float(m_vals[-1]),
                   "accepted": float(acc[-1])}
        bs = extract_stats(state.carry)
        if bs is not None:
            bstats.append({"eliminated_frac": float(bs.eliminated_frac),
                           "skipped_frac": float(bs.skipped_frac)})
            scalars.update(bstats[-1])
        mx.log_scalars(len(energies), scalars)
    state = bst.inner
    converged = bool(state.converged)
    if not converged:
        state = _close_jit(x, state, cfg, bk)
    jax.block_until_ready(state.c)
    wall = time.perf_counter() - t0

    n_iter = len(energies) + 1          # +1 for the initial G(C^0)
    n_accepted = sum(acc)
    result = KMeansResult(state.c, state.labels, state.e_last,
                          jnp.array(n_iter), jnp.array(n_accepted),
                          jnp.array(converged))
    mse = float(state.e_last) / x.shape[0]
    return KMeansTrace(result, energies, m_vals, acc, wall, mse, bstats,
                       split_bound_phases(acc, bstats))
