"""Cluster-sorted row reordering — the locality engine (DESIGN.md §Locality).

Tile-granular work elimination (the fused_bounds kernel's skip predicate,
Elkan/Yinyang group bounds) only pays when neighbouring rows share owners:
on cluster-ordered rows the converged-phase skip is ~0.75, on interleaved
`make_blobs` rows it is ~0.  This module closes that gap by *sorting rows
by their current label* once assignments stabilise, running the bound
backend on the permuted X, and inverting the permutation on exit so the
emitted labels/energies are bit-identical to the unpermuted solve.

The permutation lives INSIDE the backend carry, as a wrapper Backend:

    carry = (perm, inv, labels_sort, t, n_sorts, inner_carry)

    perm        (N,) i32  row at sorted slot j came from original row perm[j]
    inv         (N,) i32  original row i now lives at sorted slot inv[i]
    labels_sort (N,) i32  original-order labels at the time of the last sort
                          (zeros before the first sort — any real labelling
                          churns ~1 against it, so the first eligible step
                          always sorts)
    t           ()   i32  steps taken (warm-up gate)
    n_sorts     ()   i32  sorts performed (churn-trigger observability)
    inner_carry           the wrapped backend's bound carry — the shared
                          (labels, upper, lower, c_last, stats) contract of
                          backends/bounds.py, all per-row arrays in
                          *permuted* order

Because the carry rides the drivers' loop state, checkpoint persistence,
bit-identical resume, and the batched driver all come for free — the
PR-5 artifact serialises the permutation like any other carry leaf.

Exactness: every per-row quantity the bound backends compute (labels,
upper/lower bounds, min_sqdist) is row-local, so permuting rows permutes
the outputs bitwise.  The wrapper re-gathers labels/min_sqdist back to
original order and RECOMPUTES sums/counts/energy from the original-order
arrays — the exact expressions the unwrapped CPU bound backends use — so
reordering never perturbs the AA accept/revert trajectory.  The price is
one (N, d) gather of X per step; the win is the converged tail where the
kernel skips the majority of centroid tiles.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from . import lloyd
from .backends.base import Backend, StepResult


@dataclasses.dataclass(frozen=True)
class ReorderConfig:
    """Churn-triggered re-sort policy (hashable: rides jit static args).

    warmup          — steps before the first sort may fire.  The early
                      iterations churn heavily (sorting would thrash) and
                      bound upkeep has not tightened yet; the default skips
                      the init step plus one full scan.
    churn_threshold — re-sort when the fraction of rows whose label changed
                      since the LAST sort exceeds this.  0 re-sorts on any
                      drift; >= 1 never re-sorts after the first.
    sort_tile       — static label-tile width of the counting sort's rank
                      pass (None: sized so the transient one-hot stays
                      ~16 MB; see `counting_sort_perm`).
    """
    warmup: int = 2
    churn_threshold: float = 0.15
    sort_tile: Optional[int] = None


DEFAULT_REORDER = ReorderConfig()


# ---------------------------------------------------------------------------
# Stable counting sort (no argsort on the hot path)
# ---------------------------------------------------------------------------


def _rank_tile(n: int, k: int, sort_tile) -> int:
    if sort_tile is not None:
        return max(1, min(k, int(sort_tile)))
    return max(1, min(k, (1 << 22) // max(n, 1)))


def counting_sort_perm(labels: jax.Array, k: int, *, sort_tile=None):
    """Stable counting sort of rows by label via segment offsets.

    Returns ``(perm, inv)``: sorted slot j holds original row ``perm[j]``;
    original row i lands at sorted slot ``inv[i]``.  Rows sharing a label
    keep their original relative order (stability), so the result matches
    ``np.argsort(labels, kind="stable")``.

    O(N·K) work but NO O(N log N) argsort and no data-dependent control
    flow: counts by scatter-add, segment offsets by exclusive cumsum, and
    within-label ranks by a label-tiled one-hot column cumsum whose
    transient (N, sort_tile) buffer is bounded by the static tile width.
    """
    n = labels.shape[0]
    labels = labels.astype(jnp.int32)
    counts = jnp.zeros((k,), jnp.int32).at[labels].add(1)
    offsets = jnp.cumsum(counts) - counts          # exclusive segment starts
    rank = label_ranks(labels, k, sort_tile=sort_tile)
    inv = offsets[labels] + rank
    # inv is a permutation of arange(n), so the scatter-set is exact
    perm = jnp.zeros((n,), jnp.int32).at[inv].set(
        jnp.arange(n, dtype=jnp.int32))
    return perm, inv


def label_ranks(labels: jax.Array, k: int, *, sort_tile=None) -> jax.Array:
    """Within-label stable ranks: rank[i] = #{j < i : labels[j] == labels[i]}.

    The counting sorts' shared inner pass, exposed for segmented callers:
    a label-tiled one-hot column cumsum whose transient (N, sort_tile)
    buffer is bounded by the static tile width — no argsort, no
    data-dependent control flow.
    """
    n = labels.shape[0]
    labels = labels.astype(jnp.int32)
    t = _rank_tile(n, k, sort_tile)

    def body(i, rank):
        ids = i * t + jnp.arange(t, dtype=jnp.int32)
        hit = labels[:, None] == ids[None, :]       # (N, t) one-hot slice
        before = jnp.cumsum(hit.astype(jnp.int32), axis=0) - hit
        return rank + jnp.sum(jnp.where(hit, before, 0), axis=1)

    return lax.fori_loop(0, -(-k // t), body, jnp.zeros((n,), jnp.int32))


def counting_sort_perm_segmented(labels: jax.Array, k: int,
                                 offsets: jax.Array, out_size: int, *,
                                 sort_tile=None):
    """Stable counting sort against a CALLER-SUPPLIED segment-offset table.

    Where `counting_sort_perm` packs segments tightly (offsets = exclusive
    cumsum of the counts), this variant scatters label-l rows to
    consecutive slots starting at ``offsets[l]`` in an output of static
    length ``out_size`` — the primitive behind (a) the hierarchy engine's
    partition step, where offsets = arange(G) * N_max lays every
    super-cluster's rows into its own padded stripe, and (b) distribute()
    shards sorting against a SHARED centroid order so tiles align across
    shards (each shard passes the same offset table; DESIGN.md §Locality).

    Returns ``(perm, inv, counts)``:

        perm   (out_size,) i32 — slot j holds original row perm[j], or the
               sentinel N (= labels.shape[0]) for unfilled slots, so a
               gather from X padded with one trailing sentinel row yields
               the padding rows directly;
        inv    (N,) i32 — original row i lands at slot inv[i];
        counts (k,)  i32 — per-label row counts (segment fill levels).

    The caller guarantees capacity: segment l must have room for
    counts[l] rows before the next offset (overflowing rows are silently
    DROPPED by JAX's out-of-bounds scatter rule — check counts when the
    offsets are not derived from the data).  Rows sharing a label keep
    their original relative order (stability), like `counting_sort_perm`.
    """
    n = labels.shape[0]
    labels = labels.astype(jnp.int32)
    counts = jnp.zeros((k,), jnp.int32).at[labels].add(1)
    rank = label_ranks(labels, k, sort_tile=sort_tile)
    inv = offsets.astype(jnp.int32)[labels] + rank
    perm = jnp.full((out_size,), n, jnp.int32).at[inv].set(
        jnp.arange(n, dtype=jnp.int32), mode="drop")
    return perm, inv, counts


def churn_frac(labels_new: jax.Array, labels_ref: jax.Array) -> jax.Array:
    """Fraction of rows whose label differs between two assignments."""
    return jnp.mean((labels_new != labels_ref).astype(jnp.float32))


def permute_bound_carry(carry, idx: jax.Array):
    """Re-gather the per-row leaves of a bounds.py carry by ``idx``.

    ``idx[j]`` is the OLD position whose state lands at new position j —
    labels/upper/lower move in lockstep with the rows; c_last and the
    BoundStats are row-free and pass through untouched.
    """
    labels, upper, lower, c_last, stats = carry
    return (jnp.take(labels, idx, axis=0),
            jnp.take(upper, idx, axis=0),
            jnp.take(lower, idx, axis=0),
            c_last, stats)


# ---------------------------------------------------------------------------
# Reorder carry accessors (tests / drivers peek without tuple-index magic)
# ---------------------------------------------------------------------------


def permutation(carry) -> jax.Array:
    return carry[0]


def sort_count(carry) -> jax.Array:
    return carry[4]


def inner_carry(carry):
    return carry[5]


# ---------------------------------------------------------------------------
# The wrapper backend
# ---------------------------------------------------------------------------


def _require_bound_carry(carry, n: int) -> None:
    ok = isinstance(carry, tuple) and len(carry) == 5
    if ok:
        ok = all(getattr(carry[i], "shape", (None,))[:1] == (n,)
                 for i in range(3))
    if not ok:
        raise TypeError(
            "reorder_backend wraps bound-carrying backends only: the inner "
            "carry must be the (labels, upper, lower, c_last, stats) "
            "contract of backends/bounds.py with leading-N per-row arrays "
            f"(got {type(carry).__name__})")


@functools.lru_cache(maxsize=None)
def reorder_backend(inner: Backend,
                    config: ReorderConfig = DEFAULT_REORDER) -> Backend:
    """Wrap a bound-carrying backend with churn-triggered row reordering.

    The wrapped backend is a drop-in Backend: same step contract, same
    original-order outputs, conformance-matrix exact.  Compose INSIDE
    `distribute` — ``distribute(reorder_backend(b), axes)`` — so the sort
    stays shard-local (no collective) and the wrapper's shard-local stats
    are the ones psum-reduced.

    Cached per (inner, config): repeated resolution returns the identical
    instance, keeping jit static-argument caching effective.
    """
    if inner.axes:
        raise ValueError(
            f"{inner.name} is already distributed; wrap the local backend "
            "first — distribute(reorder_backend(b), axes) — so the "
            "permutation stays shard-local")
    warmup = int(config.warmup)
    threshold = float(config.churn_threshold)
    acc = inner.precision.accum_dtype

    def init_carry_fn(x, c, k):
        ic = inner.init_carry_fn(x, c, k)
        n = x.shape[-2]
        _require_bound_carry(ic, n)
        ar = jnp.arange(n, dtype=jnp.int32)
        return (ar, ar, jnp.zeros((n,), jnp.int32),
                jnp.int32(0), jnp.int32(0), ic)

    def _pre(x, k, carry):
        """Maybe re-sort, then gather X into permuted order."""
        perm, inv, labels_sort, t, n_sorts, ic = carry
        labels_prev = jnp.take(ic[0], inv, axis=0)      # original order
        do_sort = jnp.logical_and(
            t >= warmup, churn_frac(labels_prev, labels_sort) > threshold)

        def resort(args):
            _, inv_old, _, ic_old = args
            perm_new, inv_new = counting_sort_perm(
                labels_prev, k, sort_tile=config.sort_tile)
            # new slot j holds original row perm_new[j], whose carry state
            # currently sits at old slot inv_old[perm_new[j]]
            idx = jnp.take(inv_old, perm_new, axis=0)
            return (perm_new, inv_new, labels_prev,
                    permute_bound_carry(ic_old, idx))

        perm, inv, labels_sort, ic = lax.cond(
            do_sort, resort, lambda args: args, (perm, inv, labels_sort, ic))
        n_sorts = n_sorts + do_sort.astype(jnp.int32)
        xp = jnp.take(x, perm, axis=0)      # the one X gather per step
        return xp, (perm, inv, labels_sort, t, n_sorts, ic)

    def _post(x, k, carry, res_p, ic_new):
        """Invert the permutation and recompute order-invariant stats."""
        perm, inv, labels_sort, t, n_sorts, _ = carry
        labels = jnp.take(res_p.labels, inv, axis=0)
        mind = jnp.take(res_p.min_sqdist, inv, axis=0)
        # original-order recomputation: bitwise-equal to the unwrapped CPU
        # bound backends' own expressions, and independent of the current
        # permutation (DESIGN.md §Locality)
        sums, counts = lloyd.cluster_sums(x.astype(acc), labels, k)
        energy = jnp.sum(mind)
        return (StepResult(labels, mind, sums, counts, energy),
                (perm, inv, labels_sort, t + 1, n_sorts, ic_new))

    def step_fn(x, c, k, carry):
        xp, carry = _pre(x, k, carry)
        res_p, ic = inner.step_fn(xp, c, k, carry[5])
        return _post(x, k, carry, res_p, ic)

    def batched_step_fn(x, cs, k, carries, w=None):
        # per-restart permutations; x may be shared (N, d) or per-problem
        # (R, N, d).  The sort/gather bookkeeping vmaps (lax.cond lowers to
        # a select under vmap, so batched restarts pay the sort every step
        # once warm — the correctness path; see DESIGN.md §Locality), while
        # the inner step keeps its native batched kernel on the gathered
        # (R, N, d) X.
        if w is not None:
            raise TypeError(
                "reorder_backend has no weighted batched path: _post "
                "recomputes unweighted stats in original row order.  Use "
                "an unwrapped backend for weighted/hierarchical batched "
                "solves — the hierarchy engine's padded segments are "
                "already contiguous by construction, so reordering would "
                "buy nothing there anyway")
        xb = x.ndim == 3
        xp, carries = jax.vmap(
            lambda xx, cr: _pre(xx, k, cr),
            in_axes=(0 if xb else None, 0))(x, carries)
        res_p, ics = inner.batched_step(xp, cs, k, carries[5],
                                        x_batched=True)
        return jax.vmap(
            lambda xx, cr, rp, icn: _post(xx, k, cr, rp, icn),
            in_axes=(0 if xb else None, 0, 0, 0))(x, carries, res_p, ics)

    return Backend(name=f"{inner.name}+reorder",
                   step_fn=step_fn,
                   batched_step_fn=batched_step_fn,
                   # no minibatch_step_fn: carries re-init per chunk, so the
                   # warm-up gate never opens — chunk locality comes from
                   # stream_chunks(sort_by=...) instead.  The generic
                   # weighted fallback (original-order x + labels) is exact.
                   stats_fn=inner.stats_fn,
                   assign_fn=inner.assign_fn,
                   energy_fn=inner.energy_fn,
                   all_equal_fn=inner.all_equal_fn,
                   init_carry_fn=init_carry_fn,
                   precision=inner.precision)


def maybe_reorder(backend: Backend, reorder) -> Backend:
    """Driver-facing switch: False → untouched; True → default policy;
    a ReorderConfig → that policy."""
    if not reorder:
        return backend
    cfg = reorder if isinstance(reorder, ReorderConfig) else DEFAULT_REORDER
    return reorder_backend(backend, cfg)
