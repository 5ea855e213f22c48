"""Cluster-closure candidate index for sublinear-in-K assignment
(DESIGN.md §Serving).

Anderson acceleration (PAPER.md) only speeds up *fit*; at serving time
every query still paid a full K-centroid scan.  Following Wang et al.
(*Fast Approximate K-Means via Cluster Closures*, PAPERS.md), the fitted
centroids themselves are cheap to organise: cluster the K centroids into
G groups, keep each group's mean as a **router**, and precompute each
router's **closure** — the candidate list of the ``C`` centroids nearest
to it.  A query then prices G routers, follows the nearest one, and takes
the *exact* argmin over that router's C candidates:

    cost per row:  O(G·d + C·d)   instead of   O(K·d)

With the defaults (G ≈ 4√K routers, C sized like the PR-6 bound groups —
one fused-kernel k-tile of centroids) the scan shrinks by ~K/(G+C) while
recall stays near 1: a query only mislabels when its true centroid is
absent from its router's closure, i.e. when the row sits far outside its
cluster's neighbourhood.  Routers are cheap (one small GEMM), candidates
are not (a per-row gather), so the default spends G ≫ √K on routing to
buy recall at small C.  ``benchmarks/serving_bench.py`` measures the
recall-vs-latency curve over the candidate-count sweep.

Everything here is pure jnp on (K, d)-sized operands — index *build* is a
one-off at fit time (a few Lloyd iterations over the centroids), and the
*query* functions take the index as flat array arguments so the serving
tier's jitted runners recompile only when shapes change, never on a
hot-reload that merely swaps values.
"""

from __future__ import annotations

import math
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.core import lloyd
from repro.core.backends import bounds
from repro.core.lloyd import pairwise_sqdist


class ClosureIndex(NamedTuple):
    """The servable candidate index.

    routers    : (G, d) float — group-mean entry points.
    candidates : (G, C) int32 — for each router, the indices of the C
                 centroids nearest to it, nearest first (so a prefix
                 ``candidates[:, :c]`` is itself a valid, smaller index).
    n_valid    : optional (G,) int32 — ADAPTIVE per-router candidate
                 counts (`build_closure_index(adaptive=True)`): router g
                 scans only ``candidates[g, :n_valid[g]]``; columns past
                 it are masked to +inf at query time.  None (the default,
                 and what every uniform build produces) means all C
                 columns are live — the uniform index's behaviour is
                 unchanged bit for bit.
    """
    routers: jax.Array
    candidates: jax.Array
    n_valid: Optional[jax.Array] = None

    @property
    def n_groups(self) -> int:
        return self.routers.shape[0]

    @property
    def n_candidates(self) -> int:
        return self.candidates.shape[1]

    def shrink(self, n_candidates: int) -> "ClosureIndex":
        """A cheaper index over the same routers: candidate lists are
        sorted nearest-first, so truncation IS the smaller closure.  An
        adaptive index clamps its per-router counts to the new width, so
        the prefix contract survives ``adaptive=True``."""
        n_valid = None if self.n_valid is None \
            else jnp.minimum(self.n_valid, n_candidates)
        return ClosureIndex(self.routers,
                            self.candidates[:, :n_candidates], n_valid)


def default_n_groups(k: int) -> int:
    """4√K routers — still sublinear in K, but deliberately router-heavy:
    routing is one (N, G)·GEMM while candidate scanning pays a per-row
    gather, so trading a bigger G for a smaller C at equal recall is a
    straight win on every backend we measured."""
    return max(1, min(4 * int(math.isqrt(max(k, 1))), k))


def default_n_candidates(k: int) -> int:
    """Candidate lists sized like the PR-6 bound groups (one fused-kernel
    k-tile of centroids, `bounds.resolve_group_size`): the same "how many
    centroids form a neighbourhood" constant the distance-elimination
    engine already uses."""
    return min(k, bounds.resolve_group_size(k, None, policy="tile"))


def build_closure_index(centroids, n_candidates: Optional[int] = None,
                        n_groups: Optional[int] = None, *,
                        n_iter: int = 10, seed: int = 0,
                        adaptive: bool = False) -> ClosureIndex:
    """Build the index from the fitted centroids alone.

    Routers come from ``n_iter`` plain Lloyd iterations clustering the K
    centroids into ``n_groups`` groups (k-means on the codebook — K rows,
    so this is trivia next to the fit that produced them); each router's
    closure is the ``n_candidates`` centroids nearest to it by
    centroid-centroid distance, nearest first.  Deterministic in
    ``seed``.

    ``adaptive=True`` sizes each router's LIVE candidate count by its
    radius (the distance to its farthest member centroid): a router in a
    dense codebook region needs few candidates for full recall while a
    sparse-region router needs many, so ``n_candidates`` becomes the
    *mean* count and each router gets a share proportional to its radius
    (clamped to [1, C_max]).  The candidate matrix stays rectangular —
    width = the largest live count — with per-router validity in
    ``n_valid``; a uniform build (``adaptive=False``) returns
    ``n_valid=None`` and is untouched."""
    c = jnp.asarray(centroids)
    k = c.shape[0]
    g = n_groups if n_groups is not None else default_n_groups(k)
    g = max(1, min(int(g), k))
    n_cand = n_candidates if n_candidates is not None \
        else default_n_candidates(k)
    n_cand = max(1, min(int(n_cand), k))
    key = jax.random.PRNGKey(seed)
    routers = c[jax.random.choice(key, k, (g,), replace=False)]
    for _ in range(max(int(n_iter), 0)):
        labels = jnp.argmin(pairwise_sqdist(c, routers), axis=1)
        sums, counts = lloyd.cluster_sums(c, labels, g)
        routers = lloyd.update_from_sums(sums, counts,
                                         routers.astype(sums.dtype)
                                         ).astype(c.dtype)
    d2 = pairwise_sqdist(routers, c)                           # (G, K)
    if not adaptive:
        _, candidates = jax.lax.top_k(-d2, n_cand)
        return ClosureIndex(routers, candidates.astype(jnp.int32))
    # Radius of router g = distance to its farthest OWNED centroid; an
    # ownerless router scans the mean count (radius -> mean radius).
    owner = jnp.argmin(d2, axis=0)                             # (K,)
    mine = owner[None, :] == jnp.arange(g)[:, None]            # (G, K)
    radius = jnp.sqrt(jnp.max(jnp.where(mine, d2, 0.0), axis=1))
    has = jnp.any(mine, axis=1)
    mean_r = jnp.sum(jnp.where(has, radius, 0.0)) \
        / jnp.maximum(jnp.sum(has), 1)
    radius = jnp.where(has, radius, mean_r)
    share = radius / jnp.maximum(mean_r, 1e-30)
    n_valid = jnp.clip(jnp.round(n_cand * share), 1, k).astype(jnp.int32)
    c_max = int(jax.device_get(jnp.max(n_valid)))
    _, candidates = jax.lax.top_k(-d2, c_max)
    return ClosureIndex(routers, candidates.astype(jnp.int32), n_valid)


def hierarchy_closure_index(centroids, routers, group_offsets
                            ) -> ClosureIndex:
    """The hierarchical solve's FREE serving index (DESIGN.md §Hierarchy).

    `repro.core.hierarchy.aa_kmeans_hierarchical` already produced the
    two-level structure a closure index is built from: the super-centroid
    routers and a group-major codebook where group g owns the rows
    [offsets[g], offsets[g+1]).  No clustering happens here — each
    router's candidate list is exactly its own group's codebook rows,
    reordered nearest-first so the `shrink` prefix contract holds.  A
    query routed and scanned through this index replays the solve's own
    two-level assignment rule."""
    c = jnp.asarray(centroids)
    routers = jnp.asarray(routers)
    off = jnp.asarray(group_offsets, jnp.int32)
    g = routers.shape[0]
    sizes = off[1:] - off[:-1]
    if bool(jax.device_get(jnp.any(sizes != sizes[0]))):
        raise ValueError(
            "hierarchy_closure_index needs uniform group sizes (the "
            "hierarchy engine emits them); got offsets with mixed strides")
    k_sub = int(jax.device_get(sizes[0]))
    ids = off[:-1, None] + jnp.arange(k_sub, dtype=jnp.int32)[None, :]
    table = jnp.take(c, ids.reshape(-1), axis=0).reshape(g, k_sub, -1)
    d2 = jnp.sum((table - routers[:, None, :]) ** 2, axis=-1)  # (G, k_sub)
    order = jnp.argsort(d2, axis=1)
    return ClosureIndex(routers,
                        jnp.take_along_axis(ids, order, axis=1
                                            ).astype(jnp.int32))


# -- query-time kernels (flat array args: jit-cache-friendly across
#    hot reloads — same shapes, new values, zero retraces) ------------------
#
# The centroid gather is the whole query-time cost story.  Gathering
# ``centroids[candidates[g]]`` with (N, C) scattered row indices is
# catastrophically slow on CPU XLA (scalar-loop gather, ~10x the full-K
# GEMM at C=512).  Instead the candidate *table* (G, C, d) is materialised
# once per call — a fixed G·C-row gather amortised over all N queries —
# and each row then gathers ONE contiguous (C, d) block by its router id.


def candidate_table(centroids, candidates):
    """(G, C, d) centroid rows of every router's closure — the operand
    the query kernels actually scan.  O(G·C·d) to build; callers holding
    an index between calls (the serving tier) should build it once per
    model version rather than per batch."""
    g, c = candidates.shape
    return jnp.take(jnp.asarray(centroids), candidates.reshape(-1),
                    axis=0).reshape(g, c, -1)


def _routed_sqdist(x, g, table, n_valid=None):
    """Exact distances from each row to its router's candidate block.
    ``n_valid`` (G,) masks each row's columns past its router's live
    count to +inf (adaptive indices); None scans the full width."""
    cc = table[g]                                  # (N, C, d) block rows
    x_sq = jnp.sum(x * x, axis=-1, keepdims=True)               # (N, 1)
    c_sq = jnp.sum(table * table, axis=-1)[g]                   # (N, C)
    cross = jnp.einsum("nd,ncd->nc", x, cc,
                       precision=lloyd.MATMUL_PRECISION)        # (N, C)
    d2 = jnp.maximum(x_sq - 2.0 * cross + c_sq, 0.0)
    if n_valid is None:
        return d2
    cols = jnp.arange(table.shape[1], dtype=jnp.int32)[None, :]  # (1, C)
    return jnp.where(cols < n_valid[g][:, None], d2, jnp.inf)


def _candidate_sqdist(x, routers, candidates, table, bucketed=False,
                      n_valid=None):
    """Shared core: route, block-gather, exact distances to candidates.
    Returns (g (N,), d2 (N, C)).

    ``bucketed=True`` counting-sorts the rows by router id before the
    block gather and inverts the permutation on the way out (DESIGN.md
    §Locality): rows sharing a router then read the SAME contiguous
    (C, d) table block back to back instead of hopping between blocks —
    the serving-tier analogue of the solver's cluster-sorted reordering.
    All per-row math is row-local, so the outputs are bit-identical to
    the unbucketed path."""
    x = jnp.asarray(x)
    g = jnp.argmin(pairwise_sqdist(x, routers), axis=1)        # (N,)
    if bucketed:
        from repro.core.locality import counting_sort_perm
        perm, inv = counting_sort_perm(g, routers.shape[0])
        d2s = _routed_sqdist(jnp.take(x, perm, axis=0),
                             jnp.take(g, perm, axis=0), table,
                             n_valid=n_valid)
        return g, jnp.take(d2s, inv, axis=0)
    return g, _routed_sqdist(x, g, table, n_valid=n_valid)


def closure_assign(x, centroids, routers, candidates, table=None,
                   bucketed=False, n_valid=None):
    """Approximate assignment: exact argmin over the nearest router's
    candidate list.  Returns (labels (N,) int32, min_sqdist (N,)).

    The only approximation is the candidate restriction — distances to
    the scanned centroids are exact, so a row whose true centroid is in
    its router's closure gets exactly the full-scan label.  ``table`` is
    the `candidate_table`; pass a precomputed one to skip the per-call
    build (hot serving path).  ``bucketed=True`` sorts the batch by
    router id for contiguous table reads (bit-identical outputs; see
    `_candidate_sqdist`).  ``n_valid`` is the adaptive index's per-router
    live count (`ClosureIndex.n_valid`): masked columns price +inf, so a
    masked candidate can never win the argmin."""
    if table is None:
        table = candidate_table(centroids, candidates)
    g, d2 = _candidate_sqdist(x, routers, candidates, table,
                              bucketed=bucketed, n_valid=n_valid)
    j = jnp.argmin(d2, axis=1)
    take = lambda a: jnp.take_along_axis(a, j[:, None], axis=1)[:, 0]
    return take(candidates[g]).astype(jnp.int32), take(d2)


def closure_sqdist(x, centroids, routers, candidates, table=None,
                   fill=jnp.inf, bucketed=False, n_valid=None):
    """Approximate transform support: (N, K) squared distances, computed
    exactly for each row's candidate centroids and ``fill`` (+inf by
    default) everywhere else — +inf keeps any downstream argmin/softmin
    consistent with `closure_assign`, at the cost that non-candidate
    columns carry no information (that is the point of not pricing
    them).  ``bucketed`` / ``n_valid`` as in `closure_assign` — a masked
    adaptive column stays at ``fill``, exactly like a non-candidate."""
    k = jnp.asarray(centroids).shape[0]
    if table is None:
        table = candidate_table(centroids, candidates)
    g, d2 = _candidate_sqdist(x, routers, candidates, table,
                              bucketed=bucketed, n_valid=n_valid)
    if n_valid is not None:
        d2 = jnp.where(jnp.isinf(d2), jnp.asarray(fill, d2.dtype), d2)
    out = jnp.full((d2.shape[0], k), fill, dtype=d2.dtype)
    rows = jnp.arange(d2.shape[0])[:, None]
    return out.at[rows, candidates[g]].set(d2)
