"""Lloyd's algorithm primitives: the fixed-point map G of the paper.

The paper (Zhang et al., 2018) treats one Lloyd iteration — assignment step
(Eq. 3) followed by the centroid-update step (Eq. 4) — as a fixed-point map

    C_{t+1} = G(C_t),   G = Update o Assign,

whose residual F(C) = G(C) - C vanishes at a local minimum of the K-Means
energy (Eq. 1).  This module provides the three primitives (assign / update /
energy) as pure, jit-able JAX functions plus an `Ops` container so that the
same Algorithm-1 driver (kmeans.py) can run with

  * the dense single-device ops below,
  * the Pallas TPU kernels (repro.kernels.ops), or
  * the shard_map distributed ops (repro.core.distributed)

without any change to the acceleration logic.

Hardware adaptation note (see DESIGN.md): the paper's CPU implementation uses
Hamerly's bound-based assignment to skip distance computations.  Bound
checking is data-dependent branching — hostile to the TPU's SIMD/MXU model —
so the TPU-native formulation is a dense blocked matmul
``dist^2 = |x|^2 - 2 x.c + |c|^2`` that runs on the MXU, optionally fused with
the update pass (repro/kernels/fused_lloyd.py).  A masked Hamerly variant is
provided in `hamerly.py` for completeness and CPU benchmarking.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp

# f32 matmuls run at f32 on every platform: a TPU's DEFAULT precision
# rounds f32 operands to bf16, which moves near-tie assignments and the
# Anderson solve away from the CPU result.  bf16 operands are exact
# either way.
MATMUL_PRECISION = jax.lax.Precision.HIGHEST


class AssignResult(NamedTuple):
    labels: jax.Array      # (N,) int32 — index of the closest centroid
    min_sqdist: jax.Array  # (N,) float — squared distance to that centroid


# ---------------------------------------------------------------------------
# Distance computation
# ---------------------------------------------------------------------------

def pairwise_sqdist(x: jax.Array, c: jax.Array) -> jax.Array:
    """Squared Euclidean distances between rows of x (N,d) and c (K,d).

    Uses the MXU-friendly expansion |x|^2 - 2 x.c + |c|^2 and clamps tiny
    negative values produced by cancellation.
    """
    x_sq = jnp.sum(x * x, axis=-1, keepdims=True)          # (N,1)
    c_sq = jnp.sum(c * c, axis=-1)                         # (K,)
    cross = jnp.matmul(x, c.T, precision=MATMUL_PRECISION)  # (N,K) — MXU
    return jnp.maximum(x_sq - 2.0 * cross + c_sq[None, :], 0.0)


def assign(x: jax.Array, c: jax.Array, *, block_n: int = 0,
           block_unroll: bool = False) -> AssignResult:
    """Assignment step (Eq. 3): nearest centroid for every sample.

    ``block_n > 0`` evaluates distances in blocks of rows to bound the (N,K)
    intermediate — the pure-JAX analogue of the Pallas kernel's N-tiling.
    ``block_unroll`` uses a python loop instead of lax.map (the dry-run uses
    it so cost_analysis sees every block body; see launch/dryrun.py)."""
    n = x.shape[0]
    if block_n and n > block_n and n % block_n == 0:
        def body(xb):
            d = pairwise_sqdist(xb, c)
            return (jnp.argmin(d, axis=-1).astype(jnp.int32),
                    jnp.min(d, axis=-1))

        xs = x.reshape(n // block_n, block_n, x.shape[1])
        if block_unroll:
            outs = [body(xs[i]) for i in range(n // block_n)]
            labels = jnp.stack([o[0] for o in outs])
            dists = jnp.stack([o[1] for o in outs])
        else:
            labels, dists = jax.lax.map(body, xs)
        return AssignResult(labels.reshape(n), dists.reshape(n))
    d = pairwise_sqdist(x, c)
    return AssignResult(jnp.argmin(d, axis=-1).astype(jnp.int32),
                        jnp.min(d, axis=-1))


# ---------------------------------------------------------------------------
# Update step
# ---------------------------------------------------------------------------

def _accum_dtype(*dtypes):
    """Statistics accumulate in AT LEAST f32 (§Kernels-v2 precision
    policy: compute-dtype distances, f32 accumulation).  Accumulating in
    the compute dtype is a correctness bug, not a precision trade-off: a
    bf16 count (8 mantissa bits) stops incrementing at 256 — `256 + 1`
    rounds back to 256 — so any cluster beyond 256 members silently
    freezes its count and drifts its centroid.  f64 inputs keep f64."""
    return jnp.promote_types(jnp.result_type(*dtypes), jnp.float32)


def cluster_sums(x: jax.Array, labels: jax.Array, k: int):
    """Per-cluster sums (K,d) and counts (K,) via segment-sum.

    Accumulates in `_accum_dtype(x.dtype)` (>= f32) regardless of the
    compute dtype; cast at the boundary if a narrower dtype is needed."""
    acc = _accum_dtype(x.dtype)
    sums = jax.ops.segment_sum(x.astype(acc), labels, num_segments=k)
    counts = jax.ops.segment_sum(jnp.ones((x.shape[0],), acc), labels,
                                 num_segments=k)
    return sums, counts


def weighted_cluster_sums(x: jax.Array, labels: jax.Array, w: jax.Array,
                          k: int):
    """Weighted per-cluster sums (K,d) and weight totals (K,).

    The masked/mini-batch generalisation of `cluster_sums`: each row
    contributes `w` times (w = 0 drops a padding row entirely; w = 1 for
    every row recovers `cluster_sums` exactly).  Accumulates >= f32 like
    `cluster_sums` — decayed streaming counts hit the same bf16 ceiling."""
    acc = _accum_dtype(x.dtype, w.dtype)
    wa = w.astype(acc)
    sums = jax.ops.segment_sum(x.astype(acc) * wa[:, None], labels,
                               num_segments=k)
    counts = jax.ops.segment_sum(wa, labels, num_segments=k)
    return sums, counts


def update_from_sums(sums: jax.Array, counts: jax.Array,
                     c_prev: jax.Array) -> jax.Array:
    """Update step (Eq. 4) given partial sums.  Empty clusters keep their
    previous centroid (the standard Lloyd convention; the paper does not
    treat empty clusters specially)."""
    safe = jnp.maximum(counts, 1.0)[:, None]
    mean = sums / safe
    return jnp.where(counts[:, None] > 0, mean, c_prev)


def update(x: jax.Array, labels: jax.Array, k: int,
           c_prev: jax.Array) -> jax.Array:
    """Update step (Eq. 4): each centroid becomes the mean of its samples.
    The mean is formed in the >= f32 accumulation dtype and cast back to
    the centroid dtype at the boundary."""
    sums, counts = cluster_sums(x, labels, k)
    return update_from_sums(sums, counts,
                            c_prev.astype(sums.dtype)).astype(c_prev.dtype)


# ---------------------------------------------------------------------------
# Energy
# ---------------------------------------------------------------------------

def energy(x: jax.Array, c: jax.Array, labels: jax.Array) -> jax.Array:
    """K-Means energy (Eq. 1) E(P, C) with a pre-computed assignment P.

    O(N d) — this is the cheap re-evaluation the paper uses to test whether
    an accelerated iterate decreases the energy (Sec. 2.1, overhead part ii).
    """
    diff = x - c[labels]
    return jnp.sum(diff * diff)


def energy_from_mindist(min_sqdist: jax.Array) -> jax.Array:
    return jnp.sum(min_sqdist)


# ---------------------------------------------------------------------------
# Ops container — dependency injection point for kernels / distribution
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class LloydOps:
    """DEPRECATED dependency-injection container (see DESIGN.md §Backends).

    Superseded by `repro.core.backends.Backend`, whose single-pass
    ``step()`` primitive lets the driver run one pass over X per accepted
    iteration; separate assign/update call sites cannot express that.
    Passing a LloydOps to the solvers still works — it is adapted through
    `repro.core.backends.from_lloyd_ops` with the legacy two-pass cost.

    assign_fn(x, c)            -> AssignResult
    update_fn(x, labels, k, c) -> new centroids (K,d)
    energy_fn(x, c, labels)    -> scalar energy
    all_equal_fn(a, b)         -> scalar bool (assignments identical;
                                  distributed backends psum-reduce this)
    """
    assign_fn: Callable = assign
    update_fn: Callable = update
    energy_fn: Callable = energy
    all_equal_fn: Callable = lambda a, b: jnp.all(a == b)
    # scalar cross-shard reduction (distributed backends psum); the solver
    # computes E(P^t, C^t) as sum(min_sqdist) reusing the assignment — the
    # paper's O(N) overhead argument (Sec 2.1 part ii) — then reduces it.
    reduce_scalar: Callable = lambda x: x

    def g_map(self, x: jax.Array, c: jax.Array, k: int):
        """One application of the fixed-point map G = Update o Assign.

        Returns (G(c), labels, min_sqdist)."""
        res = self.assign_fn(x, c)
        c_new = self.update_fn(x, res.labels, k, c)
        return c_new, res


DENSE_OPS = LloydOps()


def lloyd_iteration(x: jax.Array, c: jax.Array, k: int,
                    ops: LloydOps = DENSE_OPS):
    """One classical Lloyd iteration; returns (C', labels, energy(P, C))."""
    c_new, res = ops.g_map(x, c, k)
    return c_new, res.labels, energy_from_mindist(res.min_sqdist)


@partial(jax.jit, static_argnames=("k", "max_iter"))
def lloyd_kmeans(x: jax.Array, c0: jax.Array, k: int, max_iter: int = 500):
    """Baseline: plain Lloyd's algorithm run to assignment convergence.

    This is the unaccelerated reference the paper compares against
    (Table 3, "Lloyd" columns).  Returns (C, labels, energy, n_iter).
    """
    res0 = assign(x, c0)

    def cond(state):
        _, _, _, converged, t = state
        return jnp.logical_and(~converged, t < max_iter)

    def body(state):
        c, labels, _, _, t = state
        c_new = update(x, labels, k, c)
        res = assign(x, c_new)
        converged = jnp.all(res.labels == labels)
        return (c_new, res.labels, energy_from_mindist(res.min_sqdist),
                converged, t + 1)

    state = (c0, res0.labels, energy_from_mindist(res0.min_sqdist),
             jnp.array(False), jnp.array(0, jnp.int32))
    c, labels, e, _, t = jax.lax.while_loop(cond, body, state)
    return c, labels, e, t
