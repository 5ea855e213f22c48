"""Dense and row-blocked jnp backends (single-device reference semantics).

`dense` is the semantic oracle every other backend is tested against: the
MXU-friendly |x|^2 - 2 x.c + |c|^2 distance expansion plus segment-sum
cluster stats — exactly the arithmetic of the legacy DENSE_OPS path, so the
step-driven solver reproduces the old trajectories bit-for-bit at f32.

`blocked` evaluates the distance rows in fixed-size blocks so the (N, K)
intermediate never materialises — the pure-JAX analogue of the Pallas
kernel's N-tiling, for datasets where N*K exceeds memory.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import lloyd
from repro.core.backends.base import (Backend, Precision, StepResult,
                                      DEFAULT_PRECISION)
from repro.core.lloyd import AssignResult


def _blocked_assign(x, c, block_n: int) -> AssignResult:
    """Row-blocked assignment for arbitrary N: lloyd.assign only engages
    its blocked path when block_n divides N, so handle the remainder as a
    separate tail block (< block_n rows, dense) rather than silently
    materialising the full (N, K) matrix the blocking exists to avoid —
    and without copying X into a padded buffer every step."""
    n = x.shape[0]
    rem = n % block_n if block_n else 0
    if rem and n > block_n:
        main = lloyd.assign(x[:n - rem], c, block_n=block_n)
        tail = lloyd.assign(x[n - rem:], c)
        return AssignResult(
            jnp.concatenate([main.labels, tail.labels]),
            jnp.concatenate([main.min_sqdist, tail.min_sqdist]))
    return lloyd.assign(x, c, block_n=block_n)


def _stats(precision: Precision):
    def stats_fn(x, labels, k):
        return lloyd.cluster_sums(x.astype(precision.accum_dtype), labels, k)
    return stats_fn


def _step(precision: Precision, block_n: int = 0):
    def step_fn(x, c, k, carry):
        xc = precision.compute_cast(x)
        cc = precision.compute_cast(c)
        res = _blocked_assign(xc, cc, block_n)
        mind = res.min_sqdist.astype(precision.accum_dtype)
        sums, counts = lloyd.cluster_sums(x.astype(precision.accum_dtype),
                                          res.labels, k)
        return StepResult(res.labels, mind, sums, counts,
                          jnp.sum(mind)), carry
    return step_fn


def _minibatch_step(precision: Precision, block_n: int = 0):
    """Natively-weighted step for streaming chunks: one pass computes the
    assignment and folds the row weights straight into sums/counts/energy
    — the generic fallback pays a second segment-sum for the reweighting."""
    def minibatch_step_fn(x, c, k, w, carry):
        xc = precision.compute_cast(x)
        cc = precision.compute_cast(c)
        res = _blocked_assign(xc, cc, block_n)
        acc = precision.accum_dtype
        wa = w.astype(acc)
        mind = res.min_sqdist.astype(acc)
        sums, counts = lloyd.weighted_cluster_sums(x.astype(acc), res.labels,
                                                   wa, k)
        return StepResult(res.labels, mind, sums, counts,
                          jnp.sum(mind * wa)), carry
    return minibatch_step_fn


def _batched_step(precision: Precision):
    """Natively-batched dense step for the multi-restart driver.

    Semantics match ``_step`` per restart row; the formulation differs in
    two performance-critical ways: (1) the distance cross-terms for ALL
    R centroid sets come from one einsum that reads the shared X stream
    once, and (2) cluster stats use a one-hot matmul instead of R vmapped
    segment-sums — the scatter path serialises badly when batched.  Sums
    therefore accumulate in matmul reduction order (last-ulp differences
    vs the sequential scatter; same class as psum reordering).

    Memory contract: peak footprint is two (R, N, K) buffers (distances
    and the one-hot) — R times the sequential path's single (N, K).  When
    R*N*K approaches device memory, use the blocked backend: its vmapped
    fallback bounds the distance intermediate at (R, block_n, K) per
    step and never materialises a one-hot (DESIGN.md §Batching)."""
    def batched_step_fn(x, cs, k, carries, w=None):
        # x: (N, d) shared or (R, N, d); cs: (R, K, d); w: None or (R, N)
        xc = precision.compute_cast(x)
        cc = precision.compute_cast(cs)
        c_sq = jnp.sum(cc * cc, axis=-1)                       # (R, K)
        x_sq = jnp.sum(xc * xc, axis=-1)                       # (N,)|(R,N)
        if x.ndim == 2:
            cross = jnp.einsum("nd,rkd->rnk", xc, cc,
                               precision=lloyd.MATMUL_PRECISION)
            x_term = x_sq[None, :, None]
        else:
            cross = jnp.einsum("rnd,rkd->rnk", xc, cc,
                               precision=lloyd.MATMUL_PRECISION)
            x_term = x_sq[:, :, None]
        d2 = jnp.maximum(x_term - 2.0 * cross + c_sq[:, None, :], 0.0)
        labels = jnp.argmin(d2, axis=-1).astype(jnp.int32)     # (R, N)
        mind = jnp.min(d2, axis=-1).astype(precision.accum_dtype)
        onehot = jax.nn.one_hot(labels, k, dtype=precision.accum_dtype)
        if w is not None:
            # per-problem row weights scale the one-hot, so sums/counts/
            # energy weight in the same contraction; labels/mind stay
            # unweighted (the minibatch contract on the restart axis)
            onehot = onehot * w.astype(precision.accum_dtype)[:, :, None]
        xa = x.astype(precision.accum_dtype)
        if x.ndim == 2:
            sums = jnp.einsum("rnk,nd->rkd", onehot, xa,
                              precision=lloyd.MATMUL_PRECISION)
        else:
            sums = jnp.einsum("rnk,rnd->rkd", onehot, xa,
                              precision=lloyd.MATMUL_PRECISION)
        counts = jnp.sum(onehot, axis=1)                       # (R, K)
        if w is None:
            energy = jnp.sum(mind, axis=-1)
        else:
            energy = jnp.sum(mind * w.astype(mind.dtype), axis=-1)
        return StepResult(labels, mind, sums, counts, energy), carries
    return batched_step_fn


def dense_backend(precision: Precision = DEFAULT_PRECISION) -> Backend:
    return Backend(name="dense",
                   step_fn=_step(precision),
                   batched_step_fn=_batched_step(precision),
                   minibatch_step_fn=_minibatch_step(precision),
                   stats_fn=_stats(precision),
                   assign_fn=lloyd.assign,
                   precision=precision)


def blocked_backend(block_n: int = 4096,
                    precision: Precision = DEFAULT_PRECISION) -> Backend:
    def assign_fn(x, c):
        return _blocked_assign(x, c, block_n)

    return Backend(name=f"blocked{block_n}",
                   step_fn=_step(precision, block_n=block_n),
                   minibatch_step_fn=_minibatch_step(precision,
                                                     block_n=block_n),
                   stats_fn=_stats(precision),
                   assign_fn=assign_fn,
                   precision=precision)
