"""Pure-jnp oracles for every Pallas kernel in this package.

Each function is the semantic specification its kernel is tested against
(tests/test_kernels.py sweeps shapes/dtypes and assert_allclose's).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def assignment_ref(x: jax.Array, c: jax.Array):
    """Nearest-centroid assignment.  x (N,d), c (K,d) ->
    (labels (N,) int32, min_sqdist (N,) f32)."""
    x = x.astype(jnp.float32)
    c = c.astype(jnp.float32)
    x_sq = jnp.sum(x * x, axis=-1, keepdims=True)
    c_sq = jnp.sum(c * c, axis=-1)
    cross = jnp.matmul(x, c.T, precision=jax.lax.Precision.HIGHEST)
    d = jnp.maximum(x_sq - 2.0 * cross + c_sq[None, :], 0.0)
    return jnp.argmin(d, axis=-1).astype(jnp.int32), jnp.min(d, axis=-1)


def update_ref(x: jax.Array, labels: jax.Array, k: int, w=None):
    """Per-cluster sums and counts, optionally row-weighted by w (N,).
    -> (sums (K,d) f32, counts (K,) f32)."""
    x = x.astype(jnp.float32)
    w = jnp.ones((x.shape[0],), jnp.float32) if w is None \
        else w.astype(jnp.float32)
    sums = jax.ops.segment_sum(x * w[:, None], labels, num_segments=k)
    counts = jax.ops.segment_sum(w, labels, num_segments=k)
    return sums, counts


def fused_lloyd_ref(x: jax.Array, c: jax.Array):
    """One fused Lloyd pass: assignment + cluster sums + counts + energy,
    reading X exactly once.  -> (labels, min_sqdist, sums, counts, energy)."""
    labels, mind = assignment_ref(x, c)
    sums, counts = update_ref(x, labels, c.shape[0])
    return labels, mind, sums, counts, jnp.sum(mind)


def minibatch_ref(x: jax.Array, c: jax.Array, w: jax.Array):
    """Weighted chunk pass (the `Backend.minibatch_step` oracle): row
    weights w (N,) scale each row's contribution to sums/counts/energy;
    labels and min_sqdist stay per-row and unweighted.
    -> (labels, min_sqdist, sums, counts, energy)."""
    labels, mind = assignment_ref(x, c)
    w = w.astype(jnp.float32)
    k = c.shape[0]
    sums = jax.ops.segment_sum(x.astype(jnp.float32) * w[:, None], labels,
                               num_segments=k)
    counts = jax.ops.segment_sum(w, labels, num_segments=k)
    return labels, mind, sums, counts, jnp.sum(mind * w)
