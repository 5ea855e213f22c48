"""Jit'd dispatch layer over the Pallas kernels.

`use_pallas=True` runs the kernels, which pick their own execution mode
(`tiles.interpret_default`): compiled on a TPU, interpreted elsewhere.
`use_pallas=False` runs the pure-jnp oracles of `kernels/ref.py`.

The solver-facing integration lives in `repro.core.backends`
(`get_backend("pallas" | "fused")`): the fused single-pass kernel is
consumed through the step primitive, so Algorithm 1 reads X exactly once
per accepted iteration — at arbitrary K, since the v2 kernel k-tiles the
centroid stream (DESIGN.md §Kernels-v2; there is no VMEM fallback path).
Row weights and the leading-R batch axis of the kernels are exposed here
as optional arguments.
"""

from __future__ import annotations

import jax

from repro.core.backends import fused_backend, pallas_backend  # noqa: F401
from repro.core.backends.pallas import (FUSED_MAX_KD,          # noqa: F401
                                        FUSED_VMEM_BYTES)
from repro.core.lloyd import update_from_sums
from repro.kernels import ref
from repro.kernels.assignment import assignment_pallas
from repro.kernels.fused_lloyd import fused_lloyd_pallas
from repro.kernels.update import update_pallas


def assignment(x: jax.Array, c: jax.Array, *, use_pallas: bool = True):
    """(labels, min_sqdist) — Pallas kernel or jnp oracle.  c may carry a
    leading R axis (R centroid sets in one launch)."""
    if use_pallas:
        return assignment_pallas(x, c)
    if c.ndim == 3:
        return jax.vmap(ref.assignment_ref, in_axes=(None, 0))(x, c)
    return ref.assignment_ref(x, c)


def cluster_update(x: jax.Array, labels: jax.Array, k: int, *,
                   w: jax.Array | None = None, use_pallas: bool = True):
    """(sums, counts) — Pallas kernel or jnp oracle; optional row
    weights w scale each row's contribution (the minibatch stats)."""
    if use_pallas:
        return update_pallas(x, labels, k, w=w)
    return ref.update_ref(x, labels, k, w=w)


def fused_lloyd_step(x: jax.Array, c: jax.Array, *,
                     w: jax.Array | None = None, use_pallas: bool = True):
    """(labels, min_sqdist, sums, counts, energy) in one X pass; optional
    row weights fold into the stats/energy, and a (R, K, d) centroid
    batch adds a leading R axis to every output."""
    if use_pallas:
        return fused_lloyd_pallas(x, c, w)
    if c.ndim == 3:
        fn = (lambda cc: ref.fused_lloyd_ref(x, cc)) if w is None else \
            (lambda cc: ref.minibatch_ref(x, cc, w))
        return jax.vmap(fn)(c)
    if w is None:
        return ref.fused_lloyd_ref(x, c)
    return ref.minibatch_ref(x, c, w)


def fused_step(x: jax.Array, c: jax.Array, *, use_pallas: bool = True):
    """One full Lloyd iteration via the fused kernel:
    returns (c_next, labels, energy)."""
    labels, _, sums, counts, energy = fused_lloyd_step(
        x, c, use_pallas=use_pallas)
    c_next = update_from_sums(sums, counts, c.astype(sums.dtype))
    return c_next.astype(c.dtype), labels, energy
