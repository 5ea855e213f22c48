"""The plain reference that decides ``correct``.

Straightforward float32 ``jax.numpy`` of nearest-centroid assignment and
the Lloyd update, in row blocks so that the (rows, K) distance block fits
beside the data.  It imports nothing of the program under test.

``precision="highest"`` multiplies float32 exactly (six bf16 MXU passes
on a TPU).  ``precision="high"`` is the control: the three-pass bf16
product (bf16_3x) that a TPU's ``Precision.HIGH`` computes, written out
so that it computes the same on every platform.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

BLOCK_ROWS = 65_536
PRECISIONS = ("highest", "high")


def _split_bf16(a):
    hi = a.astype(jnp.bfloat16)
    lo = (a - hi.astype(jnp.float32)).astype(jnp.bfloat16)
    return hi, lo


def cross(x, c, precision: str):
    """x @ c.T in float32 at the named precision."""
    if precision == "highest":
        return jnp.matmul(x, c.T, precision=jax.lax.Precision.HIGHEST)
    if precision == "high":
        xh, xl = _split_bf16(x)
        ch, cl = _split_bf16(c)

        def mm(a, b):
            return jnp.matmul(a, b.T, preferred_element_type=jnp.float32)
        return mm(xh, ch) + mm(xh, cl) + mm(xl, ch)
    raise ValueError(f"precision must be one of {PRECISIONS}")


@functools.partial(jax.jit, static_argnames=("precision",))
def _assign_block(x, c, precision):
    x = x.astype(jnp.float32)
    c = c.astype(jnp.float32)
    d = jnp.sum(x * x, axis=1, keepdims=True) - 2.0 * cross(x, c, precision) \
        + jnp.sum(c * c, axis=1)[None, :]
    d = jnp.maximum(d, 0.0)
    return jnp.argmin(d, axis=1).astype(jnp.int32), jnp.min(d, axis=1)


def assign(x, c, precision: str = "highest", block: int = BLOCK_ROWS):
    """Nearest-centroid labels and squared distances of every row of
    ``x`` (device or host array), as host arrays."""
    c = jnp.asarray(c, jnp.float32)
    labels, mind = [], []
    for i in range(0, x.shape[0], block):
        lab, md = _assign_block(jnp.asarray(x[i:i + block]), c, precision)
        labels.append(np.asarray(lab))
        mind.append(np.asarray(md))
    return np.concatenate(labels), np.concatenate(mind)


@functools.partial(jax.jit, static_argnames=("k",))
def _stats_block(x, labels, k):
    x = x.astype(jnp.float32)
    return (jax.ops.segment_sum(x, labels, num_segments=k),
            jax.ops.segment_sum(jnp.ones_like(labels, jnp.float32), labels,
                                num_segments=k))


def means(x, labels, c, block: int = BLOCK_ROWS):
    """The Lloyd update: each cluster's mean under ``labels``; a cluster
    with no row keeps its centroid from ``c``."""
    k = c.shape[0]
    sums = jnp.zeros(c.shape, jnp.float32)
    counts = jnp.zeros((k,), jnp.float32)
    for i in range(0, x.shape[0], block):
        s, n = _stats_block(jnp.asarray(x[i:i + block]),
                            jnp.asarray(labels[i:i + block]), k)
        sums, counts = sums + s, counts + n
    c = jnp.asarray(c, jnp.float32)
    return jnp.where(counts[:, None] > 0,
                     sums / jnp.maximum(counts, 1.0)[:, None], c)


def label_gap(x, c, got, want) -> float:
    """The widest gap by which a row's label ``got`` is farther than the
    reference's ``want``, relative to |x|² + |c|² (the scale of the
    rounding of the |x|² − 2x·c + |c|² expansion), over the rows where
    the two differ; 0 when none does.  Computed in float64."""
    got, want = np.asarray(got), np.asarray(want)
    bad = np.nonzero(got != want)[0]
    if bad.size == 0:
        return 0.0
    xb = np.asarray(x[bad] if isinstance(x, np.ndarray)
                    else jnp.take(x, jnp.asarray(bad), axis=0), np.float64)
    c64 = np.asarray(c, np.float64)
    kmax = c64.shape[0]
    if np.any((got[bad] < 0) | (got[bad] >= kmax)):
        return float("inf")          # no centroid has that label
    cg, cw = c64[got[bad]], c64[want[bad]]
    dg = np.sum((xb - cg) ** 2, axis=1)
    dw = np.sum((xb - cw) ** 2, axis=1)
    scale = np.sum(xb * xb, axis=1) + np.maximum(np.sum(cg * cg, axis=1),
                                                 np.sum(cw * cw, axis=1))
    return float(np.max(np.abs(dg - dw) / scale))
