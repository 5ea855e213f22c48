"""Pallas TPU kernel for the K-Means update step (Eq. 4): segment-sum.

Scatter-add is hostile to the TPU's vector units; the TPU-native analogue is
a one-hot matmul on the MXU:

    sums[k, :]  = sum_i w_i * 1[labels_i == k] * x_i   =  (w*onehot)^T @ X
    counts[k]   = sum_i w_i * 1[labels_i == k]

tiled over samples (grid minor axis, sequential accumulation into the
(TK x d) output block) and over centroid tiles, with a leading R axis for
batched label sets (v2).  Row weights are native — the weighted one-hot
costs nothing extra on the MXU, which is what lets the `pallas` backend's
minibatch step skip the separate weighted segment-sum pass the generic
fallback pays.  Restart and centroid tiles own independent output blocks
(`parallel`); only the sample sweep accumulates (`arbitrary`).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import tiles
from repro.kernels.tiles import pad_to


def _update_kernel(labels_ref, x_ref, w_ref, sums_ref, counts_ref, *,
                   tk: int):
    jk = pl.program_id(1)         # centroid tile (owns the output block)
    i = pl.program_id(2)          # sample tile (minor, sequential)

    labels = labels_ref[...].reshape(-1)               # (TN,)
    x = x_ref[...]
    x = x.reshape(x.shape[-2], x.shape[-1]).astype(jnp.float32)
    w = w_ref[...].reshape(-1)                         # (TN,) f32

    local = labels - jk * tk              # position within this tile
    ks = jax.lax.broadcasted_iota(jnp.int32, (labels.shape[0], tk), 1)
    onehot = jnp.where(local[:, None] == ks, w[:, None],
                       jnp.float32(0.0))               # weighted (TN, TK)

    psum = jax.lax.dot_general(
        onehot, x, (((0,), (0,)), ((), ())),
        precision=tiles.mxu_precision(onehot, x),
        preferred_element_type=jnp.float32)            # (TK, d) on the MXU
    pcount = jnp.sum(onehot, axis=0, keepdims=True)    # (1, TK)

    @pl.when(i == 0)
    def _init():
        sums_ref[...] = psum.reshape(sums_ref.shape)
        counts_ref[...] = pcount.reshape(counts_ref.shape)

    @pl.when(i > 0)
    def _accum():
        sums_ref[...] += psum.reshape(sums_ref.shape)
        counts_ref[...] += pcount.reshape(counts_ref.shape)


@functools.partial(jax.jit, static_argnames=("k", "tn", "tk", "interpret"))
def _update_call(x, labels, w, *, k: int, tn: int, tk: int, interpret: bool):
    r = labels.shape[0]
    x_batched = x.ndim == 3

    xp = pad_to(pad_to(x, -2, tn), -1, tiles.LANE)
    # lane-major (R, 1, Np) labels and (1, Np) weights; padded rows get
    # label -1 and weight 0
    lp = pad_to(labels.astype(jnp.int32), -1, tn, value=-1)[:, None, :]
    wp = pad_to(w, 0, tn)[None, :]

    np_, dp = xp.shape[-2], xp.shape[-1]
    kp = tiles.round_up(k, tk)
    if not interpret:
        tiles.check_tiles(tn, np_, tk, kp)
    grid = (r, kp // tk, np_ // tn)

    if x_batched:
        x_spec = pl.BlockSpec((1, tn, dp), lambda rr, jk, i: (rr, i, 0))
    else:
        x_spec = pl.BlockSpec((tn, dp), lambda rr, jk, i: (i, 0))

    sums, counts = pl.pallas_call(
        functools.partial(_update_kernel, tk=tk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, tn), lambda rr, jk, i: (rr, 0, i)),
            x_spec,
            pl.BlockSpec((1, tn), lambda rr, jk, i: (0, i)),
        ],
        out_specs=[
            pl.BlockSpec((1, tk, dp), lambda rr, jk, i: (rr, jk, 0)),
            pl.BlockSpec((1, 1, tk), lambda rr, jk, i: (rr, 0, jk)),
        ],
        out_shape=[
            tiles.out_struct((r, kp, dp), jnp.float32, lp, xp, wp),
            tiles.out_struct((r, 1, kp), jnp.float32, lp, xp, wp),
        ],
        **tiles.compiler_params(
            "update", ("parallel", "parallel", "arbitrary"), tn=tn, tk=tk,
            kp=kp, dp=dp, itemsize=jnp.dtype(xp.dtype).itemsize,
            interpret=interpret),
        interpret=interpret,
    )(lp, xp, wp)
    return sums[:, :k, :x.shape[-1]], counts[:, 0, :k]


def update_pallas(x: jax.Array, labels: jax.Array, k: int, *,
                  w=None, tn=None, tk=None, interpret=None,
                  vmem_bytes=None):
    """Per-cluster sums (K,d) f32 and counts (K,) f32 via the Pallas kernel.

    labels (N,) — or (R, N) for R label sets over shared (N, d) or
    per-problem (R, N, d) samples, adding a leading R axis to the outputs.
    w: optional (N,) row weights scaling each row's contribution (the
    weighted segment-sum of the minibatch step).  Tile-padded sample rows
    get label -1 *and* weight 0, so they land in no cluster.
    ``interpret`` defaults to `tiles.interpret_default()`.
    """
    batched = labels.ndim == 2
    if x.ndim == 3 and not batched:
        raise ValueError(
            f"per-problem x {x.shape} needs per-problem labels (R, N); "
            f"got {labels.shape}")
    ls = labels if batched else labels[None]
    n, d = x.shape[-2], x.shape[-1]
    if w is None:
        w = jnp.ones((n,), jnp.float32)
    else:
        w = w.astype(jnp.float32)
    if tn is None or tk is None:
        ct, ck = tiles.choose_tiles(n, k, d, jnp.dtype(x.dtype).itemsize,
                                    kind="update", vmem_bytes=vmem_bytes)
        tn = ct if tn is None else tn
        tk = ck if tk is None else tk
    sums, counts = _update_call(x, ls, w, k=k, tn=tn, tk=tk,
                                interpret=tiles.resolve_interpret(interpret))
    if not batched:
        return sums[0], counts[0]
    return sums, counts
