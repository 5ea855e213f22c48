"""Pallas TPU kernel for the K-Means assignment step (Eq. 3).

This is the paper's stated per-iteration bottleneck: O(N*K) distance
evaluations.  The paper's CPU implementation avoids work with Hamerly's
bounds; on TPU the same insight does not transfer (data-dependent branching
starves the MXU — see DESIGN.md §Hardware-adaptation), so the TPU-native
formulation is a dense blocked computation

    dist^2(i, k) = |x_i|^2 - 2 <x_i, c_k> + |c_k|^2

where the cross term is an MXU matmul, tiled so each (TN x d) sample block
and (TK x d) centroid block live in VMEM, with a running (min, argmin)
reduction across centroid tiles.

Grid layout (v2): (R, n_tiles, k_tiles); the k dimension is the minor
(sequential) axis so the running min/argmin accumulation into the output
block (indexed by the restart and n tile only) touches consecutive grid
steps — the legal accumulation pattern on TPU.  The leading R axis runs
R centroid sets against shared or per-problem samples in one launch (the
batched slot); restart and sample tiles are independent, so both are
hinted `parallel` for Mosaic, with `arbitrary` only on the k sweep.
Tile sizes come from the VMEM-budget chooser in `tiles.py`.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import tiles
from repro.kernels.tiles import pad_to


def _assignment_kernel(x_ref, c_ref, csq_ref, labels_ref, mind_ref, *,
                       tk: int):
    """One (r, n_tile, k_tile) cell: distances + running min/argmin."""
    j = pl.program_id(2)

    x = x_ref[...]
    x = x.reshape(x.shape[-2], x.shape[-1])            # (TN, d)
    c = c_ref[0]                                       # (TK, d)
    csq = csq_ref[0]                                   # (1, TK)

    xf = x.astype(jnp.float32)
    xsq = jnp.sum(xf * xf, axis=-1, keepdims=True)     # (TN, 1)
    cross = jax.lax.dot_general(
        x, c, (((1,), (1,)), ((), ())),
        precision=tiles.mxu_precision(x, c),
        preferred_element_type=jnp.float32)            # (TN, TK) on the MXU
    dist = jnp.maximum(xsq - 2.0 * cross + csq, 0.0)

    local_arg = (jnp.argmin(dist, axis=-1).astype(jnp.int32)
                 + j * tk).reshape(labels_ref.shape)
    local_min = jnp.min(dist, axis=-1).reshape(mind_ref.shape)

    @pl.when(j == 0)
    def _init():
        labels_ref[...] = local_arg
        mind_ref[...] = local_min

    @pl.when(j > 0)
    def _accum():
        prev_min = mind_ref[...]
        prev_lab = labels_ref[...]
        better = local_min < prev_min                # strict: ties keep the
        labels_ref[...] = jnp.where(better, local_arg, prev_lab)
        mind_ref[...] = jnp.where(better, local_min, prev_min)


@functools.partial(jax.jit, static_argnames=("tn", "tk", "interpret"))
def _assignment_call(x, cs, *, tn: int, tk: int, interpret: bool):
    r, k = cs.shape[0], cs.shape[-2]
    n = x.shape[-2]
    x_batched = x.ndim == 3

    xp = pad_to(pad_to(x, -2, tn), -1, tiles.LANE)
    cp = pad_to(pad_to(cs, -2, tk), -1, tiles.LANE)

    cpf = cp.astype(jnp.float32)
    csq = jnp.sum(cpf * cpf, axis=-1)                  # (R, Kp)
    if cp.shape[-2] != k:
        # padded centroids must never win the argmin
        mask = jnp.arange(cp.shape[-2]) >= k
        csq = jnp.where(mask[None, :],
                        jnp.float32(jnp.finfo(jnp.float32).max), csq)
    csq = csq[:, None, :]                              # (R, 1, Kp) lane-major

    np_, dp = xp.shape[-2], xp.shape[-1]
    kp = cp.shape[-2]
    if not interpret:
        tiles.check_tiles(tn, np_, tk, kp)
    grid = (r, np_ // tn, kp // tk)

    if x_batched:
        x_spec = pl.BlockSpec((1, tn, dp), lambda rr, i, j: (rr, i, 0))
    else:
        x_spec = pl.BlockSpec((tn, dp), lambda rr, i, j: (i, 0))
    row = pl.BlockSpec((1, 1, tn), lambda rr, i, j: (rr, 0, i))

    labels, mind = pl.pallas_call(
        functools.partial(_assignment_kernel, tk=tk),
        grid=grid,
        in_specs=[
            x_spec,
            pl.BlockSpec((1, tk, dp), lambda rr, i, j: (rr, j, 0)),
            pl.BlockSpec((1, 1, tk), lambda rr, i, j: (rr, 0, j)),
        ],
        out_specs=[row, row],
        out_shape=[
            tiles.out_struct((r, 1, np_), jnp.int32, xp, cp),
            tiles.out_struct((r, 1, np_), jnp.float32, xp, cp),
        ],
        **tiles.compiler_params(
            "assignment", ("parallel", "parallel", "arbitrary"), tn=tn,
            tk=tk, kp=kp, dp=dp, itemsize=jnp.dtype(xp.dtype).itemsize,
            interpret=interpret),
        interpret=interpret,
    )(xp, cp, csq)
    return labels[:, 0, :n], mind[:, 0, :n]


def assignment_pallas(x: jax.Array, c: jax.Array, *,
                      tn=None, tk=None, interpret=None,
                      vmem_bytes=None):
    """Nearest-centroid assignment via the Pallas kernel.

    x: (N, d) f32/bf16 — or (R, N, d) per-problem; c: (K, d) — or
    (R, K, d) for R centroid sets in one launch.  Returns (labels i32,
    mind f32), each with a leading R axis when c is (R, K, d).

    Arbitrary N, K, d — inputs are padded to tile multiples; padded
    centroid rows get +inf squared norms so they are never selected.
    Tile sizes default to the VMEM-budget chooser (`tiles.choose_tiles`);
    ``interpret`` defaults to `tiles.interpret_default()`.
    """
    batched = c.ndim == 3
    if x.ndim == 3 and not batched:
        raise ValueError(
            f"per-problem x {x.shape} needs a per-problem c (R, K, d); "
            f"got {c.shape} — broadcast c yourself if the sets are shared")
    cs = c if batched else c[None]
    k, d = cs.shape[-2], cs.shape[-1]
    n = x.shape[-2]
    if tn is None or tk is None:
        ct, ck = tiles.choose_tiles(n, k, d, jnp.dtype(x.dtype).itemsize,
                                    kind="assignment", vmem_bytes=vmem_bytes)
        tn = ct if tn is None else tn
        tk = ck if tk is None else tk
    labels, mind = _assignment_call(x, cs, tn=tn, tk=tk,
                                    interpret=tiles.resolve_interpret(
                                        interpret))
    if not batched:
        return labels[0], mind[0]
    return labels, mind
