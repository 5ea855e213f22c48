"""Fused Pallas TPU kernel v2: one full Lloyd pass reading X exactly once,
for arbitrary K (DESIGN.md §Kernels-v2).

A Lloyd iteration as separate assignment + update + energy passes streams X
from HBM two to three times; the per-iteration work is memory-bound for
small/medium K (arithmetic intensity ~ K flops/byte for assignment), so
fusing the three into a single pass halves the dominant roofline term.

v1 of this kernel held the full (K, d) centroid block in VMEM and fell
back to the two-kernel path past an 8 MB gate.  v2 k-tiles instead: the
grid is (R, n_tiles, k_tiles) with k minor, and each X row tile is
resident in VMEM for the whole k sweep —

    1. distances of the (TN x d) X tile against one (TK x d) centroid
       tile per grid step (MXU), folding a running (min, argmin) held in
       VMEM *scratch* across the k tiles;
    2. at the final k tile the assignment of the X tile is complete:
       emit labels/min-dist and accumulate the weighted one-hot cluster
       stats and energy — while the X block is still resident, so X is
       read from HBM exactly once regardless of K.

The (K, d) f32 stats accumulator stays VMEM-resident across the grid
(k-tiling the *inputs* is what removed the old cliff; the accumulator's
K·d·4 bytes, double-buffered, is the remaining — much later — limit:
`tiles.compiler_params` raises Mosaic's VMEM limit to fit it, and
refuses a shape past the chip's VMEM).

Per-row and per-centroid vectors are lane-major, (R, 1, N) and
(R, 1, K), so every block obeys Mosaic's (8, 128) rule for any R
(`tiles` module docstring).

Row weights are native: every row's contribution to sums/counts/energy is
scaled by its weight, which (a) makes this kernel the streaming
`minibatch_step` (padding rows carry weight 0 and vanish exactly — no
post-hoc subtraction) and (b) is how the wrapper handles its own
tile-padding rows.  labels/min_sqdist stay per-row and unweighted.

The leading R grid axis batches restarts: c of shape (R, K, d) runs R
centroid sets against shared (N, d) or per-problem (R, N, d) samples in
one kernel launch — the native `batched_step` for the multi-restart
driver and the minibatch validation guard's R = 2 step.

Outputs: labels (N,), min_sqdist (N,), sums (K,d), counts (K,), energy ()
— with a leading R axis when c is (R, K, d).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import tiles
from repro.kernels.tiles import pad_to


def _distances(x_ref, c_ref, csq_ref):
    """(x tile in f32, (TN, TK) squared distances) of one grid cell."""
    x = x_ref[...]
    x = x.reshape(x.shape[-2], x.shape[-1])            # (TN, d)
    c = c_ref[0]                                       # (TK, d)
    csq = csq_ref[0]                                   # (1, TK)
    xf = x.astype(jnp.float32)
    xsq = jnp.sum(xf * xf, axis=-1, keepdims=True)
    cross = jax.lax.dot_general(
        x, c, (((1,), (1,)), ((), ())),
        precision=tiles.mxu_precision(x, c),
        preferred_element_type=jnp.float32)            # (TN, TK) on the MXU
    return xf, jnp.maximum(xsq - 2.0 * cross + csq, 0.0)


def _emit(i, labels, mind, xf, w_ref, labels_ref, mind_ref, sums_ref,
          counts_ref, energy_ref, *, tk: int, nk: int):
    """Final k tile: the X tile's assignment is complete and the block is
    still resident — write labels/min-dist and fold the weighted one-hot
    stats and energy into the grid-resident accumulators."""
    w = w_ref[...].reshape(-1)                         # (TN,) f32
    labels_ref[...] = labels.reshape(labels_ref.shape)
    mind_ref[...] = mind.reshape(mind_ref.shape)

    @pl.when(i == 0)
    def _init():
        sums_ref[...] = jnp.zeros(sums_ref.shape, sums_ref.dtype)
        counts_ref[...] = jnp.zeros(counts_ref.shape, counts_ref.dtype)
        energy_ref[...] = jnp.zeros(energy_ref.shape, energy_ref.dtype)

    tn = labels.shape[0]

    def _accum_tile(off):
        # Weighted one-hot restricted to one centroid tile keeps the
        # intermediate at (TN, TK) — never (TN, K).
        ks = jax.lax.broadcasted_iota(jnp.int32, (tn, tk), 1) + off
        onehot = jnp.where(labels[:, None] == ks, w[:, None],
                           jnp.float32(0.0))
        psum = jax.lax.dot_general(
            onehot, xf, (((0,), (0,)), ((), ())),
            precision=tiles.mxu_precision(onehot, xf),
            preferred_element_type=jnp.float32)        # (TK, d) on the MXU
        sums_ref[0, pl.ds(off, tk), :] += psum
        counts_ref[0, :, pl.ds(off, tk)] += jnp.sum(onehot, axis=0,
                                                    keepdims=True)

    if nk == 1:
        _accum_tile(0)
    else:
        def body(jj, carry):
            # tk is a lane multiple here, so the counts slice is aligned
            _accum_tile(pl.multiple_of(jj * tk, tk))
            return carry
        jax.lax.fori_loop(0, nk, body, 0)
    energy_ref[...] += jnp.sum(mind * w).reshape(energy_ref.shape)


def _fused_kernel(x_ref, c_ref, csq_ref, w_ref,
                  labels_ref, mind_ref, sums_ref, counts_ref, energy_ref,
                  mind_s, amin_s, *, tk: int, nk: int):
    i = pl.program_id(1)          # X row tile (sequential: stats accumulate)
    j = pl.program_id(2)          # centroid tile (minor: argmin sweep)

    xf, dist = _distances(x_ref, c_ref, csq_ref)
    local_min = jnp.min(dist, axis=-1)                 # (TN,)
    local_arg = jnp.argmin(dist, axis=-1).astype(jnp.int32) + j * tk

    @pl.when(j == 0)
    def _seed():
        mind_s[...] = local_min
        amin_s[...] = local_arg

    @pl.when(j > 0)
    def _sweep():
        better = local_min < mind_s[...]     # strict: ties keep the low tile
        amin_s[...] = jnp.where(better, local_arg, amin_s[...])
        mind_s[...] = jnp.where(better, local_min, mind_s[...])

    @pl.when(j == nk - 1)
    def _final():
        _emit(i, amin_s[...], mind_s[...], xf, w_ref, labels_ref, mind_ref,
              sums_ref, counts_ref, energy_ref, tk=tk, nk=nk)


def _fused_bounds_kernel(x_ref, c_ref, csq_ref, w_ref, lb_ref, ub_ref,
                         lab0_ref, labels_ref, mind_ref, sums_ref,
                         counts_ref, energy_ref, gmin_ref, skip_ref,
                         mind_s, amin_s, *, tk: int, nk: int):
    """The fused kernel with a per-(row-tile, k-tile) skip predicate.

    Extra inputs per X row tile: the squared inclusive lower bound of
    the current k-tile's group, lb (1, TN) — the bounds are laid out
    group-major, (R, G, 1, N), so the BlockSpec selects group j — the
    squared upper bound ub (1, TN), and the previous labels (1, TN).  A
    k tile j is computed only when ANY row of the tile has lb <= ub (the
    non-strict predicate is what guarantees a row's owner tile is always
    computed: lb_owner <= d(x, c_a)^2 <= ub); otherwise the whole
    distance block, and the C tile's use, are skipped under `pl.when`
    and the drift-maintained bound is passed through as the new group
    min.  The running min is *seeded* with (ub, previous label), so a
    row all of whose non-owner tiles are skipped still emits its exact
    min-dist: the computed owner tile can only tighten the seed, and if
    it does not, ub was already exactly d(x, c_a)^2.

    Emits the fused kernel's five outputs plus the updated squared group
    mins (group-major, like lb) and a skipped-tile counter (one per
    restart), which the wrapper normalises to a fraction of the
    (row-tile x k-tile) grid.
    """
    i = pl.program_id(1)
    j = pl.program_id(2)

    lb = lb_ref[0, 0]                                          # (1, TN)
    pred = jnp.max(jnp.where(lb <= ub_ref[0], 1.0, 0.0)) > 0.0

    @pl.when(j == 0)
    def _seed():
        mind_s[...] = ub_ref[...].reshape(-1)
        amin_s[...] = lab0_ref[...].reshape(-1)

    @pl.when(jnp.logical_and(i == 0, j == 0))
    def _zero_skip():
        skip_ref[...] = jnp.zeros(skip_ref.shape, skip_ref.dtype)

    @pl.when(pred)
    def _compute():
        _, dist = _distances(x_ref, c_ref, csq_ref)
        local_min = jnp.min(dist, axis=-1)
        local_arg = jnp.argmin(dist, axis=-1).astype(jnp.int32) + j * tk
        # strict <: a tie keeps the seed (the row's standing assignment)
        better = local_min < mind_s[...]
        amin_s[...] = jnp.where(better, local_arg, amin_s[...])
        mind_s[...] = jnp.where(better, local_min, mind_s[...])
        gmin_ref[...] = local_min.reshape(gmin_ref.shape)

    @pl.when(jnp.logical_not(pred))
    def _skip():
        skip_ref[...] += jnp.ones(skip_ref.shape, skip_ref.dtype)
        # the drift-maintained bound stays the best known group min
        gmin_ref[...] = lb_ref[...]

    @pl.when(j == nk - 1)
    def _final():
        x = x_ref[...]
        xf = x.reshape(x.shape[-2], x.shape[-1]).astype(jnp.float32)
        _emit(i, amin_s[...], mind_s[...], xf, w_ref, labels_ref, mind_ref,
              sums_ref, counts_ref, energy_ref, tk=tk, nk=nk)


def _rows(v, tn, value=0.0):
    """(..., N) per-row vector -> lane-major (B, 1, Np) kernel operand."""
    vp = pad_to(v, -1, tn, value=value)
    return vp.reshape((-1, 1, vp.shape[-1]))


def _operands(x, cs, w, *, tn: int, tk: int):
    """Padded operands and BlockSpecs shared by both fused kernels."""
    k = cs.shape[-2]
    xp = pad_to(pad_to(x, -2, tn), -1, tiles.LANE)
    cp = pad_to(pad_to(cs, -2, tk), -1, tiles.LANE)
    wp = _rows(w, tn)            # tile-padding rows weigh 0 -> inert

    cpf = cp.astype(jnp.float32)
    csq = jnp.sum(cpf * cpf, axis=-1)                  # (R, Kp)
    if cp.shape[-2] != k:
        # padded centroid rows must never win the argmin
        mask = jnp.arange(cp.shape[-2]) >= k
        csq = jnp.where(mask[None, :],
                        jnp.float32(jnp.finfo(jnp.float32).max), csq)
    csq = csq[:, None, :]                              # (R, 1, Kp)

    dp = xp.shape[-1]
    if x.ndim == 3:
        x_spec = pl.BlockSpec((1, tn, dp), lambda rr, i, j: (rr, i, 0))
    else:
        x_spec = pl.BlockSpec((tn, dp), lambda rr, i, j: (i, 0))
    if wp.shape[0] > 1:
        w_spec = pl.BlockSpec((1, 1, tn), lambda rr, i, j: (rr, 0, i))
    else:
        w_spec = pl.BlockSpec((1, 1, tn), lambda rr, i, j: (0, 0, i))
    specs = [x_spec,
             pl.BlockSpec((1, tk, dp), lambda rr, i, j: (rr, j, 0)),
             pl.BlockSpec((1, 1, tk), lambda rr, i, j: (rr, 0, j)),
             w_spec]
    return [xp, cp, csq, wp], specs


def _stat_outputs(r, np_, kp, dp, tn, operands):
    """(out_specs, out_shape) of the five outputs both kernels emit."""
    row = pl.BlockSpec((1, 1, tn), lambda rr, i, j: (rr, 0, i))
    specs = [row, row,
             pl.BlockSpec((1, kp, dp), lambda rr, i, j: (rr, 0, 0)),
             pl.BlockSpec((1, 1, kp), lambda rr, i, j: (rr, 0, 0)),
             pl.BlockSpec((1, 1, 1), lambda rr, i, j: (rr, 0, 0))]
    shapes = [tiles.out_struct((r, 1, np_), jnp.int32, *operands),
              tiles.out_struct((r, 1, np_), jnp.float32, *operands),
              tiles.out_struct((r, kp, dp), jnp.float32, *operands),
              tiles.out_struct((r, 1, kp), jnp.float32, *operands),
              tiles.out_struct((r, 1, 1), jnp.float32, *operands)]
    return specs, shapes


def _launch(kind, kernel, operands, in_specs, out_specs, out_shape, *,
            r, tn, tk, interpret):
    xp, cp = operands[0], operands[1]
    np_, dp, kp = xp.shape[-2], xp.shape[-1], cp.shape[-2]
    if not interpret:
        tiles.check_tiles(tn, np_, tk, kp)
    nk = kp // tk
    return pl.pallas_call(
        functools.partial(kernel, tk=tk, nk=nk),
        grid=(r, np_ // tn, nk),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((tn,), jnp.float32),            # running min
            pltpu.VMEM((tn,), jnp.int32),              # running argmin
        ],
        # restarts are independent; stats accumulate across i; the k
        # sweep folds scratch sequentially
        **tiles.compiler_params(
            kind, ("parallel", "arbitrary", "arbitrary"), tn=tn, tk=tk,
            kp=kp, dp=dp, itemsize=jnp.dtype(xp.dtype).itemsize,
            interpret=interpret),
        interpret=interpret,
    )(*operands)


@functools.partial(jax.jit, static_argnames=("tn", "tk", "interpret"))
def _fused_bounds_call(x, cs, w, lab0, lb_sq, ub_sq, *, tn: int, tk: int,
                       interpret: bool):
    r = cs.shape[0]
    operands, in_specs = _operands(x, cs, w, tn=tn, tk=tk)
    xp, cp = operands[0], operands[1]
    np_, dp, kp = xp.shape[-2], xp.shape[-1], cp.shape[-2]
    g = kp // tk
    assert lb_sq.shape[-1] == g, (lb_sq.shape, g)
    # padding rows must never force a tile's computation: their lower
    # bound is +max and their upper bound 0, so lb <= ub is always false
    fmax = jnp.float32(jnp.finfo(jnp.float32).max)
    lbp = pad_to(lb_sq, -2, tn, value=fmax)
    lbp = jnp.swapaxes(lbp, -1, -2)[:, :, None, :]     # (R, G, 1, Np)
    operands += [lbp, _rows(ub_sq, tn), _rows(lab0, tn)]
    row = pl.BlockSpec((1, 1, tn), lambda rr, i, j: (rr, 0, i))
    group = pl.BlockSpec((1, 1, 1, tn), lambda rr, i, j: (rr, j, 0, i))
    out_specs, out_shape = _stat_outputs(r, np_, kp, dp, tn, operands)
    out_specs += [group, pl.BlockSpec((1, 1, 1), lambda rr, i, j: (rr, 0, 0))]
    out_shape += [tiles.out_struct((r, g, 1, np_), jnp.float32, *operands),
                  tiles.out_struct((r, 1, 1), jnp.float32, *operands)]
    out = _launch("fused_bounds", _fused_bounds_kernel, operands,
                  in_specs + [group, row, row], out_specs, out_shape,
                  r=r, tn=tn, tk=tk, interpret=interpret)
    gmin = jnp.swapaxes(out[5][:, :, 0, :], -1, -2)    # (R, Np, G)
    return (*out[:5], gmin, out[6])


@functools.partial(jax.jit, static_argnames=("tn", "tk", "interpret"))
def _fused_call(x, cs, w, *, tn: int, tk: int, interpret: bool):
    r = cs.shape[0]
    operands, in_specs = _operands(x, cs, w, tn=tn, tk=tk)
    xp, cp = operands[0], operands[1]
    np_, dp, kp = xp.shape[-2], xp.shape[-1], cp.shape[-2]
    out_specs, out_shape = _stat_outputs(r, np_, kp, dp, tn, operands)
    return _launch("fused", _fused_kernel, operands, in_specs, out_specs,
                   out_shape, r=r, tn=tn, tk=tk, interpret=interpret)


def fused_lloyd_pallas(x: jax.Array, c: jax.Array, w=None, *,
                       tn=None, tk=None, interpret=None,
                       vmem_bytes=None, bounds=None):
    """Fused assignment+update+energy in ONE physical pass over x.

    x: (N, d) — or (R, N, d) for per-problem batches; c: (K, d) — or
    (R, K, d) to run R centroid sets in one launch (the batched slot).
    w: optional (N,) row weights folded into sums/counts/energy (the
    minibatch slot; labels/min_sqdist stay unweighted) — or (R, N)
    per-problem weights in the batched case, the masking column of the
    hierarchy engine's padded segments (DESIGN.md §Hierarchy).

    Returns (labels i32, min_sqdist f32, sums (K,d) f32, counts (K,) f32,
    energy () f32), each gaining a leading R axis when c is (R, K, d).

    Tile sizes default to `tiles.choose_tiles` (VMEM-budget-aware; k is
    tiled, so arbitrary K takes this path — there is no fallback; a
    shape whose resident accumulator exceeds the chip's VMEM raises).
    ``interpret`` defaults to `tiles.interpret_default()`: compiled on a
    TPU, interpreted elsewhere.

    ``bounds=(labels0, lb_sq, ub_sq)`` switches to the tile-skipping
    variant (DESIGN.md §Bounds): labels0 (N,) i32 is the standing
    assignment, lb_sq (N, G) the SQUARED inclusive group lower bounds
    with one group per k-tile (G = ceil(K/tk) — pass a matching ``tk``),
    and ub_sq (N,) the squared upper bound on the assigned distance.  A
    whole centroid tile is skipped when no row of the X tile can beat
    its bound; two extra outputs are appended: the updated squared group
    mins (N, G) and the skipped-tile fraction () of the (row-tile x
    k-tile) grid.  Each bound input gains a leading R axis when c does.
    """
    batched = c.ndim == 3
    if x.ndim == 3 and not batched:
        raise ValueError(
            f"per-problem x {x.shape} needs a per-problem c (R, K, d); "
            f"got {c.shape} — broadcast c yourself if the sets are shared")
    cs = c if batched else c[None]
    k, d = cs.shape[-2], cs.shape[-1]
    n = x.shape[-2]
    if w is None:
        w = jnp.ones((n,), jnp.float32)
    else:
        w = w.astype(jnp.float32)
    if w.ndim == 2 and not batched:
        raise ValueError(
            f"per-problem w {w.shape} needs a per-problem c (R, K, d); "
            f"got {c.shape}")
    interpret = tiles.resolve_interpret(interpret)
    kind = "fused" if bounds is None else "fused_bounds"
    if tn is None or tk is None:
        ct, ck = tiles.choose_tiles(n, k, d, jnp.dtype(x.dtype).itemsize,
                                    kind=kind, vmem_bytes=vmem_bytes)
        tn = ct if tn is None else tn
        tk = ck if tk is None else tk

    if bounds is None:
        labels, mind, sums, counts, energy = _fused_call(
            x, cs, w, tn=tn, tk=tk, interpret=interpret)
    else:
        lab0, lb_sq, ub_sq = bounds
        if not batched:
            lab0, lb_sq, ub_sq = lab0[None], lb_sq[None], ub_sq[None]
        g = -(-tiles.round_up(k, tk) // tk)
        if lb_sq.shape[-1] != g:
            raise ValueError(
                f"lb_sq has {lb_sq.shape[-1]} groups but tk={tk} tiles "
                f"K={k} into {g} — group size and k tile must agree")
        labels, mind, sums, counts, energy, gmin, skipped = \
            _fused_bounds_call(x, cs, w, lab0, lb_sq.astype(jnp.float32),
                               ub_sq.astype(jnp.float32),
                               tn=tn, tk=tk, interpret=interpret)
        n_cells = (gmin.shape[-2] // tn) * g
        skipped_frac = skipped[:, 0, 0] / jnp.float32(n_cells)
        gmin = gmin[:, :n, :]

    labels, mind = labels[:, 0, :n], mind[:, 0, :n]
    sums, counts = sums[:, :k, :d], counts[:, 0, :k]
    energy = energy[:, 0, 0]
    if bounds is not None:
        if not batched:
            return (labels[0], mind[0], sums[0], counts[0], energy[0],
                    gmin[0], skipped_frac[0])
        return labels, mind, sums, counts, energy, gmin, skipped_frac
    if not batched:
        return labels[0], mind[0], sums[0], counts[0], energy[0]
    return labels, mind, sums, counts, energy
