"""Pallas-kernel backends: separate-kernel (`pallas`) and single-pass
(`fused`) engines for Algorithm 1 (DESIGN.md §Kernels-v2).

`fused` consumes `fused_lloyd_pallas` v2: distances, argmin, cluster stats
and energy in ONE physical pass over X for *arbitrary* K — the kernel
k-tiles the centroid stream and carries the running argmin in VMEM
scratch, so the old K*d VMEM gate (and its fallback to the two-kernel
path) is gone.  Under the step-driven solver an accepted Algorithm-1
iteration therefore costs exactly one X read — the paper's Sec-2.1 cost
model realised on hardware at any K.

`pallas` drives the tiled assignment and one-hot-matmul update kernels as
two X passes per step — kept as the decomposed engine (predict-style
assignment reuse, per-kernel benchmarking) and as an independent check on
the fused path.

Both backends fill all three step slots natively (v2):

  * ``step``           — one fused pass / assignment+update pair;
  * ``batched_step``   — the kernels' leading-R grid runs R centroid
    sets per launch (multi-restart driver, the minibatch guard's R=2);
  * ``minibatch_step`` — the kernels' native row weights fold chunk
    weights into sums/counts/energy in the same pass, instead of the
    generic step + weighted-segment-sum fallback.

Precision policy (applied identically in both engines): the *compute*
dtype covers the distance math AND the X stream into the stats matmul —
X enters VMEM once per pass, in one dtype — while sums/counts/energy
accumulate in f32 on the MXU (`preferred_element_type`) and are returned
in the policy's accum dtype.  (v1 split the difference: assignment saw
the compute-cast X but the update kernel re-read the uncast original,
so the two engines' stats disagreed at bf16.)

`fused_bounds` is the fused engine carrying the shared bound contract of
`backends/bounds.py` (DESIGN.md §Bounds): squared per-(row, k-group)
lower bounds — one group per k-tile — and a squared upper bound ride into
VMEM next to each X row tile, and the kernel SKIPS whole centroid tiles
whose bound says no row can improve.  The drift maintenance between step
calls is the same triangle-inequality algebra as the elkan/yinyang CPU
backends, so it stays valid across accepted Anderson jumps and reverts.

The kernels decide their own execution mode (`tiles.interpret_default`):
compiled by Mosaic on a TPU, interpreted on any other host.  The TPU
lowering at real widths is checked without a chip by
tests/test_tpu_compile.py.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.backends import bounds as _bounds
from repro.core.backends.base import (Backend, Precision, StepResult,
                                      DEFAULT_PRECISION)
from repro.core.backends.bounds import BoundStats
from repro.core.lloyd import AssignResult
from repro.kernels import tiles
from repro.kernels.assignment import assignment_pallas
from repro.kernels.fused_lloyd import fused_lloyd_pallas
from repro.kernels.update import update_pallas

# Legacy names: the VMEM budget is no longer a gate (there is no fallback
# path) — it seeds the tile chooser's footprint model (kernels/tiles.py).
FUSED_VMEM_BYTES = tiles.DEFAULT_VMEM_BUDGET
FUSED_MAX_KD = FUSED_VMEM_BYTES // 4


def _assign_fn(x, c):
    labels, mind = assignment_pallas(x, c)
    return AssignResult(labels, mind)


def _stats_fn(x, labels, k):
    return update_pallas(x, labels, k)


def _pack(precision: Precision, labels, mind, sums, counts, energy=None):
    acc = precision.accum_dtype
    mind = mind.astype(acc)
    if energy is None:
        energy = jnp.sum(mind, axis=-1)
    else:
        energy = energy.astype(acc)
    return StepResult(labels, mind, sums.astype(acc), counts.astype(acc),
                      energy)


# ---------------------------------------------------------------------------
# Split two-kernel engine ("pallas")
# ---------------------------------------------------------------------------

def _split_step(precision: Precision):
    def step_fn(x, c, k, carry):
        xc = precision.compute_cast(x)
        cc = precision.compute_cast(c)
        labels, mind = assignment_pallas(xc, cc)
        # policy: the stats matmul reads the same compute-cast X as the
        # distance pass (one X stream, one dtype), accumulating in f32
        sums, counts = update_pallas(xc, labels, k)
        return _pack(precision, labels, mind, sums, counts), carry
    return step_fn


def _split_batched(precision: Precision):
    def batched_step_fn(x, cs, k, carries, w=None):
        if w is not None:
            # the split engine's update kernel takes one (N,) weight
            # vector; per-problem weights route through the vmapped
            # minibatch slot (one launch per problem — the fused engine
            # is the batched-weighted fast path)
            mb = _split_minibatch(precision)
            return jax.vmap(
                lambda xx, cc, ww, cr: mb(xx, cc, k, ww, cr),
                in_axes=(0 if x.ndim == 3 else None, 0, 0, 0))(
                    x, cs, w, carries)
        xc = precision.compute_cast(x)
        cc = precision.compute_cast(cs)
        labels, mind = assignment_pallas(xc, cc)
        sums, counts = update_pallas(xc, labels, k)
        return _pack(precision, labels, mind, sums, counts), carries
    return batched_step_fn


def _split_minibatch(precision: Precision):
    def minibatch_step_fn(x, c, k, w, carry):
        xc = precision.compute_cast(x)
        cc = precision.compute_cast(c)
        labels, mind = assignment_pallas(xc, cc)
        sums, counts = update_pallas(xc, labels, k, w=w)
        acc = precision.accum_dtype
        energy = jnp.sum(mind.astype(acc) * w.astype(acc))
        return _pack(precision, labels, mind, sums, counts, energy), carry
    return minibatch_step_fn


def pallas_backend(precision: Precision = DEFAULT_PRECISION) -> Backend:
    return Backend(name="pallas",
                   step_fn=_split_step(precision),
                   batched_step_fn=_split_batched(precision),
                   minibatch_step_fn=_split_minibatch(precision),
                   stats_fn=_stats_fn,
                   assign_fn=_assign_fn,
                   precision=precision)


# ---------------------------------------------------------------------------
# Single-pass engine ("fused")
# ---------------------------------------------------------------------------

def _fused_step(precision: Precision):
    def step_fn(x, c, k, carry):
        xc = precision.compute_cast(x)
        cc = precision.compute_cast(c)
        labels, mind, sums, counts, energy = fused_lloyd_pallas(
            xc, cc)
        return _pack(precision, labels, mind, sums, counts, energy), carry
    return step_fn


def _fused_batched(precision: Precision):
    def batched_step_fn(x, cs, k, carries, w=None):
        xc = precision.compute_cast(x)
        cc = precision.compute_cast(cs)
        labels, mind, sums, counts, energy = fused_lloyd_pallas(
            xc, cc, w)
        return _pack(precision, labels, mind, sums, counts, energy), carries
    return batched_step_fn


def _fused_minibatch(precision: Precision):
    def minibatch_step_fn(x, c, k, w, carry):
        xc = precision.compute_cast(x)
        cc = precision.compute_cast(c)
        labels, mind, sums, counts, energy = fused_lloyd_pallas(
            xc, cc, w)
        return _pack(precision, labels, mind, sums, counts, energy), carry
    return minibatch_step_fn


def fused_backend(precision: Precision = DEFAULT_PRECISION) -> Backend:
    return Backend(name="fused",
                   step_fn=_fused_step(precision),
                   batched_step_fn=_fused_batched(precision),
                   minibatch_step_fn=_fused_minibatch(precision),
                   stats_fn=_stats_fn,
                   assign_fn=_assign_fn,
                   precision=precision)


# ---------------------------------------------------------------------------
# Tile-skipping single-pass engine ("fused_bounds")
# ---------------------------------------------------------------------------

def fused_bounds_backend(precision: Precision = DEFAULT_PRECISION,
                         group_size=None) -> Backend:
    """The fused kernel consuming group lower bounds to skip k tiles.

    The carry is the shared contract of `backends/bounds.py` with groups
    sized to the kernel's k tile (one group per tile, gs == tk), so the
    drift-maintained (N, G) lower bounds land in VMEM as exactly the
    per-(row-tile, k-tile) skip predicate.  The bound algebra runs in
    Euclidean space outside the kernel; the kernel works in squared
    space (lb² / ub², with inf² = inf on the first, bound-free step).

    An explicit ``group_size`` is rounded up to the f32 sublane so the
    k tile stays Mosaic-tileable.  Default sizing follows the "tile"
    policy — for K <= MAX_TILE that is ONE group (graceful degradation
    to the plain fused kernel plus bound upkeep); pass a smaller
    ``group_size`` to get real skipping at small K.
    """

    def gs_of(k):
        gs = _bounds.resolve_group_size(k, group_size, "tile")
        return tiles.round_up(gs, tiles.sublane(4))

    def init_carry_fn(x, c, k):
        return _bounds.init_carry(x, c, k, gs_of(k))

    def _prep(labels0, upper, lower, c_last, cf, g, gs):
        drift = _bounds.centroid_drift(cf, c_last)
        upper, lower = _bounds.drift_update(labels0, upper, lower,
                                            drift, g, gs)
        lb_sq = jnp.square(jnp.maximum(lower, 0.0))
        ub_sq = jnp.square(upper)
        return lb_sq, ub_sq

    def _run(x, c, k, carry, w=None, batched=False):
        labels0, upper, lower, c_last, _ = carry
        g, gs = _bounds.group_layout(k, gs_of(k))
        xc = precision.compute_cast(x)
        cc = precision.compute_cast(c)
        cf = cc.astype(jnp.float32)
        prep = jax.vmap(_prep, in_axes=(0, 0, 0, 0, 0, None, None)) \
            if batched else _prep
        lb_sq, ub_sq = prep(labels0, upper, lower, c_last, cf, g, gs)
        labels, mind, sums, counts, energy, gmin_sq, skipped = \
            fused_lloyd_pallas(xc, cc, w, tk=gs,
                               bounds=(labels0, lb_sq, ub_sq))
        u_new = jnp.sqrt(mind)
        lower_new = jnp.sqrt(gmin_sq)
        stats = BoundStats(skipped, skipped)
        new_carry = (labels, u_new, lower_new, cf, stats)
        return _pack(precision, labels, mind, sums, counts, energy), \
            new_carry

    def step_fn(x, c, k, carry):
        return _run(x, c, k, carry)

    def batched_step_fn(x, cs, k, carries, w=None):
        return _run(x, cs, k, carries, w=w, batched=True)

    def minibatch_step_fn(x, c, k, w, carry):
        return _run(x, c, k, carry, w=w)

    return Backend(name="fused_bounds",
                   step_fn=step_fn,
                   batched_step_fn=batched_step_fn,
                   minibatch_step_fn=minibatch_step_fn,
                   stats_fn=_stats_fn,
                   assign_fn=_assign_fn,
                   init_carry_fn=init_carry_fn,
                   precision=precision)
