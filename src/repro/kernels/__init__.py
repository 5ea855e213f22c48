"""Pallas TPU kernel engine v2 (DESIGN.md §Kernels-v2).

    tiles.py       — tile chooser, VMEM limits, execution mode, vma
    assignment.py  — tiled argmin-distance kernel (Eq. 3)
    update.py      — weighted one-hot segment-sum kernel (Eq. 4)
    fused_lloyd.py — single-pass fused step: one X read per iteration,
                     arbitrary K (k-tiled), native weights + R batching
    ops.py         — jit'd dispatch (pallas vs jnp oracle)
    ref.py         — pure-jnp semantic oracles for every kernel

All kernels accept an optional leading R axis on the centroid (and label)
inputs — one launch runs R problems.  The stats-producing kernels
(fused_lloyd, update) additionally take optional per-row weights that
fold into the cluster statistics and the energy; assignment is
weight-free (labels/min-dist are per-row by definition).
"""
