"""Tile sizing, VMEM limits and execution mode for the Pallas kernel
engine (DESIGN.md §Kernels-v2).

Every kernel in this package streams X in (TN x d) row tiles and C in
(TK x d) centroid tiles.  `choose_tiles` picks, for a problem shape and
the compute dtype's byte width, the largest (TN, TK) whose working set
fits the tile budget, shrinking the k tile first (k-tiling is the lever
that removed the fused kernel's VMEM cliff; see fused_lloyd.py).

Tiles are lane-legal for the Mosaic compiler.  Per-row vectors (labels,
min-dist, weights, bounds) and per-centroid vectors (|c|², counts) are
laid out lane-major, (R, 1, N) and (R, 1, K), so TN and TK sit on the
128-wide lane axis: a tile either covers the whole padded extent (one
tile) or is a multiple of ``LANE``.  `check_tiles` enforces that for
explicit tiles on the compiled path; interpret mode has no such rule.

The tile budget is ``DEFAULT_VMEM_BUDGET`` (8 MiB of the 16 MiB default
scoped VMEM of a v5e TensorCore).  The footprint model counts, per
kernel kind:

  * double-buffered input and output tiles (X, C, |c|², row weights,
    labels, min-dist),
  * the distance / one-hot compute blocks (TN x TK f32),
  * the *resident* accumulators: the fused kernels keep the full
    (K, d) f32 cluster stats (and the (1, K) counts) in VMEM across the
    whole grid, double-buffered like every output block, so they are a
    fixed term no tile size can shrink.

The chooser charges the resident term only up to half the budget.  The
rest of the real footprint is paid for by `compiler_params`, which sets
Mosaic's ``vmem_limit_bytes`` from the same model.  Mosaic neither
spills nor shrinks a tile: a kernel whose buffers exceed the limit is
refused at compile time.  So a shape whose footprint exceeds the
chip's VMEM (``VMEM_CAPACITY``) is refused here, by the wrapper, with a
message that says so — there is no fallback path.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental.pallas import tpu as pltpu

LANE = 128                       # minor-dim tile width on TPU
MAX_TILE = 512                   # largest tile the chooser will pick
DEFAULT_VMEM_BUDGET = 8 * 1024 * 1024
SCOPED_VMEM_DEFAULT = 16 * 1024 * 1024   # Mosaic's default limit on v5e
VMEM_CAPACITY = 128 * 1024 * 1024        # one v5e TensorCore's VMEM
# head-room above the modelled footprint for Mosaic's own temporaries
# (the cross-term block, relayouts of the per-row vectors)
_VMEM_SLACK = 4 * 1024 * 1024


def interpret_default() -> bool:
    """The one execution-mode decision: compiled Mosaic on a TPU, the
    Pallas interpreter everywhere else.  There is no override — on a TPU
    the kernels are never interpreted."""
    return jax.default_backend() != "tpu"


def resolve_interpret(interpret: Optional[bool]) -> bool:
    return interpret_default() if interpret is None else bool(interpret)


def round_up(v: int, m: int) -> int:
    return v + (-v) % m


def sublane(itemsize: int) -> int:
    """Minimum second-to-minor tile extent for a dtype's byte width."""
    return {4: 8, 2: 16, 1: 32}.get(itemsize, 8)


def pad_to(a: jax.Array, axis: int, multiple: int, value=0.0):
    """Pad ``axis`` of ``a`` up to a multiple of ``multiple``."""
    size = a.shape[axis]
    rem = (-size) % multiple
    if rem == 0:
        return a
    widths = [(0, 0)] * a.ndim
    widths[axis % a.ndim] = (0, rem)
    return jnp.pad(a, widths, constant_values=value)


def mxu_precision(a, b):
    """Matmul precision for a kernel's (a, b) operands: f32 operands
    multiply at full f32 precision, the arithmetic of interpret mode and
    the jnp oracles; bf16 operands take the MXU's native single pass
    (Mosaic refuses fp32 contraction precision on bf16 operands)."""
    if a.dtype == jnp.float32 and b.dtype == jnp.float32:
        return jax.lax.Precision.HIGHEST
    return None


def out_struct(shape, dtype, *operands) -> jax.ShapeDtypeStruct:
    """Output ShapeDtypeStruct varying over the union of the operands'
    mesh axes, so a kernel called inside ``jax.shard_map`` keeps the
    checker on."""
    vma = frozenset().union(*(jax.typeof(a).vma for a in operands))
    return jax.ShapeDtypeStruct(shape, dtype, vma=vma)


def _resident(kind: str, kp: int, dp: int) -> int:
    """Grid-resident bytes that no tile size can shrink: the fused
    kernels' f32 stats accumulators — (Kp, dp) sums, (1, Kp) counts
    padded to 8 sublanes, the energy (and for fused_bounds the skip
    counter) block — each double-buffered."""
    if kind in ("fused", "fused_bounds"):
        blocks = kp * dp * 4 + 8 * kp * 4 + 8 * LANE * 4
        if kind == "fused_bounds":
            blocks += 8 * LANE * 4
        return 2 * blocks
    return 0


def _tile_cost(kind: str, tn: int, tk: int, dp: int, itemsize: int,
               kp: int = 0) -> int:
    """Tile-dependent VMEM bytes of one grid cell's working set.  A
    lane-major (1, T) vector block occupies 8 sublanes of VMEM, hence the
    8x on the per-row and per-centroid vectors."""
    x_tile = 2 * tn * dp * itemsize          # double-buffered X tile
    c_tile = 2 * tk * dp * itemsize          # double-buffered C tile
    csq_tile = 2 * 8 * tk * 4
    w_tile = 2 * 8 * tn * 4
    lab_tiles = 2 * 8 * tn * (4 + 4)         # labels + min-dist tiles
    dist = tn * tk * 4                       # distance / one-hot block
    if kind in ("fused", "fused_bounds"):
        scratch = tn * (4 + 4)               # running min / argmin
        cost = (x_tile + c_tile + csq_tile + w_tile + lab_tiles
                + 2 * dist + scratch)
        if kind == "fused_bounds":
            # lower-bound tile in + group-min tile out, squared upper
            # bound and previous labels in (all (1, TN), double-buffered)
            cost += 4 * 2 * 8 * tn * 4
        return cost
    if kind == "assignment":
        return x_tile + c_tile + csq_tile + lab_tiles + dist
    if kind == "update":
        out_tiles = 2 * (tk * dp * 4 + 8 * tk * 4)   # sums + counts blocks
        return x_tile + w_tile + 2 * 8 * tn * 4 + out_tiles + dist
    raise ValueError(f"unknown kernel kind {kind!r}")


def _footprint(kind: str, tn: int, tk: int, kp: int, dp: int,
               itemsize: int) -> int:
    """Approximate VMEM bytes of one grid cell's working set."""
    return _tile_cost(kind, tn, tk, dp, itemsize, kp) + \
        _resident(kind, kp, dp)


def _halve(t: int) -> int:
    """Next smaller lane-legal tile: a multiple of LANE, at least LANE."""
    return max(LANE, round_up(t // 2, LANE))


def choose_tiles(n: int, k: int, d: int, itemsize: int, *,
                 kind: str = "fused",
                 vmem_bytes: Optional[int] = None) -> Tuple[int, int]:
    """Pick lane-legal (tn, tk) for a kernel of ``kind`` so its working
    set fits.

    Starts from the padded problem extent capped at MAX_TILE and halves
    the larger of the two tiles (k tile on ties — k-tiling is the v2
    lever) until the `_footprint` model fits ``vmem_bytes`` (default: the
    module's ``DEFAULT_VMEM_BUDGET``, read at call time so tests can
    monkeypatch it).  A tile is either the whole padded extent or a
    multiple of LANE, never below LANE once it splits the axis.

    The fused kernels' grid-resident stats accumulator is charged only
    up to *half* the budget: once K·d is irreducibly past that, further
    tile shrinking cannot buy the accumulator back — it would only
    multiply the C re-stream traffic.  `compiler_params` then raises the
    scoped-VMEM limit to the real footprint, or refuses the shape when
    not even the chip's whole VMEM holds it.
    """
    budget = DEFAULT_VMEM_BUDGET if vmem_bytes is None else vmem_bytes
    sl = sublane(itemsize)
    dp = round_up(max(d, 1), LANE)
    tn = min(MAX_TILE, round_up(max(n, 1), sl))
    tk = min(MAX_TILE, round_up(max(k, 1), sl))

    def cost(a, b):
        kp = round_up(max(k, 1), b)
        resident = _resident(kind, kp, dp)
        return _tile_cost(kind, a, b, dp, itemsize, kp) + \
            min(resident, budget // 2)

    while cost(tn, tk) > budget and (tn > LANE or tk > LANE):
        if tk > LANE and (tk >= tn or tn <= LANE):
            tk = _halve(tk)
        else:
            tn = _halve(tn)
    return tn, tk


def check_tiles(tn: int, np_: int, tk: int, kp: int) -> None:
    """Refuse tiles the Mosaic compiler cannot lower: a tile that splits
    the lane-major row or centroid axis must be a multiple of LANE."""
    for name, t, full in (("tn", tn, np_), ("tk", tk, kp)):
        if t != full and t % LANE:
            raise ValueError(
                f"{name}={t} splits an axis of {full} into tiles that are "
                f"not a multiple of {LANE} lanes; on the TPU a tile covers "
                f"the whole axis or is a multiple of {LANE}")


def compiler_params(kind: str, sems, *, tn: int, tk: int, kp: int,
                    dp: int, itemsize: int, interpret: bool) -> dict:
    """``pallas_call`` kwargs: the Mosaic dimension-semantics hint and a
    scoped-VMEM limit sized from the footprint model.  Raises ValueError
    when the footprint exceeds the chip's VMEM (compiled path only —
    interpret mode has no VMEM)."""
    if interpret:
        return {}
    need = _footprint(kind, tn, tk, kp, dp, itemsize) + _VMEM_SLACK
    if need > VMEM_CAPACITY:
        raise ValueError(
            f"{kind} kernel needs ~{need / 2**20:.1f} MiB of VMEM at "
            f"tn={tn}, tk={tk}, K padded to {kp}, d padded to {dp} (the "
            f"resident (K, d) f32 stats accumulator alone is "
            f"{_resident(kind, kp, dp) / 2**20:.1f} MiB); one TensorCore "
            f"has {VMEM_CAPACITY / 2**20:.0f} MiB.  Reduce K*d, or use "
            f"the hierarchical solve for very large K")
    limit = min(VMEM_CAPACITY, max(SCOPED_VMEM_DEFAULT, need))
    return {"compiler_params": pltpu.CompilerParams(
        dimension_semantics=tuple(sems), vmem_limit_bytes=limit)}
