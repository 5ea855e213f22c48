"""Anderson acceleration window with the paper's dynamic-m adjustment.

Implements the accelerated-iterate computation of Algorithm 1 (lines 16-19):

    theta* = argmin || F^t - sum_j theta_j (F^{t-j+1} - F^{t-j}) ||^2      (7)
    C^{t+1} = G^t  -  sum_j theta_j* (G^{t-j+1} - G^{t-j})                 (19)

NOTE on sign: Eq. (8) of the paper prints a "+" while Algorithm 1 line 19
prints a "-".  The "-" is the correct classical type-II Anderson update (the
affine-combination weights alpha_j of {G^{t-j}} with sum alpha = 1 transform
to backward-difference coefficients theta with a minus sign; see Walker & Ni
2011, Eq. 2.2).  We implement the minus sign; DESIGN.md records the typo.

All state lives in fixed-shape circular buffers so the whole accelerated
solver can run inside jax.lax.while_loop.  The least-squares problem (7) is
solved via normal equations with a tiny relative Tikhonov term (the
stabilisation used by Peng et al. 2018's reference implementation); columns
beyond the active window m_t are masked out with an identity block so the
solve is well-posed at any m_t <= mbar.

Dynamic adjustment of m (Algorithm 1 lines 7-11): with the energy-decrease
ratio r = (E^{t-1} - E^t) / (E^{t-2} - E^{t-1}),

    r < eps1  ->  m = max(m - 1, 0)       # step ineffective, shrink window
    r > eps2  ->  m = min(m + 1, mbar)    # step effective, grow window

with paper defaults eps1 = 0.02, eps2 = 0.5, mbar = 30, m0 = 2.

Batching contract (DESIGN.md §Batching): every function here is vmap-safe
over a leading problem axis — AAState leaves are fixed-shape arrays, the
window solve is already a *masked* dense (mbar x mbar) system (no
data-dependent shapes), and `_spd_solve`'s unrolled elimination batches
as fused elementwise ops (unlike LAPACK-backed `jnp.linalg.solve`, which
it replaced).  The batched driver (kmeans.aa_kmeans_batched) relies on
this to run R independent Anderson windows inside one `lax.while_loop`;
do not introduce value-dependent Python control flow here.
"""

from __future__ import annotations

import dataclasses
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core.lloyd import MATMUL_PRECISION


@dataclasses.dataclass(frozen=True)
class AAConfig:
    m0: int = 2            # initial window size
    mbar: int = 30         # maximum window size (paper: 30)
    eps1: float = 0.02     # shrink threshold (paper: 0.02)
    eps2: float = 0.5      # grow threshold (paper: 0.5)
    dynamic_m: bool = True  # False -> fixed m = m0 (Table 2 "Fixed" columns)
    ridge: float = 1e-12   # relative Tikhonov regularisation for (7)


class AAState(NamedTuple):
    """Fixed-shape Anderson window.

    dF, dG : (mbar, D) circular buffers of residual / iterate differences,
             column ``head - 1 - j (mod mbar)`` holds (F^{t-j} - F^{t-j-1}).
    f_prev, g_prev : (D,) last residual / last fixed-point image.
    ncols  : number of valid history columns (= min(t, mbar)).
    head   : next write position in the circular buffers.
    m      : current window size (dynamically adjusted).

    Persistence contract (DESIGN.md §Persistence): this tuple IS the
    acceleration's whole memory — there is no hidden host state — and
    every leaf is a fixed-shape array, so snapshotting it (inside the
    solver's `_LoopState`, via `repro.core.serialize`) and restoring it
    bit-exactly resumes the accelerated trajectory the paper's energy
    guard depends on.  A restart from bare centroids instead discards the
    window (ncols/head/m reset), which changes every subsequent AA step.
    Adding a field here is a snapshot-schema change: bump
    `serialize.SCHEMA_VERSION` and provide a migration.
    """
    dF: jax.Array
    dG: jax.Array
    f_prev: jax.Array
    g_prev: jax.Array
    ncols: jax.Array
    head: jax.Array
    m: jax.Array


def aa_init(d_flat: int, cfg: AAConfig, dtype=jnp.float32) -> AAState:
    return AAState(
        dF=jnp.zeros((cfg.mbar, d_flat), dtype),
        dG=jnp.zeros((cfg.mbar, d_flat), dtype),
        f_prev=jnp.zeros((d_flat,), dtype),
        g_prev=jnp.zeros((d_flat,), dtype),
        ncols=jnp.array(0, jnp.int32),
        head=jnp.array(0, jnp.int32),
        m=jnp.array(cfg.m0, jnp.int32),
    )


def aa_seed(state: AAState, f0: jax.Array, g0: jax.Array) -> AAState:
    """Record (F^0, G^0) before the first accelerated iteration."""
    return state._replace(f_prev=f0, g_prev=g0)


@jax.named_scope("repro.aa")
def adjust_m(state: AAState, e_curr: jax.Array, e_prev: jax.Array,
             e_prev2: jax.Array, cfg: AAConfig) -> AAState:
    """Algorithm 1 lines 7-11.  Guarded for t < 2 (e_prev2 = +inf) and for a
    zero previous decrease (ratio -> +inf -> grow, matching the limit)."""
    if not cfg.dynamic_m:
        return state
    num = e_prev - e_curr
    den = e_prev2 - e_prev
    # den == +inf (first two iterations): ratio 0/inf -> leave m unchanged by
    # construction of the guards below; den == 0: treat as ratio = +inf.
    ratio = jnp.where(den > 0, num / jnp.maximum(den, jnp.finfo(num.dtype).tiny),
                      jnp.where(num > 0, jnp.inf, -jnp.inf))
    defined = jnp.isfinite(e_prev2)  # only adjust once E^{t-2} exists
    shrink = jnp.logical_and(defined, ratio < cfg.eps1)
    grow = jnp.logical_and(defined, ratio > cfg.eps2)
    m = jnp.where(shrink, jnp.maximum(state.m - 1, 0),
                  jnp.where(grow, jnp.minimum(state.m + 1, cfg.mbar), state.m))
    return state._replace(m=m.astype(jnp.int32))


def _spd_solve(a: jax.Array, b: jax.Array) -> jax.Array:
    """Solve a (n, n) SPD system with pure-XLA Gauss–Jordan elimination.

    The window gram is symmetric positive definite by construction (A Aᵀ
    over the active columns + relative ridge, identity rows elsewhere), so
    elimination without pivoting is stable here.  A hand-rolled fori_loop
    beats `jnp.linalg.solve` for this shape because the LAPACK custom
    call costs ~200us of dispatch per (mbar, mbar) solve on CPU — per
    *solver iteration* — and lowers to a per-matrix host loop when the
    batched driver vmaps it; this formulation is a handful of fused
    elementwise ops that batch for free."""
    n = a.shape[-1]
    aug = jnp.concatenate([a, b[:, None]], axis=-1)       # (n, n+1)
    # n (= mbar) is static and small, so unroll: one fused kernel instead
    # of an XLA while loop whose per-step dispatch would dominate.
    for i in range(n):
        pivot_row = aug[i] / aug[i, i]                    # (n+1,)
        factors = aug[:, i]                               # (n,)
        aug = aug - factors[:, None] * pivot_row[None, :]
        aug = aug.at[i].set(pivot_row)
    return aug[:, n]


def _column_ages(state: AAState, mbar: int) -> jax.Array:
    """age[i] = how many steps ago buffer column i was written (1 = newest).
    Invalid columns get age > mbar."""
    idx = jnp.arange(mbar, dtype=jnp.int32)
    age = (state.head - 1 - idx) % mbar + 1          # 1 .. mbar
    return jnp.where(age <= state.ncols, age, mbar + 1)


@jax.named_scope("repro.aa")
def aa_push_and_solve(state: AAState, f: jax.Array, g: jax.Array,
                      cfg: AAConfig):
    """Push (F^t, G^t), solve (7) over the active window, return C^{t+1}.

    Returns (new_state, c_next_flat, theta, m_t)."""
    mbar = cfg.mbar
    df = f - state.f_prev
    dg = g - state.g_prev
    dF = state.dF.at[state.head].set(df)
    dG = state.dG.at[state.head].set(dg)
    head = (state.head + 1) % mbar
    ncols = jnp.minimum(state.ncols + 1, mbar)
    state = state._replace(dF=dF, dG=dG, f_prev=f, g_prev=g,
                           ncols=ncols, head=head)

    m_t = jnp.minimum(state.m, ncols)                 # Algorithm 1 line 17
    age = _column_ages(state, mbar)                   # (mbar,)
    active = (age <= m_t)                             # newest m_t columns

    # Normal equations over masked columns:  (A A^T + lam I) theta = A f
    a_mask = jnp.where(active[:, None], dF, 0.0)
    gram = jnp.matmul(a_mask, a_mask.T,
                      precision=MATMUL_PRECISION)     # (mbar, mbar)
    rhs = jnp.matmul(a_mask, f, precision=MATMUL_PRECISION)   # (mbar,)
    lam = cfg.ridge * (jnp.trace(gram) + 1.0)
    eye = jnp.eye(mbar, dtype=f.dtype)
    # Identity rows/cols for inactive entries keep the solve well-posed.
    gram = jnp.where(active[:, None] & active[None, :], gram, 0.0) + \
        eye * jnp.where(active, lam, 1.0)
    theta = _spd_solve(gram, rhs)
    theta = jnp.where(active, theta, 0.0)

    dg_mask = jnp.where(active[:, None], dG, 0.0)
    c_next = g - jnp.matmul(theta, dg_mask,
                            precision=MATMUL_PRECISION)   # Alg. 1 line 19
    # m_t == 0 -> plain Lloyd iterate (theta is all zero already, but be
    # explicit so a zero window is exactly un-accelerated).
    c_next = jnp.where(m_t > 0, c_next, g)
    return state, c_next, theta, m_t
