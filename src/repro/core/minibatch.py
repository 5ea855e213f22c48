"""Streaming mini-batch Anderson-accelerated K-Means (DESIGN.md §Streaming).

Every solver before this one assumes the whole dataset X sits in device
memory.  This module runs Algorithm 1 over *chunked* data: each step reads
one chunk, folds its weighted cluster statistics into exponentially-decayed
running sums, and treats the running mean as the fixed-point image G(C) —
the mini-batch analogue of the Lloyd update (Sculley 2010, with decay in
place of per-centre learning rates so the map stays a fixed-shape
fixed-point iteration AA can accelerate).

Three adaptations of Algorithm 1, all local to this module:

  * **G is the decayed running mean.**  With chunk stats (s, n) at C^t,

        S_t = γ·S_{t-1} + s,   W_t = γ·W_{t-1} + n,   G(C^t) = S_t / W_t

    (clusters with W = 0 keep their previous centroid).  S/W is invariant
    under pure decay, so a cluster unseen for many chunks holds its last
    mean rather than drifting.

  * **The energy guard runs on a held-out validation chunk.**  The paper's
    accept test compares full-X energies, which are unavailable online.
    Instead each step evaluates the accelerated candidate C^t and the
    fallback C_AU^t on one fixed validation chunk (a single batched step —
    R = 2 centroid sets, one pass over the val rows; the dense backend's
    shared-X einsum, or ONE leading-R-grid kernel launch on the
    pallas/fused engines) and keeps the candidate only if it is strictly
    better there.  The same validation energies drive the paper's
    dynamic-m adjustment.

  * **Seeding happens on the first chunk.**  The window is seeded with
    (G(C^0) − C^0, G(C^0)) computed from chunk 0's stats; the first step
    is therefore plain mini-batch Lloyd, exactly as the full-batch driver's
    init step is plain Lloyd.

The per-chunk communication under `distribute()` is one (K,(d+1))-stat
psum for the chunk step plus the scalar validation energies — independent
of the chunk size (DESIGN.md §Streaming).

The epoch driver lives in `kmeans.aa_kmeans_minibatch`; this module holds
the per-chunk state machine so the estimator's `partial_fit` and the
benchmarks can drive single steps / single epochs directly.
"""

from __future__ import annotations

import dataclasses
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core import anderson, lloyd
from repro.core.anderson import AAConfig, AAState
from repro.core.backends import Backend


@dataclasses.dataclass(frozen=True)
class MiniBatchConfig:
    """Static configuration of the streaming solver (jit-static)."""
    k: int
    chunk_size: int = 4096     # rows per chunk (data layer pads the tail)
    epochs: int = 5            # passes over the chunked data (fit path)
    decay: float = 0.9         # running-stat decay per chunk step
    aa: AAConfig = dataclasses.field(default_factory=AAConfig)
    accelerated: bool = True   # False -> plain mini-batch Lloyd


class MiniBatchState(NamedTuple):
    """Loop state carried across chunk steps (all fixed-shape arrays)."""
    c: jax.Array        # C^t — current (possibly accelerated) candidate
    c_au: jax.Array     # C_AU^t — fallback from the running stats
    sums: jax.Array     # decayed running cluster sums (K, d)
    counts: jax.Array   # decayed running cluster weights (K,)
    e_prev: jax.Array   # validation energy of the previous kept iterate
    e_prev2: jax.Array  # ... and the one before (dynamic-m ratio)
    aa: AAState
    t: jax.Array        # chunk steps taken
    n_acc: jax.Array    # steps whose accelerated candidate was kept


class MiniBatchTrace(NamedTuple):
    """Per-chunk-step diagnostics (scan-stacked by the epoch driver)."""
    e_val: jax.Array      # validation energy of the kept iterate
    e_cand: jax.Array     # ... of the accelerated candidate
    e_fallback: jax.Array  # ... of the running-stats fallback
    accepted: jax.Array   # guard decision


class MiniBatchResult(NamedTuple):
    centroids: jax.Array   # (K, d) — guard-picked final iterate
    energy: jax.Array      # total validation-chunk energy of `centroids`
    n_steps: jax.Array     # chunk steps executed
    n_accepted: jax.Array  # accelerated candidates kept


def minibatch_init(c0: jax.Array, cfg: MiniBatchConfig,
                   backend: Backend) -> MiniBatchState:
    k, d = c0.shape
    # accum_dtype is floored at f32 by the Precision policy (a bf16
    # running count freezes at 256 — see lloyd._accum_dtype)
    acc = backend.precision.accum_dtype
    inf = jnp.array(jnp.inf, acc)
    return MiniBatchState(
        c=c0, c_au=c0,
        sums=jnp.zeros((k, d), acc), counts=jnp.zeros((k,), acc),
        e_prev=inf, e_prev2=inf,
        aa=anderson.aa_init(k * d, cfg.aa, c0.dtype),
        t=jnp.array(0, jnp.int32), n_acc=jnp.array(0, jnp.int32))


def _centroids_from_running(sums, counts, c_prev, eps: float = 1e-6):
    """G(C) from the decayed running stats.  Unlike `lloyd.update_from_sums`
    (whose max(counts, 1) safe-divide assumes integer-ish counts), decayed
    weights legitimately sit below 1 and must still divide exactly."""
    safe = jnp.maximum(counts, eps)[:, None]
    mean = (sums / safe).astype(c_prev.dtype)
    return jnp.where(counts[:, None] > eps, mean, c_prev)


def guard_pick(x_val, state: MiniBatchState, cfg: MiniBatchConfig,
               backend: Backend):
    """Validation-chunk energy guard (Algorithm 1 lines 12-14, adapted).

    One batched step (R = 2 centroid sets, one pass over the val rows —
    shared-X einsum on the dense backend, the native leading-R fused
    kernel on pallas/fused) prices both the accelerated candidate and the
    fallback; the candidate is kept only if strictly better.  Returns
    (kept_c, kept_energy, accepted, (e_cand, e_fallback)).
    """
    cands = jnp.stack([state.c, state.c_au])
    carries = jax.vmap(lambda cc: backend.init_carry(x_val, cc, cfg.k))(cands)
    vres, _ = backend.batched_step(x_val, cands, cfg.k, carries)
    e_c, e_au = vres.energy[0], vres.energy[1]
    accepted = e_c < e_au
    c_t = jnp.where(accepted, state.c, state.c_au)
    e_t = jnp.where(accepted, e_c, e_au)
    return c_t, e_t, accepted, (e_c, e_au)


def minibatch_iteration(x_chunk, w, x_val, state: MiniBatchState,
                        cfg: MiniBatchConfig, backend: Backend):
    """One chunk step of streaming Algorithm 1.

    Structure mirrors Algorithm 1's loop body (`kmeans._trip` and
    `kmeans._complete_iteration`), with E replaced
    by the validation-chunk energy and G by the decayed-running-stats map:
    guard (accept/revert) -> m-adjustment -> one weighted pass over the
    chunk -> running-stat update -> Anderson push/solve.

    Returns (new_state, MiniBatchTrace).
    """
    k = cfg.k

    if cfg.accelerated:
        # Lines 7-14: m-adjustment then accept/revert, on val energies.
        c_t, e_t, accepted, (e_c, _e_au) = guard_pick(x_val, state, cfg,
                                                      backend)
        aa_adj = anderson.adjust_m(state.aa, e_c, state.e_prev,
                                   state.e_prev2, cfg.aa)
    else:
        # Plain mini-batch Lloyd: c == c_au always, so price the single
        # iterate (R=1) — an R=2 guard would double the val-row compute
        # to compare two identical candidates.
        vres, _ = backend.step(x_val, state.c_au, k,
                               backend.init_carry(x_val, state.c_au, k))
        c_t, e_t = state.c_au, vres.energy
        e_c = _e_au = vres.energy
        accepted = jnp.array(False)
        aa_adj = state.aa

    # Line 16 (mini-batch form): one weighted pass over the chunk at the
    # kept iterate; its stats decay into the running sums.  The carry is
    # chunk-local state, re-initialised because the rows are fresh.
    res, _ = backend.minibatch_step(x_chunk, c_t, k, w,
                                    backend.init_carry(x_chunk, c_t, k))
    sums = cfg.decay * state.sums + res.sums
    counts = cfg.decay * state.counts + res.counts
    c_au_next = _centroids_from_running(sums, counts, c_t)

    # Lines 17-19: Anderson acceleration across chunks.  The first step
    # seeds the window (the full-batch driver seeds in _init_state) and
    # emits the plain mini-batch iterate.
    g_flat = c_au_next.reshape(-1)
    f_flat = g_flat - c_t.reshape(-1)
    is_first = state.t == 0
    if cfg.accelerated:
        # lax.cond, not a select: the seed branch fires exactly once, and
        # a whole-AAState select would pay two (mbar, D)-buffer copies
        # plus a wasted window solve on every chunk of every epoch
        def _seed(args):
            aa, f, g = args
            return anderson.aa_seed(aa, f, g), g

        def _push(args):
            aa, f, g = args
            aa2, c2, _, _ = anderson.aa_push_and_solve(aa, f, g, cfg.aa)
            return aa2, c2

        aa_next, c_next_flat = jax.lax.cond(is_first, _seed, _push,
                                            (aa_adj, f_flat, g_flat))
        c_next = c_next_flat.reshape(c_t.shape)
    else:
        aa_next, c_next = aa_adj, c_au_next

    new_state = MiniBatchState(
        c=c_next, c_au=c_au_next, sums=sums, counts=counts,
        e_prev=e_t, e_prev2=state.e_prev, aa=aa_next,
        t=state.t + 1,
        n_acc=state.n_acc + accepted.astype(jnp.int32))
    trace = MiniBatchTrace(e_val=e_t, e_cand=e_c, e_fallback=_e_au,
                           accepted=accepted)
    return new_state, trace


def run_epoch(chunks, weights, x_val, state: MiniBatchState,
              cfg: MiniBatchConfig, backend: Backend, key):
    """One pass over every chunk in a fresh random order.

    ``chunks`` is (n_chunks, B, d) and ``weights`` (n_chunks, B) — the
    device-resident layout from `repro.data.streaming.chunk_dataset`.
    The scan gathers one chunk per step (dynamic index, no permuted copy
    of X).  Under shard_map the key is replicated, so every shard walks
    the same chunk order.  Returns (state, MiniBatchTrace with a leading
    n_chunks axis).
    """
    n_chunks = chunks.shape[0]
    perm = jax.random.permutation(key, n_chunks)

    def body(st, idx):
        xc = jnp.take(chunks, idx, axis=0)
        w = jnp.take(weights, idx, axis=0)
        return minibatch_iteration(xc, w, x_val, st, cfg, backend)

    return jax.lax.scan(body, state, perm)
