"""Centroid initialization schemes used in the paper's Table 3.

The paper evaluates robustness of AA-KMeans under four seedings:
K-Means++ (Arthur & Vassilvitskii 2007), afk-mc^2 (Bachem et al. 2016),
bf (Bradley & Fayyad 1998) and CLARANS (Newling & Fleuret 2017).  The paper
uses external code to generate seeds; here each scheme is implemented from
scratch in JAX so the whole pipeline is self-contained (system prompt:
"If the paper compares against a baseline, implement the baseline too").

All schemes are deterministic given a PRNG key and jit-able except CLARANS
(whose swap-acceptance loop is inherently sequential; it runs as a Python
loop over jitted cost evaluations).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.core.lloyd import assign, pairwise_sqdist


def _validate_seeding(x: jax.Array, k: int, scheme: str) -> None:
    """Reject degenerate requests with a clear error instead of the opaque
    gather/concatenate failures the schemes otherwise die with.  Shape-only,
    so it is safe at trace time (inside jit and under vmap)."""
    if x.ndim < 2:
        raise ValueError(
            f"{scheme}: x must be (N, d); got shape {tuple(x.shape)}")
    n = x.shape[0]
    if k < 1:
        raise ValueError(f"{scheme}: need at least one cluster; got k={k}")
    if k > n:
        raise ValueError(
            f"{scheme}: cannot seed k={k} centroids from only n={n} "
            f"samples; need k <= n")


def _take_rows(x, idx):
    return x[idx]


def random_init(key: jax.Array, x: jax.Array, k: int,
                w=None, take=None) -> jax.Array:
    """Uniformly sample K distinct rows of X.  ``w`` (N,) >= 0 biases the
    draw (p ∝ w) — a zero-weight (padding) row is never picked.
    ``take(x, idx)`` fetches the picked rows (rows sharded over a mesh:
    `repro.core.distributed.row_take`)."""
    _validate_seeding(x, k, "random_init")
    p = None if w is None else w / jnp.maximum(jnp.sum(w), 1e-30)
    idx = jax.random.choice(key, x.shape[0], (k,), replace=False, p=p)
    return (take or _take_rows)(x, idx)


@partial(jax.jit, static_argnames=("k", "take"))
def kmeanspp_init(key: jax.Array, x: jax.Array, k: int,
                  w=None, take=None) -> jax.Array:
    """K-Means++: D^2-weighted sequential sampling.

    ``w`` (N,) >= 0 makes the sampling SEGMENT-AWARE: the first pick
    draws p ∝ w and every D^2 round draws p ∝ w·D^2, so a zero-weight
    row — the hierarchy engine's segment padding — is never seeded
    (DESIGN.md §Hierarchy).  ``w=None`` keeps the classic scheme
    bit-for-bit (the unweighted draws use different PRNG primitives, so
    ``w=ones`` is distributionally equal but not bitwise).  ``take`` as
    in `random_init`."""
    _validate_seeding(x, k, "kmeanspp_init")
    take = take or _take_rows
    n = x.shape[0]
    key, sub = jax.random.split(key)
    if w is None:
        first = jax.random.randint(sub, (), 0, n)
    else:
        w = w.astype(jnp.float32)
        first = jax.random.categorical(
            sub, jnp.log(jnp.maximum(w / jnp.maximum(jnp.sum(w), 1e-30),
                                     1e-38)))
    c0 = take(x, first)
    mind = jnp.sum((x - c0) ** 2, axis=-1)

    def body(carry, key_t):
        mind, _ = carry
        # Sample proportional to (w ·) D^2.  Weighted all-zero corner
        # (every live row already a centroid): fall back to w itself so
        # padding rows stay unseedable; unweighted keeps the classic
        # uniform fallback via the clamp below.
        if w is None:
            score = mind
        else:
            s = mind * w
            score = jnp.where(jnp.sum(s) > 0, s, w)
        p = score / jnp.maximum(jnp.sum(score), 1e-30)
        idx = jax.random.categorical(key_t, jnp.log(jnp.maximum(p, 1e-38)))
        c_new = take(x, idx)
        d_new = jnp.sum((x - c_new) ** 2, axis=-1)
        mind = jnp.minimum(mind, d_new)
        return (mind, idx), c_new

    keys = jax.random.split(key, k - 1)
    (_, _), rest = jax.lax.scan(body, (mind, first), keys)
    return jnp.concatenate([c0[None], rest], axis=0)


@partial(jax.jit, static_argnames=("k", "chain_length"))
def afkmc2_init(key: jax.Array, x: jax.Array, k: int,
                chain_length: int = 100) -> jax.Array:
    """Assumption-free K-MC^2 (Bachem et al. 2016): MCMC approximation of
    K-Means++ using a D^2+uniform proposal distribution."""
    _validate_seeding(x, k, "afkmc2_init")
    n = x.shape[0]
    key, sub = jax.random.split(key)
    first = jax.random.randint(sub, (), 0, n)
    c0 = x[first]
    # Proposal q(x) = 0.5 * d(x, c0)^2 / sum + 0.5 / n
    d0 = jnp.sum((x - c0) ** 2, axis=-1)
    q = 0.5 * d0 / jnp.maximum(jnp.sum(d0), 1e-30) + 0.5 / n
    logq = jnp.log(jnp.maximum(q, 1e-38))

    def sample_center(carry, key_t):
        centers, n_c = carry               # centers: (k, d) buffer; n_c valid
        k1, k2, k3 = jax.random.split(key_t, 3)
        # Candidate chain: chain_length proposals from q.
        cand = jax.random.categorical(k1, logq, shape=(chain_length,))
        us = jax.random.uniform(k2, (chain_length,))

        def mind_to_centers(i):
            d = jnp.sum((x[i][None, :] - centers) ** 2, axis=-1)
            masked = jnp.where(jnp.arange(centers.shape[0]) < n_c, d, jnp.inf)
            return jnp.min(masked)

        def chain_step(state, t):
            cur, cur_val = state
            nxt = cand[t]
            nxt_val = mind_to_centers(nxt) / q[nxt]
            accept = us[t] < nxt_val / jnp.maximum(cur_val, 1e-30)
            cur = jnp.where(accept, nxt, cur)
            cur_val = jnp.where(accept, nxt_val, cur_val)
            return (cur, cur_val), None

        start = cand[0]
        start_val = mind_to_centers(start) / q[start]
        (chosen, _), _ = jax.lax.scan(chain_step, (start, start_val),
                                      jnp.arange(1, chain_length))
        centers = centers.at[n_c].set(x[chosen])
        return (centers, n_c + 1), None

    centers = jnp.zeros((k, x.shape[1]), x.dtype).at[0].set(c0)
    keys = jax.random.split(key, k - 1)
    (centers, _), _ = jax.lax.scan(sample_center, (centers, 1), keys)
    return centers


def bf_init(key: jax.Array, x: jax.Array, k: int, n_subsets: int = 10,
            subset_frac: float = 0.1, max_iter: int = 20) -> jax.Array:
    """Bradley & Fayyad 1998 refinement: run K-Means on J random subsamples,
    then cluster the union of the J solutions and return the best seed set."""
    from repro.core.kmeans import KMeansConfig, aa_kmeans
    _validate_seeding(x, k, "bf_init")
    n = x.shape[0]
    subset = max(k * 2, int(n * subset_frac))
    subset = min(subset, n)
    cfg = KMeansConfig(k=k, max_iter=max_iter, accelerated=False)

    def solve_subset(key_j):
        k1, k2 = jax.random.split(key_j)
        idx = jax.random.choice(k1, n, (subset,), replace=False)
        xs = x[idx]
        c0 = random_init(k2, xs, k)
        res = aa_kmeans(xs, c0, cfg)
        return res.centroids

    keys = jax.random.split(key, n_subsets + 1)
    cms = jax.lax.map(solve_subset, keys[:n_subsets])   # (J, K, d)
    cm_all = cms.reshape(n_subsets * k, -1)

    # Cluster the union of subset solutions, seeding from each solution in
    # turn; keep the seed set with the lowest distortion over CM (as in BF98).
    def refine(cj):
        res = aa_kmeans(cm_all, cj, cfg)
        return res.centroids, res.energy

    fms, costs = jax.lax.map(refine, cms)
    best = jnp.argmin(costs)
    return fms[best]


def clarans_init(key: jax.Array, x: jax.Array, k: int,
                 num_local: int = 2, max_neighbor: int = 32,
                 sample_n: int = 2048) -> jax.Array:
    """Simplified CLARANS (Ng & Han 1994) k-medoids seeding as used for
    K-Means initialisation by Newling & Fleuret 2017.

    Randomized medoid-swap local search on a subsample (CLARANS evaluates
    swaps on a sample for scalability).  Python loop over jitted swap
    evaluations — initialisation cost, not part of the timed solver.
    """
    _validate_seeding(x, k, "clarans_init")
    if num_local < 1:
        raise ValueError(
            f"clarans_init: num_local must be >= 1 (got {num_local}); "
            f"zero local searches would yield no medoid set at all")
    n = x.shape[0]
    key, sub = jax.random.split(key)
    if n > sample_n:
        sidx = jax.random.choice(sub, n, (sample_n,), replace=False)
        xs = x[sidx]
    else:
        xs = x

    @jax.jit
    def cost_of(medoids):
        d = pairwise_sqdist(xs, medoids)
        return jnp.sum(jnp.min(d, axis=-1))

    @jax.jit
    def swap(medoids, slot, cand):
        return medoids.at[slot].set(xs[cand])

    best_medoids, best_cost = None, jnp.inf
    for restart in range(num_local):
        key, k1 = jax.random.split(key)
        medoids = random_init(k1, xs, k)
        cost = cost_of(medoids)
        stall = 0
        while stall < max_neighbor:
            key, k2, k3 = jax.random.split(key, 3)
            slot = int(jax.random.randint(k2, (), 0, k))
            cand = int(jax.random.randint(k3, (), 0, xs.shape[0]))
            trial = swap(medoids, slot, cand)
            tcost = cost_of(trial)
            if float(tcost) < float(cost):
                medoids, cost, stall = trial, tcost, 0
            else:
                stall += 1
        if float(cost) < float(best_cost):
            best_medoids, best_cost = medoids, cost
    return best_medoids


INIT_SCHEMES = {
    "random": random_init,
    "kmeans++": kmeanspp_init,
    "afk-mc2": afkmc2_init,
    "bf": bf_init,
    "clarans": clarans_init,
}

# Schemes whose whole computation is jit-able, hence vmap-safe over a keys
# axis; bf's subset solves and clarans's swap-acceptance loop run host-side
# Python, so batched_init falls back to stacking per-key results for them.
VMAP_SAFE_INITS = frozenset({"random", "kmeans++", "afk-mc2"})


def make_init(name: str):
    if name not in INIT_SCHEMES:
        raise ValueError(f"unknown init scheme {name!r}; "
                         f"choose from {sorted(INIT_SCHEMES)}")
    return INIT_SCHEMES[name]


def batched_init(name: str, keys: jax.Array, x: jax.Array,
                 k: int, weights=None, take=None) -> jax.Array:
    """Seed R restarts at once: (R, 2) keys -> (R, K, d) centroid stacks.

    ``x`` is (N, d) shared across restarts, or (R, N, d) one dataset per
    problem.  Vmap-safe schemes produce the whole stack in one traced
    computation (feeding the batched solver without a host round-trip);
    the host-loop schemes (bf, clarans) are looped and stacked, which is
    semantically identical — seeding cost only, never solver cost.

    ``weights`` (R, N) >= 0 makes the seeding segment-aware (the
    hierarchy engine's padded sub-problems: padding rows weigh 0 and are
    never seeded) — supported for the weighted schemes random/kmeans++
    only.

    ``take(x, idx)`` fetches picked rows for the schemes that only pick
    rows (random, kmeans++): with rows sharded over a mesh,
    `repro.core.distributed.row_take` moves the picked rows alone.  The
    other schemes would gather X and refuse it."""
    fn = make_init(name)
    if take is not None:
        if name not in ("random", "kmeans++"):
            raise ValueError(
                f"init={name!r} cannot seed from rows sharded over a mesh "
                f"without gathering them; use 'random' or 'kmeans++'")
        fn = partial(fn, take=take)
    x_axis = 0 if x.ndim == 3 else None
    if x_axis == 0 and x.shape[0] != keys.shape[0]:
        raise ValueError(
            f"batched x has {x.shape[0]} problems but got "
            f"{keys.shape[0]} keys")
    if weights is not None:
        if name not in ("random", "kmeans++"):
            raise ValueError(
                f"batched_init(weights=...) supports the weighted schemes "
                f"'random' and 'kmeans++' only; got {name!r}")
        return jax.vmap(lambda kk, xx, ww: fn(kk, xx, k, w=ww),
                        in_axes=(0, x_axis, 0))(keys, x, weights)
    if name in VMAP_SAFE_INITS:
        return jax.vmap(lambda kk, xx: fn(kk, xx, k),
                        in_axes=(0, x_axis))(keys, x)
    seeds = [fn(keys[i], x if x_axis is None else x[i], k)
             for i in range(keys.shape[0])]
    return jnp.stack([jnp.asarray(s) for s in seeds])
