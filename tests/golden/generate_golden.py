"""Golden-trajectory generator + single source of truth for the
regression in tests/test_golden.py.

`compute_trajectory()` runs a small fixed-seed full-batch AA solve on the
dense backend through the solver's jitted trip (`_trip`: one backend step
and the completion it allows) and records, for every completed
iteration, the post-revert energy and labels, plus the final centroids —
exactly the quantities whose silent drift the golden test guards.

Regenerating the file is an *intentional numerics change* and belongs in
its own reviewed commit:

    PYTHONPATH=src JAX_PLATFORMS=cpu python tests/golden/generate_golden.py

The stored trajectory is CPU-XLA specific; the test compares bitwise on
CPU and falls back to tolerances elsewhere.
"""

from pathlib import Path

import numpy as np

GOLDEN_PATH = Path(__file__).parent / "aa_dense_cpu.npz"
# overlapping clusters (spread 0.9): long enough a trajectory (~25
# iterations, mixed accepts and reverts) to pin the guard dynamics, small
# enough to rerun in milliseconds
N, D, K, SEED, SPREAD, MAX_ITER = 400, 6, 6, 0, 0.9, 200


def compute_trajectory():
    import jax
    import jax.numpy as jnp
    from repro.core import kmeans as KM
    from repro.core.init_schemes import kmeanspp_init
    from repro.core.kmeans import KMeansConfig
    from repro.core.backends import get_backend
    from repro.data.synthetic import make_blobs

    x = jnp.asarray(make_blobs(N, D, K, seed=SEED, spread=SPREAD))
    c0 = kmeanspp_init(jax.random.PRNGKey(SEED), x, K)
    cfg = KMeansConfig(k=K, max_iter=MAX_ITER)
    backend = get_backend("dense")

    bst = KM._BatchedState(KM._init_state_jit(x, c0, cfg, backend),
                           jnp.array(False))
    energies, labels = [], []
    while int(bst.inner.t) < MAX_ITER:
        bst = KM._trip_jit(x, bst, cfg, backend)
        if bool(bst.inner.converged):
            break
        if bool(bst.pending):       # a rejected iterate: not done yet
            continue
        energies.append(np.asarray(bst.inner.e_last))
        labels.append(np.asarray(bst.inner.labels))
    state = bst.inner
    return {
        "energies": np.stack(energies),              # (T,) f32, exact bits
        "labels": np.stack(labels).astype(np.int32),  # (T, N)
        "centroids": np.asarray(state.c, np.float32),  # (K, d)
        "shape": np.array([N, D, K, SEED], np.int64),
    }


if __name__ == "__main__":
    traj = compute_trajectory()
    np.savez_compressed(GOLDEN_PATH, **traj)
    print(f"wrote {GOLDEN_PATH}: T={traj['energies'].shape[0]} iterations, "
          f"final E={traj['energies'][-1]:.6f}")
