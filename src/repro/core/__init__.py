"""The paper's contribution: Anderson-accelerated K-Means (Algorithm 1).

Public surface:
    AAKMeans              — sklearn-shaped estimator (batched multi-restart)
    MiniBatchAAKMeans     — streaming estimator (partial_fit / chunked fit)
    aa_kmeans             — jit-able Algorithm 1: one solve on the
                            split-phase loop body (lax.while_loop)
    aa_kmeans_batched     — R restarts/problems in one device program
    aa_kmeans_minibatch   — streaming chunked driver (DESIGN.md §Streaming)
    aa_kmeans_minibatch_streamed — host-source epoch driver with prefetch
    ReorderConfig/reorder_backend — locality engine (DESIGN.md §Locality)
    select_best           — on-device best-of-R selection
    aa_kmeans_traced      — the same trips from a Python loop, recording
                            per-iteration stats
    lloyd_kmeans          — classical Lloyd baseline
    hamerly_kmeans        — Hamerly-bound Lloyd baseline
    KMeansConfig/AAConfig/MiniBatchConfig — solver configuration
    make_distributed_kmeans / make_distributed_kmeans_batched /
    make_distributed_kmeans_minibatch
                          — shard_map multi-pod solvers
    get_backend/distribute/Precision — composable step-primitive engine
                            (DESIGN.md §Backends)
"""

from repro.core.anderson import AAConfig                       # noqa: F401
from repro.core.api import AAKMeans, MiniBatchAAKMeans         # noqa: F401
from repro.core.backends import (Backend, Precision,           # noqa: F401
                                 StepResult, distribute, get_backend)
from repro.core.distributed import (make_distributed_kmeans,   # noqa: F401
                                    make_distributed_kmeans_batched,
                                    make_distributed_kmeans_minibatch)
from repro.core.hamerly import hamerly_kmeans                  # noqa: F401
from repro.core.kmeans import (KMeansConfig, aa_kmeans,        # noqa: F401
                               aa_kmeans_batched, aa_kmeans_minibatch,
                               aa_kmeans_minibatch_streamed,
                               aa_kmeans_traced, select_best)
from repro.core.locality import (ReorderConfig,                # noqa: F401
                                 reorder_backend)
from repro.core.lloyd import lloyd_kmeans                      # noqa: F401
from repro.core.minibatch import (MiniBatchConfig,             # noqa: F401
                                  MiniBatchResult)
