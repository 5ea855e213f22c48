"""Composable backend engine for Algorithm 1 (DESIGN.md §Backends).

    from repro.core.backends import get_backend, distribute, Precision

    backend = get_backend("fused")                       # local compute
    backend = get_backend("dense",
                          precision=Precision(jnp.bfloat16))  # precision
    ops = distribute(backend, ("pod", "data"))           # any mesh

Step slots per backend: ``step`` (one pass over X), ``batched_step``
(R restarts at once), ``minibatch_step`` (weighted chunk pass for the
streaming solver; DESIGN.md §Streaming).  tests/test_conformance.py pins
every registered backend x slot x precision against the kernels/ref.py
oracle.

Registered backends:

    dense        — jnp reference semantics (the oracle)
    blocked      — row-blocked distances, bounded (block_n, K) intermediate
    pallas       — separate tiled assignment/update kernels (decomposed)
    fused        — single-pass Pallas kernel: one X read per accepted
                   iteration at arbitrary K (k-tiled; DESIGN.md §Kernels-v2)
    hamerly      — scalar second-closest bound carried across iterations
    elkan        — per-(row, k-group) lower bounds + centre-centre gate
                   (groups sized like the fused kernel's k-tiles)
    yinyang      — pure group filtering, no K x K term (t = K/10 groups)
    fused_bounds — the fused kernel consuming the group bounds to SKIP
                   whole centroid tiles via a tile-level predicate
                   (DESIGN.md §Bounds)

All three Pallas engines fill every step slot natively: batched steps
run R restarts as the kernels' leading grid axis, minibatch steps fold
row weights into the stats in-pass.  The bound family threads its carry
— (labels, upper, lower, c_last, BoundStats) — through the solver loop;
`distribute()` keeps the bounds shard-local and pmean's the stats.

Every bound backend also registers a ``<name>_reorder`` variant wrapping
it in the locality engine (churn-triggered cluster-sorted row reordering,
DESIGN.md §Locality) — original-order outputs stay bit-identical, the
kernel sees locality-ordered rows.  Reorder policy knobs (``warmup``,
``churn_threshold``, ``sort_tile``) pass through `get_backend` opts; the
rest go to the inner backend's factory.
"""

from repro.core.backends.base import (Backend, Precision,        # noqa: F401
                                      StepResult, backend_names,
                                      distribute, get_backend,
                                      instrument, register_backend)
from repro.core.backends.bounds import BoundStats                # noqa: F401
from repro.core.backends.dense import (blocked_backend,          # noqa: F401
                                       dense_backend)
from repro.core.backends.elkan import elkan_backend              # noqa: F401
from repro.core.backends.hamerly import hamerly_backend          # noqa: F401
from repro.core.backends.pallas import (fused_backend,           # noqa: F401
                                        fused_bounds_backend,
                                        pallas_backend)
from repro.core.backends.yinyang import yinyang_backend          # noqa: F401

register_backend("dense", dense_backend)
register_backend("blocked", blocked_backend)
register_backend("pallas", pallas_backend)
register_backend("fused", fused_backend)
register_backend("hamerly", hamerly_backend)
register_backend("elkan", elkan_backend)
register_backend("yinyang", yinyang_backend)
register_backend("fused_bounds", fused_bounds_backend)


def _reorder_factory(inner_name):
    def factory(*, warmup=None, churn_threshold=None, sort_tile=None,
                **inner_opts):
        from repro.core.locality import ReorderConfig, reorder_backend
        cfg = ReorderConfig()
        if warmup is not None:
            cfg = _dc.replace(cfg, warmup=warmup)
        if churn_threshold is not None:
            cfg = _dc.replace(cfg, churn_threshold=churn_threshold)
        if sort_tile is not None:
            cfg = _dc.replace(cfg, sort_tile=sort_tile)
        return reorder_backend(get_backend(inner_name, **inner_opts), cfg)
    return factory


import dataclasses as _dc  # noqa: E402

for _name in ("hamerly", "elkan", "yinyang", "fused_bounds"):
    register_backend(f"{_name}_reorder", _reorder_factory(_name))
del _name
