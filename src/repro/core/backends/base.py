"""The step-primitive backend protocol for Algorithm 1 (DESIGN.md §Backends).

The paper's Sec-2.1 overhead argument prices one Algorithm-1 iteration at
one application of the fixed-point map G = Update ∘ Assign — i.e. one pass
over X — plus O(m·K·d) for the Anderson solve.  Separate assign/update/
energy call sites would force the driver into two to three X passes per
iteration and make the fused single-pass Pallas kernel unusable.  A
`Backend`'s core op is instead

    step(x, c, k, carry) -> (StepResult(labels, min_sqdist, sums, counts,
                                        energy), carry)

one logical pass over X that returns everything an iteration needs: the
fresh assignment, the energy E(P, C) (= sum of min squared distances), and
the partial cluster statistics from which G(C) follows without touching X
again (`centroids_from_step`).  assign/update/energy remain available as
derived ops for callers that need a single piece.

``carry`` is an opaque per-backend pytree threaded through the solver loop
(default: the empty tuple).  Stateless backends ignore it; the Hamerly
backend keeps its distance bounds there so bound-based skipping survives
across iterations — including non-Lloyd centroid moves (AA steps, reverts),
whose bound update only needs the centroid drift since the previous step.

Carry vmap contract (DESIGN.md §Batching): the batched driver
(kmeans.aa_kmeans_batched) maps ``step`` over a leading restart/problem
axis, so a carry must be a pytree of fixed-shape arrays (or empty
containers) whose shapes depend only on (N, K, d) — never on data values —
and ``init_carry``/``step`` must be traceable under ``jax.vmap``.  The
driver freezes a converged restart's carry with a leaf-wise select, so a
carry must also tolerate being held constant while other restarts advance
(true for anything that is pure state, e.g. the Hamerly bounds).

Orthogonal axes, composable by construction:

    local compute — which backend (dense / blocked / pallas / fused /
                    hamerly), selected via `get_backend(name)`;
    precision     — `Precision(compute, accum)` policy applied inside the
                    backend (bf16 distance math, f32 accumulation);
    distribution  — `distribute(backend, axes)` wraps *any* local backend
                    with the psum reductions for a shard_map mesh.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.core import lloyd
from repro.core.lloyd import AssignResult


class StepResult(NamedTuple):
    """Everything one pass over X yields for one Algorithm-1 iteration.

    labels     : (N,) int32 — fresh assignment P = Assign(X, C)
    min_sqdist : (N,) float — squared distance to the assigned centroid
                 (local rows under distribution)
    sums       : (K, d) accum-dtype per-cluster sums (reduced across shards
                 for distributed backends)
    counts     : (K,) accum-dtype per-cluster counts (reduced likewise)
    energy     : scalar E(P, C) = sum(min_sqdist) (reduced likewise)
    """
    labels: jax.Array
    min_sqdist: jax.Array
    sums: jax.Array
    counts: jax.Array
    energy: jax.Array


@dataclasses.dataclass(frozen=True)
class Precision:
    """Compute-vs-accumulate dtype policy applied inside a backend.

    compute — dtype for the distance computation (None: the input dtype;
              bf16 halves the X stream on TPU, distances still accumulate
              in f32 via preferred_element_type on the MXU paths).
    accum   — dtype for cluster sums/counts and the energy (None: f32,
              matching the Pallas kernels' accumulators).  ``accum_dtype``
              floors the request at f32 (and `lloyd.cluster_sums`
              promotes internally for direct callers): a sub-f32 count
              saturates — bf16 stops counting at 256 members — which is a
              correctness bug, not a precision trade-off, so every step
              slot (single, batched one-hot, weighted minibatch)
              accumulates at >= f32.
    """
    compute: Optional[Any] = None
    accum: Optional[Any] = None

    def compute_cast(self, a: jax.Array) -> jax.Array:
        return a if self.compute is None else a.astype(self.compute)

    @property
    def accum_dtype(self):
        if self.accum is None:
            return jnp.float32
        return jnp.promote_types(self.accum, jnp.float32)


DEFAULT_PRECISION = Precision()


def _default_init_carry(x, c, k):
    return ()


def _default_all_equal(a, b):
    return jnp.all(a == b)


def _identity(s):
    return s


@dataclasses.dataclass(frozen=True)
class Backend:
    """A local-compute engine for Algorithm 1, keyed by the step primitive.

    Instances are immutable and hashable, so a Backend can be a static jit
    argument.  Use the module-level factories / `get_backend` rather than
    constructing directly; `distribute` wraps any instance for a
    shard_map mesh.
    """
    name: str
    # (x, c, k, carry) -> (StepResult, carry): ONE logical pass over X.
    step_fn: Callable = None
    # Optional natively-batched step: (x, cs, k, carries, w=None) ->
    # (StepResult with a leading R axis, carries), where cs is (R, K, d)
    # and x is (N, d) shared or (R, N, d) per-problem.  The batched driver
    # prefers this over jax.vmap(step_fn) when set — a hand-batched
    # formulation can share the X stream across restarts and use matmul
    # cluster stats where the vmapped scatter would serialise; the
    # pallas/fused engines run all R restarts as the leading grid axis of
    # ONE kernel launch instead of vmapping pl.pallas_call.  Must match
    # step_fn's semantics per row (same labels/energy up to reduction
    # order).  ``w`` (R, N) >= 0, when given, scales each row's
    # contribution to sums/counts/energy per problem — the hierarchy
    # engine's padding mask (w = 0 rows vanish exactly, DESIGN.md
    # §Hierarchy); labels/min_sqdist stay per-row and unweighted, exactly
    # the minibatch contract lifted to the restart axis.
    batched_step_fn: Optional[Callable] = None
    # Optional weighted step for streaming chunks (DESIGN.md §Streaming):
    # (x, c, k, w, carry) -> (StepResult, carry), where w (N,) >= 0 scales
    # each row's contribution to sums/counts/energy (w = 0 marks a padding
    # row).  labels and min_sqdist stay per-row and unweighted.  When None,
    # ``minibatch_step`` falls back to step_fn for the assignment plus one
    # weighted segment-sum over the chunk to reweight the stats; the
    # dense/blocked/pallas/fused engines all weight natively in-pass.
    minibatch_step_fn: Optional[Callable] = None
    # (x, labels, k) -> (sums, counts): partial stats of a known assignment
    # (the update half of G; used by the derived update op and by
    # distribute's psum wrapping).
    stats_fn: Callable = None
    # (x, c) -> AssignResult: standalone assignment (predict).
    assign_fn: Callable = None
    # (x, c, labels) -> scalar: FULLY-REDUCED energy of a fixed assignment
    # (distributed backends psum inside; do not compose with reduce_scalar).
    energy_fn: Callable = lloyd.energy
    all_equal_fn: Callable = _default_all_equal
    reduce_scalar: Callable = _identity
    init_carry_fn: Callable = _default_init_carry
    precision: Precision = DEFAULT_PRECISION
    # mesh axes this backend's step already psum-reduces over; set by
    # `distribute` — empty for local backends.
    axes: Tuple[str, ...] = ()

    # -- core op ----------------------------------------------------------

    @jax.named_scope("repro.step")
    def step(self, x, c, k, carry=()):
        return self.step_fn(x, c, k, carry)

    @jax.named_scope("repro.step")
    def batched_step(self, x, cs, k, carries, x_batched: bool = False,
                     w=None):
        """R restarts' steps at once; falls back to vmapping ``step``.
        ``x_batched`` marks x as (R, N, d) rather than shared (N, d);
        ``w`` (R, N) adds per-problem row weights (see batched_step_fn)."""
        if self.batched_step_fn is not None:
            return self.batched_step_fn(x, cs, k, carries, w=w)
        xa = 0 if x_batched else None
        if w is None:
            return jax.vmap(lambda xx, cc, cr: self.step_fn(xx, cc, k, cr),
                            in_axes=(xa, 0, 0))(x, cs, carries)
        # weighted fallback: the minibatch slot per problem.  Valid as the
        # batched slot because the hierarchy driver's per-problem rows are
        # FIXED across steps (unlike streaming chunks), so a data-dependent
        # carry keeps meaning between calls.
        return jax.vmap(
            lambda xx, cc, ww, cr: self.minibatch_step(xx, cc, k, ww, cr),
            in_axes=(xa, 0, 0, 0))(x, cs, w, carries)

    @jax.named_scope("repro.step")
    def minibatch_step(self, x, c, k, w, carry=()):
        """Weighted single pass over a chunk (DESIGN.md §Streaming).

        Row weights ``w`` scale each row's contribution to the cluster
        stats and the energy — the remainder-padded rows of a streaming
        chunk carry w = 0 and vanish from every reduction.  Chunk contents
        change between calls, so a data-dependent carry (e.g. Hamerly
        bounds, which are per-row state of *this* chunk's rows) must be
        re-initialised per chunk by the caller; the returned carry is only
        meaningful while the same chunk is re-stepped."""
        if self.minibatch_step_fn is not None:
            return self.minibatch_step_fn(x, c, k, w, carry)
        res, carry = self.step_fn(x, c, k, carry)
        wa = w.astype(res.sums.dtype)
        sums, counts = lloyd.weighted_cluster_sums(
            x.astype(res.sums.dtype), res.labels, wa, k)
        energy = jnp.sum(res.min_sqdist.astype(res.energy.dtype) * wa)
        return StepResult(res.labels, res.min_sqdist, sums, counts,
                          energy), carry

    def init_carry(self, x, c, k):
        return self.init_carry_fn(x, c, k)

    def centroids_from_step(self, x, res: StepResult, k: int, c_prev):
        """G(C) from the step's partial stats — no further pass over X."""
        c_new = lloyd.update_from_sums(res.sums, res.counts,
                                       c_prev.astype(res.sums.dtype))
        return c_new.astype(c_prev.dtype)

    # -- derived ops ------------------------------------------------------

    def assign(self, x, c) -> AssignResult:
        return self.assign_fn(x, c)

    def update(self, x, labels, k, c_prev):
        sums, counts = self.stats_fn(x, labels, k)
        c_new = lloyd.update_from_sums(sums, counts,
                                       c_prev.astype(sums.dtype))
        return c_new.astype(c_prev.dtype)

    def energy(self, x, c, labels):
        return self.energy_fn(x, c, labels)

    def all_equal(self, a, b):
        return self.all_equal_fn(a, b)

    def g_map(self, x, c, k):
        """One fixed-point map application; returns (G(c), StepResult)."""
        res, _ = self.step(x, c, k, self.init_carry(x, c, k))
        return self.centroids_from_step(x, res, k, c), res


# ---------------------------------------------------------------------------
# Registry
# ---------------------------------------------------------------------------

_REGISTRY: dict = {}
_INSTANCES: dict = {}


def register_backend(name: str, factory: Callable[..., Backend]) -> None:
    """Register a backend factory under a string key.  Re-registering a
    name replaces the factory and drops any cached instances built by the
    previous one."""
    _REGISTRY[name] = factory
    for key in [k for k in _INSTANCES if k[0] == name]:
        del _INSTANCES[key]


def backend_names() -> Tuple[str, ...]:
    return tuple(sorted(_REGISTRY))


def get_backend(name: str, **opts) -> Backend:
    """Construct (and cache) a backend by name: "dense" | "blocked" |
    "pallas" | "fused" | "hamerly".  Caching keeps the returned object
    identity stable so jit'd solvers keyed on the backend do not recompile
    per call site."""
    if name not in _REGISTRY:
        raise KeyError(f"unknown backend {name!r}; registered: "
                       f"{', '.join(backend_names())}")
    try:
        key = (name, tuple(sorted(opts.items())))
        cached = _INSTANCES.get(key)
    except TypeError:  # unhashable option (e.g. a callable): build fresh
        return _REGISTRY[name](**opts)
    if cached is None:
        cached = _INSTANCES[key] = _REGISTRY[name](**opts)
    return cached


# ---------------------------------------------------------------------------
# Distribution combinator
# ---------------------------------------------------------------------------

def distribute(backend: Backend, axes: Sequence[str]) -> Backend:
    """Wrap *any* local backend for execution inside shard_map.

    The returned backend's step runs the local step on the shard-local rows
    and psum-reduces the (K,(d+1))-sized stats plus the scalar energy over
    ``axes`` — the only communication of the solver.  labels/min_sqdist
    (and any carry, e.g. Hamerly bounds) stay shard-local.  Convergence
    checks and standalone energies reduce likewise.
    """
    if backend.axes:
        raise ValueError(
            f"backend {backend.name!r} is already distributed over "
            f"{backend.axes}; wrapping it again would double-psum the "
            f"stats and inflate the reported energy")
    axes = tuple(axes)

    # Every collective of the distributed solver runs under one device
    # scope, so a trace names its all-reduces apart from the local work.
    def psum(a):
        with jax.named_scope("repro.collective"):
            return jax.lax.psum(a, axes)

    def reduce_carry(carry):
        """Per-row bounds stay shard-local, but the BoundStats scalars a
        bound backend reports are per-shard fractions — pmean them so
        every shard carries the GLOBAL elimination fractions (and so the
        carry leaves really are replicated where `loop_state_specs`
        classifies them as such).  The group drift itself needs no
        collective: C is replicated, so every shard derives identical
        drifts."""
        from repro.core.backends.bounds import BoundStats

        def fix(node):
            if isinstance(node, BoundStats):
                with jax.named_scope("repro.collective"):
                    return jax.tree_util.tree_map(
                        lambda a: jax.lax.pmean(a, axes), node)
            return node

        return jax.tree_util.tree_map(
            fix, carry, is_leaf=lambda n: isinstance(n, BoundStats))

    def reduce_step(res: StepResult) -> StepResult:
        """The (K,(d+1))-sized stats and the energy, summed over shards;
        labels and min_sqdist stay shard-local."""
        return res._replace(sums=psum(res.sums), counts=psum(res.counts),
                            energy=psum(res.energy))

    def step_fn(x, c, k, carry):
        res, carry = backend.step_fn(x, c, k, carry)
        return reduce_step(res), reduce_carry(carry)

    # The local batched step (when present) must be re-wrapped so its
    # (R, K, d+1)-stats psum too — one collective covers all R restarts.
    # Leaving the inherited local batched_step_fn in place would silently
    # skip the reduction; when the local backend has none, None makes the
    # batched driver fall back to vmapping the psum-wrapped step above.
    if backend.batched_step_fn is not None:
        def batched_step_fn(x, cs, k, carries, w=None):
            res, carries = backend.batched_step_fn(x, cs, k, carries, w=w)
            return reduce_step(res), reduce_carry(carries)
    else:
        batched_step_fn = None

    # The streaming chunk step reduces exactly like the full step: one
    # (K,(d+1))-stat psum plus the scalar chunk energy per chunk — the
    # only communication of the streaming solver (DESIGN.md §Streaming).
    # Wrapping the *method* (not the field) keeps the generic weighted
    # fallback local-then-reduced even for backends without a native
    # minibatch_step_fn.
    def minibatch_step_fn(x, c, k, w, carry):
        res, carry = backend.minibatch_step(x, c, k, w, carry)
        return reduce_step(res), reduce_carry(carry)

    def stats_fn(x, labels, k):
        sums, counts = backend.stats_fn(x, labels, k)
        return psum(sums), psum(counts)

    def energy_fn(x, c, labels):
        return psum(backend.energy_fn(x, c, labels))

    def all_equal_fn(a, b):
        return psum(jnp.sum((a != b).astype(jnp.int32))) == 0

    return dataclasses.replace(
        backend,
        name=f"{backend.name}@{'x'.join(axes)}",
        step_fn=step_fn, batched_step_fn=batched_step_fn,
        minibatch_step_fn=minibatch_step_fn,
        stats_fn=stats_fn, energy_fn=energy_fn,
        all_equal_fn=all_equal_fn,
        reduce_scalar=psum,
        axes=axes)


# ---------------------------------------------------------------------------
# Instrumentation (pass counting — tests/test_backends.py)
# ---------------------------------------------------------------------------

def instrument(backend: Backend, on_step: Callable[[], None]) -> Backend:
    """Wrap a backend so ``on_step`` fires (host-side) once per *executed*
    step — i.e. per pass over X — including inside jit / lax.cond /
    lax.while_loop, where only the taken branch triggers the callback."""

    def step_fn(x, c, k, carry):
        jax.debug.callback(lambda: on_step())
        return backend.step_fn(x, c, k, carry)

    if backend.batched_step_fn is not None:
        def batched_step_fn(x, cs, k, carries, w=None):
            jax.debug.callback(lambda: on_step())
            return backend.batched_step_fn(x, cs, k, carries, w=w)
    else:
        batched_step_fn = None

    # A native minibatch step is a pass over the chunk; without one the
    # fallback routes through the counted step_fn above, so chunk passes
    # are counted either way.
    if backend.minibatch_step_fn is not None:
        def minibatch_step_fn(x, c, k, w, carry):
            jax.debug.callback(lambda: on_step())
            return backend.minibatch_step_fn(x, c, k, w, carry)
    else:
        minibatch_step_fn = None

    return dataclasses.replace(backend, name=f"{backend.name}+count",
                               step_fn=step_fn,
                               batched_step_fn=batched_step_fn,
                               minibatch_step_fn=minibatch_step_fn)
