"""Mesh fit traffic: ``AAKMeans(mesh=…, **estimator).fit(X)`` back to
back, with X's rows sharded over the cell's chips.

The rows exist only sharded: one program makes each chip's share of the
configuration's Gaussian mixture on that chip, block by block, from its
own key, so no chip and no host ever holds more than its shard.  The
mesh is one ``("data",)`` axis over ``jax.devices()[:chips]``; the
centroids are replicated.

Fits run as in `traffic/fit.py`: whole passes over ``params.fit_seeds``
in an order ``--seed`` draws, the window closing at the end of the pass
running after ``--seconds``, and one fit of each seed held to the plain
reference (`label_gap`, `energy_dev`, `lloyd_churn`, `failed_fits`,
with the same definitions).  The reference reads the sharded rows block
by block, each block copied to the first chip (`Rows`).

Set-up imports ``mesh_fit_program_traces`` first: a checkout without
that counter cannot run this cell, and fails before making any data.
The window records the counter before and after, for
``traces_per_fit.meshfit``.
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from lib import gen, reference
from traffic import fit as fit_traffic

AXIS = "data"
BLOCK_ROWS = 65_536
PICK = 256


def mesh(run):
    chips = run.workload["chips"]
    devices = jax.devices()[:chips]
    if len(devices) < chips:
        raise RuntimeError(f"the cell needs {chips} devices; JAX finds "
                           f"{len(devices)}")
    return jax.sharding.Mesh(np.array(devices), (AXIS,))


def _block(n_local: int) -> int:
    """The largest block of at most BLOCK_ROWS rows that divides a shard
    and keeps whole (8, 128) tiles, else the whole shard."""
    for b in range(min(BLOCK_ROWS, n_local), 7, -1):
        if n_local % b == 0 and b % 8 == 0:
            return b
    return n_local


def rows(spec: dict, mesh_) -> jax.Array:
    """The ``gaussian_mixture`` rows of a configuration, sharded over the
    mesh's rows: shard i holds ``n / shards`` rows drawn from the
    mixture's centres (`gen.mixture_params` of ``spec["seed"]``) with
    keys folded from i and the block, made on its own chip."""
    if spec["kind"] != "gaussian_mixture":
        raise ValueError("mesh rows are drawn from a gaussian_mixture")
    centers, scales = gen.mixture_params(
        gen.key(spec["seed"], 0), d=spec["d"], n_comp=spec["n_comp"],
        spread=spec["spread"])
    return rows_program(spec, mesh_)(gen.key(spec["seed"], 1), centers,
                                     scales)


def rows_program(spec: dict, mesh_):
    """``(key, centers, scales) -> rows``: the program `rows` runs."""
    shards = mesh_.shape[AXIS]
    n, d = spec["n"], spec["d"]
    if n % shards:
        raise ValueError(f"n = {n} rows do not divide over {shards} chips")
    n_local = n // shards
    block = _block(n_local)

    def local(k, centers, scales):
        k = jax.random.fold_in(k, jax.lax.axis_index(AXIS))
        keys = jax.vmap(lambda b: jax.random.fold_in(k, b))(
            jnp.arange(n_local // block))
        out = jax.lax.map(
            lambda kb: gen.mixture_rows(kb, centers, scales, n=block), keys)
        return out.reshape(n_local, d)

    return jax.jit(jax.shard_map(local, mesh=mesh_,
                                 in_specs=(P(), P(), P()),
                                 out_specs=P(AXIS)),
                   out_shardings=NamedSharding(mesh_, P(AXIS)))


class Rows:
    """Sharded rows as the reference reads them: ``shape``, and a slice
    ``[i:j]`` as one array on the first chip, made of the pieces of the
    shards it spans.  Only a block is ever copied."""

    def __init__(self, x: jax.Array):
        self.shape = x.shape
        self.device = jax.devices()[0]
        self.shards = sorted(
            ((s.index[0].start or 0, s.data) for s in x.addressable_shards),
            key=lambda s: s[0])

    def pieces(self, lo: int, hi: int):
        for start, data in self.shards:
            a, b = max(lo, start), min(hi, start + data.shape[0])
            if a < b:
                yield jax.device_put(data[a - start:b - start], self.device)

    def __getitem__(self, sl: slice):
        lo, hi, _ = sl.indices(self.shape[0])
        parts = list(self.pieces(lo, hi))
        return parts[0] if len(parts) == 1 else jnp.concatenate(parts)

    def take(self, idx) -> np.ndarray:
        """Rows ``idx`` (sorted or not) as a host array, picked on each
        shard's chip (`_pick`) in fixed chunks of PICK rows."""
        idx = np.asarray(idx)
        out = np.empty((idx.size, self.shape[1]), np.float32)
        for start, data in self.shards:
            mine = np.nonzero((idx >= start)
                              & (idx < start + data.shape[0]))[0]
            for i in range(0, mine.size, PICK):
                part = mine[i:i + PICK]
                local = np.full(PICK, -1, np.int32)
                local[:part.size] = idx[part] - start
                out[part] = np.asarray(_pick(data, local))[:part.size]
        return out


@jax.jit
def _pick(data, local):
    """Rows ``local`` of one shard (-1 picks a zero row), as a one-hot
    product at HIGHEST, which is exact: a gather would first copy the
    whole shard into a row-major layout (7.26 GB at d = 784)."""
    onehot = (local[:, None] == jnp.arange(data.shape[0])).astype(data.dtype)
    return jnp.matmul(onehot, data, precision=jax.lax.Precision.HIGHEST)


def _estimator(run, seed, **over):
    from repro.core import AAKMeans
    return AAKMeans(seed=int(seed), mesh=run.mesh, data_axes=(AXIS,),
                    **{**run.config["estimator"], **over})


def setup(run):
    from repro.core.api import mesh_fit_program_traces
    run.program_traces = mesh_fit_program_traces
    run.mesh = mesh(run)
    t0 = time.perf_counter()
    run.x = jax.block_until_ready(rows(run.config["data"], run.mesh))
    t1 = time.perf_counter()
    seeds = list(run.params["fit_seeds"])
    rng = np.random.default_rng(run.seed)
    run.order = [seeds[i] for i in rng.permutation(len(seeds))]
    run.rng = rng
    # one fit outside the set compiles and warms every program a fit uses
    model = _estimator(run, run.params["warmup_seed"]).fit(run.x)
    jax.block_until_ready(model.labels_)
    run.log("setup", data_s=round(t1 - t0, 3),
            warmup_fit_s=round(time.perf_counter() - t1, 3),
            shards=run.mesh.shape[AXIS])
    stats = run.mesh.devices.flat[0].memory_stats() or {}
    run.log("memory", **{k: stats.get(k) for k in (
        "bytes_in_use", "peak_bytes_in_use", "bytes_limit")})


def window(run):
    run.fits, run.models, run.errors = [], [], []
    run.traces_before = run.program_traces()
    t_close = time.perf_counter() + run.seconds
    i = 0
    while True:
        seed = run.order[i % len(run.order)]
        t0 = time.perf_counter()
        try:
            with jax.profiler.TraceAnnotation("bench.fit"):
                model = _estimator(run, seed).fit(run.x)
                jax.block_until_ready(model.labels_)
        except Exception as e:       # a fit that raises counts as failed
            run.errors.append(f"fit seed {seed}: {e!r}")
            model = None
        t1 = time.perf_counter()
        run.fits.append({"seed": seed, "wall_s": t1 - t0,
                         "n_iter": model.n_iter_ if model else None,
                         "n_accepted": model.n_accepted_ if model else None})
        run.models.append(model)
        i += 1
        if t1 >= t_close and i % len(run.order) == 0:
            break
    run.traces_after = run.program_traces()


end_to_end = fit_traffic.end_to_end
release = fit_traffic.release


def readings(rows_, answer) -> dict:
    """The check's three numbers for one fit's answer, the reference
    reading the rows through ``rows_`` (a `Rows`)."""
    c, labels = answer["centroids"], answer["labels"]
    want, mind = reference.assign(rows_, c)
    bad = np.nonzero(labels != want)[0]
    gap = reference.label_gap(rows_.take(bad), c, labels[bad], want[bad])
    e_ref = float(np.sum(mind, dtype=np.float64))
    moved, _ = reference.assign(rows_, reference.means(rows_, labels, c))
    return {"label_gap": gap,
            "energy_dev": abs(answer["energy"] - e_ref) / e_ref,
            "lloyd_churn": float(np.mean(moved != labels))}


def check(run):
    rows_ = Rows(run.x)
    worst = {"label_gap": 0.0, "energy_dev": 0.0, "lloyd_churn": 0.0}
    for a in run.answers:
        for name, value in readings(rows_, a).items():
            worst[name] = max(worst[name], value)
    limits = run.workload["limits"]
    out = {name: {"value": v, "limit": limits[name]}
           for name, v in worst.items()}
    out["failed_fits"] = {"value": len(run.errors), "limit": 0}
    return out
