import os
os.environ["XLA_FLAGS"] = (os.environ.get("DRYRUN_XLA_EXTRA", "") +
                           " --xla_force_host_platform_device_count=512")
# Must run before any other import (jax locks device count on first init).

"""Dry-run of the paper's solver itself at pod scale (the
"most representative of the paper's technique" roofline rows).

Workload: one AA-KMeans iteration over N = 2^27 (134M) samples, d = 64,
K = 1000, samples sharded over ("pod","data").  One iteration = assignment
+ psum'd update + energy + the replicated AA solve — the steady-state body
of Algorithm 1 (cost_analysis is exact here: no layer scans).

Variants (§Perf ladder for the K-Means hillclimb):
  split        — dense (N,K) distance matrix materialised, separate passes
  blocked      — assignment evaluated in row blocks (no (N,K) buffer)
  blocked_bf16 — + bf16 sample storage (halves the X stream)
  (fused Pallas single-pass terms are analytic — kernels_bench.py — since
   interpret-mode HLO does not reflect the TPU kernel's memory behaviour)

    PYTHONPATH=src python -m repro.launch.kmeans_dryrun [--mesh both]
"""

import argparse
import dataclasses
import json
import time
import traceback
from pathlib import Path

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core import anderson, lloyd
from repro.core.anderson import AAConfig
from repro.launch.dryrun import (ARTIFACTS, memory_dict, parse_collectives,
                                 parse_dot_flops)
from repro.launch.mesh import data_axes_of, make_production_mesh

N, D, K = 2 ** 27, 64, 1000


def one_iteration(x_local, c, aa_state, e_prev, e_prev2, axes,
                  block_n: int = 0):
    """Steady-state Algorithm-1 body under shard_map (accept path)."""
    cfg = AAConfig()
    res = lloyd.assign(x_local, c.astype(x_local.dtype), block_n=block_n,
                       block_unroll=block_n > 0)
    e_t = jax.lax.psum(lloyd.energy(x_local, c.astype(x_local.dtype),
                                    res.labels), axes)
    aa_state = anderson.adjust_m(aa_state, e_t, e_prev, e_prev2, cfg)
    sums, counts = lloyd.cluster_sums(x_local.astype(jnp.float32),
                                      res.labels, K)
    sums = jax.lax.psum(sums, axes)
    counts = jax.lax.psum(counts, axes)
    c_au = lloyd.update_from_sums(sums, counts, c)
    g = c_au.reshape(-1)
    f = g - c.reshape(-1)
    aa_state, c_next, _, _ = anderson.aa_push_and_solve(aa_state, f, g, cfg)
    return (c_next.reshape(c.shape), aa_state, e_t, e_prev,
            res.labels)


def build_full_solver(mesh):
    """The complete Algorithm-1 solver (lax.while_loop incl. convergence
    psums and the dynamic-m logic) on the production mesh — proves the
    whole program lowers/compiles, complementing the per-iteration
    variants whose costs are loop-free and therefore exactly countable."""
    from repro.core.distributed import make_distributed_kmeans
    from repro.core.kmeans import KMeansConfig
    axes = tuple(mesh.axis_names)
    fit = make_distributed_kmeans(mesh, KMeansConfig(k=K, max_iter=200),
                                  axes)
    x = jax.ShapeDtypeStruct((N, D), jnp.float32,
                             sharding=NamedSharding(mesh, P(axes)))
    c0 = jax.ShapeDtypeStruct((K, D), jnp.float32,
                              sharding=NamedSharding(mesh, P()))
    return fit, (x, c0)


def build(mesh, variant: str):
    # K-Means has no model-parallel dimension: every mesh axis is a data
    # axis (the 256/512 chips all hold sample shards; C is replicated).
    axes = tuple(mesh.axis_names)
    block_n = 0
    dtype = jnp.float32
    if variant.startswith("blocked"):
        block_n = 65536
    if variant.endswith("bf16"):
        dtype = jnp.bfloat16
    n_shards = 1
    for a in axes:
        n_shards *= mesh.shape[a]

    cfg = AAConfig()
    x_spec = P(axes)
    rep = P()

    def step(x_local, c, dF, dG, f_prev, g_prev, ncols, head, m,
             e_prev, e_prev2):
        aa_state = anderson.AAState(dF, dG, f_prev, g_prev, ncols, head, m)
        c2, aa2, e_t, e_p, labels = one_iteration(
            x_local, c, aa_state, e_prev, e_prev2, axes, block_n)
        return (c2, aa2.dF, aa2.dG, aa2.f_prev, aa2.g_prev, aa2.ncols,
                aa2.head, aa2.m, e_t, e_p, labels)

    smapped = jax.shard_map(
        step, mesh=mesh,
        in_specs=(x_spec,) + (rep,) * 10,
        out_specs=(rep,) * 10 + (x_spec,))

    def sds(shape, dt, spec):
        return jax.ShapeDtypeStruct(shape, dt,
                                    sharding=NamedSharding(mesh, spec))

    kd = K * D
    args = (
        sds((N, D), dtype, x_spec),
        sds((K, D), jnp.float32, rep),
        sds((cfg.mbar, kd), jnp.float32, rep),
        sds((cfg.mbar, kd), jnp.float32, rep),
        sds((kd,), jnp.float32, rep),
        sds((kd,), jnp.float32, rep),
        sds((), jnp.int32, rep), sds((), jnp.int32, rep),
        sds((), jnp.int32, rep),
        sds((), jnp.float32, rep), sds((), jnp.float32, rep),
    )
    return jax.jit(smapped), args


def model_flops_kmeans() -> float:
    # useful work: distance cross-term + the segment-sum adds + energy
    return 2.0 * N * K * D + N * D + N * D


def run_variant(mesh_kind: str, variant: str, save=True):
    multi = mesh_kind == "multi"
    mesh = make_production_mesh(multi_pod=multi)
    rec = {"arch": "aa-kmeans-134m-d64-k1000", "shape": f"iter_{variant}",
           "mesh": mesh_kind, "devices": 512 if multi else 256,
           "flags": {"variant": variant}, "tag": "", "ok": False}
    t0 = time.perf_counter()
    try:
        if variant == "full_solver":
            fn, args = build_full_solver(mesh)
        else:
            fn, args = build(mesh, variant)
        lowered = fn.lower(*args)
        rec["time_lower_s"] = round(time.perf_counter() - t0, 2)
        t1 = time.perf_counter()
        compiled = lowered.compile()
        rec["time_compile_s"] = round(time.perf_counter() - t1, 2)
        ca = compiled.cost_analysis()
        rec["hlo_flops_per_device"] = float(ca.get("flops", 0.0))
        rec["hlo_bytes_per_device"] = float(ca.get("bytes accessed", 0.0))
        rec["memory"] = memory_dict(compiled)
        hlo = compiled.as_text()
        rec["hlo_dot_flops_per_device"] = parse_dot_flops(hlo)
        operand, wire, counts = parse_collectives(hlo)
        rec["collective_operand_bytes_per_device"] = operand
        rec["collective_wire_bytes_per_device"] = wire
        rec["collective_counts"] = counts
        rec["collective_total_per_device"] = float(sum(wire.values()))
        rec["model_flops"] = model_flops_kmeans()
        rec["n_params"] = K * D
        rec["n_active_params"] = K * D
        # no scans/loops anywhere (blocked variants unroll the row blocks):
        # cost_analysis is exact for this workload.
        rec["ok"] = True
    except Exception as e:
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-3000:]
    rec["time_total_s"] = round(time.perf_counter() - t0, 2)
    if save:
        ARTIFACTS.mkdir(parents=True, exist_ok=True)
        path = ARTIFACTS / f"aa-kmeans__iter_{variant}__{mesh_kind}.json"
        path.write_text(json.dumps(rec, indent=1))
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--mesh", default="both",
                    choices=["single", "multi", "both"])
    ap.add_argument("--variants", default="split,blocked,blocked_bf16")
    args = ap.parse_args()
    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]
    for mk in meshes:
        for v in args.variants.split(","):
            rec = run_variant(mk, v)
            if rec["ok"]:
                print(f"[ok] kmeans {v} {mk}: "
                      f"flops/dev {rec['hlo_flops_per_device']:.3e} "
                      f"bytes/dev {rec['hlo_bytes_per_device']:.3e} "
                      f"coll/dev {rec['collective_total_per_device']:.3e} "
                      f"temp {rec['memory'].get('temp_size_in_bytes',0)/2**30:.2f}GiB",
                      flush=True)
            else:
                print(f"[FAIL] kmeans {v} {mk}: {rec['error']}", flush=True)


if __name__ == "__main__":
    main()
