"""Bring-up smoke test: the AA K-Means fit, stream and serve paths on a TPU.

    python chip_smoke.py              # one chip: fit, fit check, stream, serve
    python chip_smoke.py --chips 4    # four chips: data-parallel fit vs one
    JAX_PLATFORMS=cpu python chip_smoke.py --tiny   # off-chip rehearsal

The deployment is an IVF coarse-quantizer fit at SIFT width, sized by
FAISS's "Guidelines to choose an index" (256 training points per
centroid): N = 1,048,576 rows, d = 128, K = 4,096, float32, generated
from ``--seed`` by `repro.data.synthetic`.  Everything runs in this one
process, which holds the chip for the whole run.

Phases (one chip):

  * fit        — ``AAKMeans(backend="fused")`` with k-means++ seeding,
                 then ``predict``;
  * fit check  — one compiled fused step from the seed c0 against
                 `kernels.ref`, and the fitted energy against the dense
                 jnp (row-blocked) fit from the same c0;
  * stream     — ``MiniBatchAAKMeans(backend="fused")`` over 65,536-row
                 chunks for two epochs (the weighted and R=2 kernels);
  * serve      — save, serve with ``KMeansServer``, and check a few dozen
                 requests of 1-512 rows against ``AAKMeans.predict``.

With ``--chips 4`` only the data-parallel fit over a 4-device "data" mesh
and the one-chip fit it is compared with run.

The last line of standard output is ``{"ok": true, "device": {...}}``
only when every phase passed on a TPU.  The script exits non-zero, with
no such line, when JAX finds no TPU, when ``REPRO_PALLAS_INTERPRET`` is
set, when the compiled fused step holds no Mosaic kernel, when any check
fails, and always after ``--tiny`` (a rehearsal is not a chip result).
These are smoke facts, not benchmark numbers.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time
import traceback
from pathlib import Path

ROOT = Path(__file__).resolve().parent

FULL = dict(n=1_048_576, d=128, k=4096, chunk=65_536, val=16_384)
TINY = dict(n=8192, d=16, k=64, chunk=2048, val=1024)
MAX_ITER = 100
REF_ROWS = 65_536       # row block of the chunked reference

# Tolerances, stated before the run.
# A label may differ from the reference only at a distance tie: both
# centroids within TIE_RTOL * (|x|^2 + |c|^2) of each other, ~100x the
# f32 rounding of the |x|^2 - 2x.c + |c|^2 expansion both sides use.
TIE_RTOL = 1e-5
# One fused step against the oracle: the energy sums 2^20 f32 terms in a
# different order (rtol 1e-5); the cluster sums add ~256 rows each in a
# different order (rtol 1e-4, atol 1e-3); counts are integers < 2^24 and
# must match exactly.
STEP_ENERGY_RTOL = 1e-5
SUMS_RTOL, SUMS_ATOL = 1e-4, 1e-3
# Fitted energy, fused vs dense from the same c0 after at most MAX_ITER
# iterations: the two solvers round differently, so Anderson accept
# decisions and near-tie assignments can part ways mid-trajectory; both
# descend from one seed and land on nearby local minima.
FIT_RTOL = 1e-2
# Four chips vs one.  One step from c0 differs only in the psum order of
# the cluster stats, so it is held to the one-step tolerances above.  The
# fits differ more: the reduction order moves centroids in the last ulp,
# near-tie rows then flip, and the trajectories part (the tiny CPU
# rehearsal converged in 73 vs 46 iterations, 2.5e-3 apart in energy).
MESH_RTOL = FIT_RTOL


class SmokeFailure(RuntimeError):
    """A phase check did not hold."""


def _check(cond, msg):
    if not cond:
        raise SmokeFailure(msg)


def _log(phase, **kv):
    print(f"{phase}: " + " ".join(f"{k}={v}" for k, v in kv.items()),
          flush=True)


def _timed(fn):
    t0 = time.perf_counter()
    out = fn()
    return out, time.perf_counter() - t0


def _ties_only(x, c, got, want, what):
    """Assert labels ``got`` equal ``want`` except at distance ties; return
    the number of tie rows."""
    import numpy as np
    got, want = np.asarray(got), np.asarray(want)
    bad = np.nonzero(got != want)[0]
    if bad.size == 0:
        return 0
    xb = np.asarray(x[bad], np.float64)
    cg, cw = np.asarray(c, np.float64)[got[bad]], \
        np.asarray(c, np.float64)[want[bad]]
    dg = np.sum((xb - cg) ** 2, axis=1)
    dw = np.sum((xb - cw) ** 2, axis=1)
    scale = np.sum(xb * xb, axis=1) + np.maximum(np.sum(cg * cg, axis=1),
                                                 np.sum(cw * cw, axis=1))
    gap = np.abs(dg - dw) / scale
    _check(np.all(gap <= TIE_RTOL),
           f"{what}: {bad.size} labels differ, largest relative distance "
           f"gap {gap.max():.3e} > {TIE_RTOL} (not a tie)")
    return int(bad.size)


def _reference_assign(x, c):
    """`kernels.ref.assignment_ref` over row blocks (the (N, K) distance
    matrix of the full problem does not fit in HBM)."""
    import jax
    import jax.numpy as jnp
    from repro.kernels import ref
    run = jax.jit(ref.assignment_ref)
    labels, mind = [], []
    for i in range(0, x.shape[0], REF_ROWS):
        lab, md = run(x[i:i + REF_ROWS], c)
        labels.append(lab)
        mind.append(md)
    return jnp.concatenate(labels), jnp.concatenate(mind)


def _make_data(size, seed):
    import jax.numpy as jnp
    from repro.data.synthetic import make_blobs
    # a Gaussian mixture with fewer components than K: many centroids
    # share each component, as in a coarse quantizer over real vectors
    x_host = make_blobs(size["n"], size["d"], size["k"] // 16, seed=seed,
                        spread=1.5)
    return x_host, jnp.asarray(x_host)


def _estimator(size, seed, **kw):
    from repro.core import AAKMeans
    return AAKMeans(n_clusters=size["k"], init="kmeans++",
                    max_iter=MAX_ITER, seed=seed, **kw)


def _seed_c0(x, k, seed):
    """The k-means++ seed exactly as ``AAKMeans.fit`` draws it (n_init 1)."""
    import jax
    from repro.core.init_schemes import batched_init
    keys = jax.random.split(jax.random.PRNGKey(seed), 1)
    return jax.block_until_ready(batched_init("kmeans++", keys, x, k)[0])


def _check_step(x_host, x, c0, res, what):
    """Hold one step's outputs at c0 to the oracle: labels up to ties,
    energy, and the exact stats of the step's own assignment."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels import ref
    lab_r, mind_r = _reference_assign(x, c0)
    ties = _ties_only(x_host, c0, res.labels, lab_r, f"{what} labels")
    e_ref = float(jnp.sum(mind_r))
    e_dev = abs(float(res.energy) - e_ref) / e_ref
    _check(e_dev <= STEP_ENERGY_RTOL,
           f"{what} energy {float(res.energy)} vs reference {e_ref}: "
           f"rel dev {e_dev:.3e} > {STEP_ENERGY_RTOL}")
    sums_r, counts_r = jax.jit(ref.update_ref, static_argnums=2)(
        x, res.labels, c0.shape[0])  # exact stats of the step's labels
    np.testing.assert_allclose(np.asarray(res.sums), np.asarray(sums_r),
                               rtol=SUMS_RTOL, atol=SUMS_ATOL,
                               err_msg=f"{what} sums")
    _check(np.array_equal(np.asarray(res.counts), np.asarray(counts_r)),
           f"{what} counts differ from the reference")
    _log(what, label_ties=ties, energy=float(res.energy), energy_ref=e_ref,
         energy_rel_dev=f"{e_dev:.3e}")
    return e_ref


def phase_fit(size, seed, x_host, x, on_tpu):
    """Fit, predict, and check one fused step and the fitted energy."""
    import jax
    import numpy as np
    from repro.core.backends import get_backend

    k = size["k"]
    c0 = _seed_c0(x, k, seed)

    fused = get_backend("fused")
    step = jax.jit(lambda xx, cc: fused.step(xx, cc, k, ())[0])
    compiled, t_compile = _timed(lambda: step.lower(x, c0).compile())
    has_kernel = "tpu_custom_call" in compiled.as_text()
    _log("fit.step", compile_s=f"{t_compile:.3f}", tpu_custom_call=has_kernel)
    if on_tpu:
        _check(has_kernel, "the compiled fused step holds no Mosaic kernel "
               "(tpu_custom_call): the kernel did not lower for the TPU")
    res = jax.block_until_ready(compiled(x, c0))
    e_ref = _check_step(x_host, x, c0, res, "fit.step")

    model, t_fit = _timed(
        lambda: _estimator(size, seed, backend="fused").fit(x))
    _check(np.isfinite(model.energy_), "fused fit energy is not finite")
    _check(model.energy_ < e_ref, f"fused fit did not descend from c0 "
           f"({model.energy_} >= {e_ref})")
    _log("fit", backend="fused", fit_s=f"{t_fit:.3f}", n_iter=model.n_iter_,
         n_accepted=model.n_accepted_, energy=model.energy_)

    labels, t_pred = _timed(lambda: model.predict(x_host))
    c = model.centroids_
    sub = slice(0, REF_ROWS)
    lab_r, _ = _reference_assign(x[sub], c)
    ties = _ties_only(x_host[sub], c, labels[sub], lab_r, "predict")
    _check(labels.shape == (size["n"],), f"predict shape {labels.shape}")
    _log("predict", rows=labels.shape[0], predict_s=f"{t_pred:.3f}",
         label_ties_in_first_block=ties)

    # the dense jnp engine, row-blocked so (block, K) replaces (N, K)
    dense = get_backend("blocked", block_n=REF_ROWS)
    ref_model, t_ref = _timed(
        lambda: _estimator(size, seed, backend=dense).fit(x))
    dev = abs(model.energy_ - ref_model.energy_) / ref_model.energy_
    _log("fit.check", dense_energy=ref_model.energy_,
         dense_n_iter=ref_model.n_iter_, dense_fit_s=f"{t_ref:.3f}",
         energy_rel_dev=f"{dev:.3e}")
    _check(dev <= FIT_RTOL, f"fused fit energy {model.energy_} vs dense "
           f"{ref_model.energy_}: rel dev {dev:.3e} > {FIT_RTOL}")
    return model


def phase_stream(size, seed, x_host, x, model):
    """Mini-batch fit over on-device chunks with the fused kernels."""
    import jax.numpy as jnp
    import numpy as np
    from repro.core import MiniBatchAAKMeans

    stream, t_fit = _timed(lambda: MiniBatchAAKMeans(
        n_clusters=size["k"], chunk_size=size["chunk"], epochs=2,
        val_size=size["val"], seed=seed, backend="fused").fit(x))
    c = np.asarray(stream.centroids_)
    _check(c.shape == (size["k"], size["d"]) and np.isfinite(c).all(),
           f"stream centroids shape {c.shape} or non-finite values")
    # price both codebooks on one reference block of the training rows
    sub = slice(0, REF_ROWS)
    _, md_s = _reference_assign(x[sub], stream.centroids_)
    _, md_f = _reference_assign(x[sub], model.centroids_)
    e_s, e_f = float(jnp.sum(md_s)), float(jnp.sum(md_f))
    _log("stream", fit_s=f"{t_fit:.3f}", n_steps=stream.n_steps_,
         n_accepted=stream.n_accepted_, block_energy=e_s,
         full_batch_block_energy=e_f, ratio=f"{e_s / e_f:.4f}")
    # two epochs of chunk steps land near, not at, the full-batch optimum
    _check(np.isfinite(e_s) and e_s <= 1.25 * e_f,
           f"stream energy {e_s} exceeds 1.25x the full-batch fit's {e_f}")


def phase_serve(size, seed, x_host, model):
    """Save, serve, and check each answer against AAKMeans.predict."""
    import numpy as np
    from repro.serving import KMeansServer

    rng = np.random.default_rng(seed + 1)
    sizes = rng.integers(1, 513, size=48)
    sizes[:2] = (1, 512)
    starts = rng.integers(0, size["n"] - 512, size=sizes.size)
    requests = [x_host[s:s + m] for s, m in zip(starts, sizes)]
    with tempfile.TemporaryDirectory() as tmp:
        path = Path(tmp) / "model.npz"
        model.save(path)
        t0 = time.perf_counter()
        with KMeansServer(path, batch_size=256) as server:
            futures = [server.submit(r) for r in requests]
            answers = [f.result(timeout=300) for f in futures]
        t_serve = time.perf_counter() - t0
    ties = 0
    for i, (rows, got) in enumerate(zip(requests, answers)):
        want = model.predict(rows)
        _check(got.shape == want.shape,
               f"request {i}: shape {got.shape} vs {want.shape}")
        ties += _ties_only(rows, model.centroids_, got, want,
                           f"serve request {i}")
    _log("serve", requests=len(requests), rows=int(sizes.sum()),
         serve_s=f"{t_serve:.3f}", label_ties=ties)


def phase_mesh(size, seed, x_host, x):
    """Data-parallel fit over a 4-device mesh vs the one-chip fit."""
    import jax
    import numpy as np
    from jax.sharding import PartitionSpec as P
    from repro.core import backends as B
    from repro.core.distributed import shard_dataset, shard_map
    from repro.launch.mesh import make_host_mesh

    devices = jax.devices()
    _check(len(devices) >= 4, f"--chips 4 needs 4 devices, found "
           f"{len(devices)}")
    mesh = make_host_mesh((4,), ("data",))
    x_sh, _ = shard_dataset(x, mesh, ("data",))
    shard_devices = {s.device for s in x_sh.addressable_shards}
    _check(len(shard_devices) == 4,
           f"X's shards sit on {len(shard_devices)} devices, not 4")
    _log("mesh", shards_on_devices=len(shard_devices),
         shard_rows=x_sh.addressable_shards[0].data.shape[0])

    k = size["k"]
    c0 = _seed_c0(x, k, seed)
    dist = B.distribute(B.get_backend("fused"), ("data",))
    step = jax.jit(shard_map(
        lambda xl, cc: dist.step(xl, cc, k, ())[0], mesh=mesh,
        in_specs=(P("data"), P()),
        out_specs=B.StepResult(labels=P("data"), min_sqdist=P("data"),
                               sums=P(), counts=P(), energy=P())))
    res = jax.block_until_ready(step(x_sh, c0))
    _check_step(x_host, x, c0, res, "mesh.step")

    m4, t4 = _timed(lambda: _estimator(size, seed, backend="fused",
                                       mesh=mesh).fit(x))
    m1, t1 = _timed(lambda: _estimator(size, seed, backend="fused").fit(x))
    dev = abs(m4.energy_ - m1.energy_) / m1.energy_
    _log("mesh.fit", chips=4, fit_s=f"{t4:.3f}", n_iter=m4.n_iter_,
         energy=m4.energy_)
    _log("mesh.fit", chips=1, fit_s=f"{t1:.3f}", n_iter=m1.n_iter_,
         energy=m1.energy_, energy_rel_dev=f"{dev:.3e}")
    _check(np.isfinite(m4.energy_), "mesh fit energy is not finite")
    _check(dev <= MESH_RTOL, f"4-chip energy {m4.energy_} vs 1-chip "
           f"{m1.energy_}: rel dev {dev:.3e} > {MESH_RTOL}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the data-parallel mesh phase")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--tiny", action="store_true",
                    help="off-chip rehearsal at a tiny size; never reports "
                         "success")
    args = ap.parse_args(argv)

    if os.environ.get("REPRO_PALLAS_INTERPRET"):
        print("chip_smoke: REPRO_PALLAS_INTERPRET is set; the smoke runs "
              "the compiled kernels only", file=sys.stderr)
        return 2
    if not (ROOT / "src" / "repro").is_dir():
        print(f"chip_smoke: no repro package under {ROOT / 'src'}; run the "
              f"script from a checkout of the repository", file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))

    import jax
    try:
        dev = jax.devices()[0]
    except RuntimeError as e:
        print(f"chip_smoke: JAX started no backend: {e}", file=sys.stderr)
        return 1
    on_tpu = dev.platform == "tpu"
    if not on_tpu and not args.tiny:
        print(f"chip_smoke: JAX finds no TPU (platform {dev.platform!r}); "
              f"nothing to smoke-test", file=sys.stderr)
        return 1

    from repro.runtime.compile_cache import enable_compile_cache
    from repro.kernels import tiles
    cache = enable_compile_cache()
    _log("device", platform=dev.platform, kind=repr(dev.device_kind),
         count=len(jax.devices()), interpret=tiles.interpret_default(),
         compile_cache=cache)

    size = TINY if args.tiny else FULL
    try:
        (x_host, x), t_data = _timed(lambda: _make_data(size, args.seed))
        _log("data", n=size["n"], d=size["d"], k=size["k"],
             dtype=str(x.dtype), make_s=f"{t_data:.3f}")
        if args.chips == 4:
            phase_mesh(size, args.seed, x_host, x)
        else:
            model = phase_fit(size, args.seed, x_host, x, on_tpu)
            phase_stream(size, args.seed, x_host, x, model)
            phase_serve(size, args.seed, x_host, model)
    except Exception:            # every phase failure fails the run
        traceback.print_exc()
        print("chip_smoke: FAILED", file=sys.stderr)
        return 1
    if args.tiny:
        print("chip_smoke: --tiny rehearsal passed; not a chip result",
              file=sys.stderr)
        return 3
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
