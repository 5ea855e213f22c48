"""`AAKMeans(mesh=…).fit` on rows that exist only sharded, on four CPU
host devices (each test a subprocess that sets XLA_FLAGS before jax is
imported): the answer against the plain reference and against the
one-device fit, one trace of the mesh program per configuration, and
no program that gathers X (seeding, the fit, ragged padding)."""

import os
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]

PRELUDE = """
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
mesh = jax.make_mesh((4,), ("data",),
                     axis_types=(jax.sharding.AxisType.Auto,))
rows = NamedSharding(mesh, P("data"))
"""


def _run(code: str, timeout: int = 600) -> str:
    env = {"XLA_FLAGS": "--xla_force_host_platform_device_count=4",
           "PYTHONPATH": str(ROOT / "src"), "PATH": "/usr/bin:/bin",
           "HOME": os.environ.get("HOME", "/tmp"),
           "JAX_PLATFORMS": os.environ.get("JAX_PLATFORMS", "cpu")}
    r = subprocess.run([sys.executable, "-c", PRELUDE + code],
                       capture_output=True, text=True, timeout=timeout,
                       env=env)
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr}"
    return r.stdout


def test_mesh_fit_on_sharded_rows_matches_reference_and_one_device():
    """n 8,192, d 784, K 16, capped at 5 iterations, the configuration of
    the four-chip benchmark cell at its CPU size.  The mesh and the
    one-device fit seed alike (random rows, picked by index) and differ
    only in the order the psum adds the four shards' stats, which the
    Anderson solve can amplify over five iterations: hence the same
    n_iter_ and centroids within 1e-4 of their scale."""
    out = _run("""
from repro.core import AAKMeans
from repro.core.api import mesh_fit_program_traces
from repro.data.synthetic import make_blobs
from repro.kernels.ref import assignment_ref

x_host = make_blobs(8192, 784, 10, seed=7, spread=1.5).astype(np.float32)
x = jax.device_put(x_host, rows)
est = dict(n_clusters=16, init="random", max_iter=5, seed=11)
t0 = mesh_fit_program_traces()
m = AAKMeans(mesh=mesh, **est).fit(x)
m2 = AAKMeans(mesh=mesh, **dict(est, seed=12)).fit(x)
assert mesh_fit_program_traces() - t0 == 1      # one per configuration
assert m.labels_.sharding.is_equivalent_to(rows, 1)   # labels stay sharded
want, mind = assignment_ref(jnp.asarray(x_host), m.centroids_)
np.testing.assert_array_equal(np.asarray(m.labels_), np.asarray(want))
e_ref = float(np.sum(np.asarray(mind), dtype=np.float64))
assert abs(m.energy_ - e_ref) <= 1e-5 * e_ref, (m.energy_, e_ref)

one = AAKMeans(**est).fit(x_host)
assert m.n_iter_ == one.n_iter_ == 6, (m.n_iter_, one.n_iter_)
c, c1 = np.asarray(m.centroids_), np.asarray(one.centroids_)
assert np.abs(c - c1).max() <= 1e-4 * np.abs(c1).max()
print("MESH_FIT_OK")
""")
    assert "MESH_FIT_OK" in out


def test_no_mesh_fit_program_gathers_x():
    """The seeding and the fit program gather nothing of X's size: only
    the picked rows, the (R, K, d+1) stats and, in k-means++, each
    shard's best candidate (an (R, shards) all-gather) cross the
    chips."""
    out = _run("""
import re
from repro.core import AAKMeans
from repro.core.api import _mesh_fit_program
from repro.core.distributed import make_distributed_kmeans_batched, row_take
from repro.core.init_schemes import batched_init

# a compiled module as the device runs it: no module name, no debug
# metadata, and every instruction and computation renamed in order of
# first appearance (the jitted wrapper calls the program that
# make_distributed_kmeans_batched returns, and the inlined call shifts
# XLA's numbering and nothing else)
def program_text(hlo):
    hlo = re.sub(r", metadata=\\{[^}]*\\}", "", hlo)
    head = re.sub(r"^HloModule \\S+,", "HloModule _,", hlo.splitlines()[0])
    names = {}
    return head + re.sub(r"\\b[A-Za-z_][\\w\\-]*\\.\\d+\\b",
                         lambda m: names.setdefault(m.group(0),
                                                    f"v{len(names)}"),
                         hlo[hlo.index("\\n%"):])

def gathered(hlo):
    sizes = [0]
    for line in hlo.splitlines():
        if " all-gather(" in line:
            dims = re.search(r"= \\w+\\[([\\d,]*)\\]", line).group(1)
            sizes.append(int(np.prod([int(d) for d in dims.split(",")])))
    return max(sizes)

x = jax.ShapeDtypeStruct((8192, 784), jnp.float32, sharding=rows)
keys = jax.ShapeDtypeStruct((2, 2), jnp.uint32)
for init in ("random", "kmeans++"):
    seed = jax.jit(lambda k, x: batched_init(
        init, k, x, 16, take=row_take(mesh, ("data",))))
    assert gathered(seed.lower(keys, x).compile().as_text()) <= 8, init
cfg = AAKMeans(n_clusters=16, max_iter=5)._config()
c0s = jax.ShapeDtypeStruct((2, 16, 784), jnp.float32)
program = _mesh_fit_program(mesh, cfg, ("data",), "dense")
assert _mesh_fit_program(mesh, cfg, ("data",), "dense") is program
hlo = program.lower(x, c0s).compile().as_text()
assert gathered(hlo) == 0 and "all-reduce" in hlo
fresh = make_distributed_kmeans_batched(mesh, cfg, ("data",),
                                        backend="dense", pick_best=True)
assert program_text(hlo) == program_text(
    fresh.lower(x, c0s).compile().as_text())
print("NO_GATHER_OK")
""")
    assert "NO_GATHER_OK" in out


@pytest.mark.parametrize("case", ["max_iter", "two_devices",
                                  "two_devices_refit"])
def test_mesh_fit_program_rebuilds_per_configuration(case):
    """After a fit on the four-device mesh, a fit with another
    ``max_iter`` or on a two-device mesh traces a program of its own,
    and a second fit on the two-device mesh, differing only in seed,
    reuses that one; every fit's labels are the plain reference's
    nearest centroids, so no configuration is served a program built
    for another."""
    out = _run(f"""
from repro.core import AAKMeans
from repro.core.api import mesh_fit_program_traces
from repro.data.synthetic import make_blobs
from repro.kernels.ref import assignment_ref

case = {case!r}
x_host = make_blobs(2048, 32, 6, seed=5, spread=1.5).astype(np.float32)
mesh2 = jax.make_mesh((2,), ("data",), devices=jax.devices()[:2],
                      axis_types=(jax.sharding.AxisType.Auto,))
est = dict(n_clusters=8, init="random", max_iter=5, seed=3)

def traced_fit(mesh_, **over):
    t0 = mesh_fit_program_traces()
    m = AAKMeans(mesh=mesh_, **dict(est, **over)).fit(x_host)
    want, _ = assignment_ref(jnp.asarray(x_host), m.centroids_)
    np.testing.assert_array_equal(np.asarray(m.labels_), np.asarray(want))
    return mesh_fit_program_traces() - t0

assert traced_fit(mesh) == 1
if case == "max_iter":
    assert traced_fit(mesh, max_iter=4) == 1
    assert traced_fit(mesh, seed=4) == 0
else:
    assert traced_fit(mesh2) == 1
    assert traced_fit(mesh) == 0
    if case == "two_devices_refit":
        assert traced_fit(mesh2, seed=4) == 0
print("REBUILD_OK")
""")
    assert "REBUILD_OK" in out


def test_shard_dataset_pads_a_ragged_device_array_on_the_device():
    """A device array whose N is no multiple of the shard count is padded
    by a program, never through the host (np.concatenate is disarmed),
    and comes back sharded with the last row repeated; the mesh fit
    takes it and returns labels for the real rows only."""
    out = _run("""
from repro.core import AAKMeans
from repro.core.distributed import shard_dataset

x_host = np.random.default_rng(0).normal(size=(1022, 8)).astype(np.float32)
x = jax.device_put(x_host)

def boom(*a, **k):
    raise AssertionError("X went through the host")
concatenate, np.concatenate = np.concatenate, boom
xs, pad = shard_dataset(x, mesh)
np.concatenate = concatenate
assert pad == 2 and xs.shape == (1024, 8)
assert xs.sharding.is_equivalent_to(rows, 2)
got = np.asarray(xs)
assert np.array_equal(got[:1022], x_host)
assert np.array_equal(got[1022:], np.repeat(x_host[-1:], 2, axis=0))
same, pad0 = shard_dataset(xs, mesh)
assert pad0 == 0 and same is xs              # already laid out: as is
m = AAKMeans(n_clusters=4, init="random", max_iter=3, mesh=mesh).fit(x)
assert m.labels_.shape == (1022,)
print("PAD_OK")
""")
    assert "PAD_OK" in out


def test_mesh_seeding_never_picks_a_padding_row():
    """13 distinct rows over four shards take 3 padding rows, copies of
    the last.  With K = 13 a seeding that draws from all 16 rows nearly
    always seeds some row twice and leaves another real row without a
    centroid; seeding over the real rows alone picks each once, so the
    fit ends with 13 distinct centroids and an energy that is only the
    rounding of the distance expansion (rows lie units apart)."""
    out = _run("""
from repro.core import AAKMeans

x = jax.device_put(np.random.default_rng(3).normal(size=(13, 8))
                   .astype(np.float32))
for init in ("random", "kmeans++"):
    for seed in range(4):
        m = AAKMeans(n_clusters=13, init=init, max_iter=3, seed=seed,
                     mesh=mesh).fit(x)
        c = np.asarray(m.centroids_)
        assert len(np.unique(c, axis=0)) == 13, (init, seed)
        assert m.energy_ < 1e-3, (init, seed, m.energy_)
print("SEED_OK")
""")
    assert "SEED_OK" in out
