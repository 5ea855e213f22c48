"""The four-chip mesh fit cell (``mnist8m-k256.meshfit``): a ``--tiny``
rehearsal on four CPU host devices, its check against planted faults,
and its two per-layer readers on hand-built records."""

import json
import os
import subprocess
import sys
from pathlib import Path
from types import SimpleNamespace

import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
CELL = "mnist8m-k256.meshfit"


def test_tiny_rehearsal_on_four_devices_is_correct():
    env = {**os.environ,
           "XLA_FLAGS": "--xla_force_host_platform_device_count=4",
           "JAX_PLATFORMS": "cpu"}
    r = subprocess.run([sys.executable, "bench/run.py", "--workload", CELL,
                        "--seed", "2147483659", "--seconds", "1",
                        "--trace", "1", "--tiny"], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 3, r.stderr
    assert r.stdout == ""                          # never a result
    line = json.loads([ln for ln in r.stderr.splitlines()
                       if ln.startswith("{")][-1])
    assert line["correct"] is True, line["checks"]
    assert all(c["value"] <= c["limit"] for c in line["checks"].values())
    assert set(line["checks"]) == {"label_gap", "energy_dev",
                                   "lloyd_churn", "failed_fits"}
    assert line["attempted"] >= 4 and line["failed"] == 0
    assert line["device"]["count"] == 4
    # every fit builds its mesh program anew, so each traces it once
    assert line["metrics"]["traces_per_fit.meshfit"]["value"] == 1.0
    # the fit cell's readers read the mesh fits too (the device ones, on
    # a chip's trace only)
    assert line["metrics"]["iters_per_fit"]["value"] == 6.0   # capped
    assert {"compile_ms_per_fit", "accept_pct"} <= set(line["metrics"])


def _answer(x, c):
    from lib import reference
    labels, mind = reference.assign(x, c)
    return {"seed": 1, "centroids": np.asarray(c, np.float32),
            "labels": labels, "energy": float(np.sum(mind, dtype=np.float64)),
            "n_iter": 21}


@pytest.fixture
def meshfit(harness, bench_path):
    return harness.load_module("traffic", "meshfit")


def test_readings_catch_each_fault(meshfit):
    """The check's readings, through `Rows`, on a one-device array,
    against the cell's own limits: a sound fit passes; a fit that
    returned its seeding fails ``lloyd_churn``; a moved label fails
    ``label_gap``; half the rows' energy fails ``energy_dev``."""
    from repro.core import AAKMeans
    from repro.data.synthetic import make_blobs
    x = jnp.asarray(make_blobs(4096, 16, 8, seed=1, spread=1.5))
    rows = meshfit.Rows(x)
    limits = json.loads((ROOT / "bench" / "workloads" /
                         f"{CELL}.json").read_text())["limits"]
    m = AAKMeans(n_clusters=8, init="random", max_iter=20, seed=1).fit(x)
    sound = {"seed": 1, "centroids": np.asarray(m.centroids_),
             "labels": np.asarray(m.labels_), "energy": m.energy_}
    got = meshfit.readings(rows, sound)
    assert all(got[k] <= limits[k] for k in limits), got
    seeding = _answer(x, np.asarray(x)[:800:100])
    assert meshfit.readings(rows, seeding)["lloyd_churn"] > \
        limits["lloyd_churn"]
    moved = {**sound, "labels": sound["labels"].copy()}
    moved["labels"][0] = (moved["labels"][0] + 1) % 8
    assert meshfit.readings(rows, moved)["label_gap"] > limits["label_gap"]
    half = {**sound, "energy": sound["energy"] / 2}
    assert meshfit.readings(rows, half)["energy_dev"] > limits["energy_dev"]


def test_the_control_fails_energy_dev(meshfit):
    """The control, the program's centroids with labels and energy from
    the reference at the precision below the configuration's
    (``precision="high"``), fails ``energy_dev`` at the cell's limit,
    where the sound fit passes it.  The three-pass product loses
    digits of the |x|² − 2x·c + |c|² expansion in proportion to the
    rows' norms: rows off the origin make the loss show at this size,
    as the cell's 8.1M rows do at theirs."""
    from repro.core import AAKMeans
    from repro.data.synthetic import make_blobs
    from lib import reference
    x = jnp.asarray(make_blobs(4096, 64, 8, seed=1, spread=1.5)) + 8.0
    rows = meshfit.Rows(x)
    limit = json.loads((ROOT / "bench" / "workloads" /
                        f"{CELL}.json").read_text())["limits"]["energy_dev"]
    m = AAKMeans(n_clusters=8, init="random", max_iter=20, seed=1).fit(x)
    c = np.asarray(m.centroids_)
    sound = {"seed": 1, "centroids": c, "labels": np.asarray(m.labels_),
             "energy": m.energy_}
    labels, mind = reference.assign(rows, c, "high")
    control = {"seed": 1, "centroids": c, "labels": labels,
               "energy": float(np.sum(mind, dtype=np.float64))}
    assert meshfit.readings(rows, sound)["energy_dev"] < limit / 2
    assert meshfit.readings(rows, control)["energy_dev"] > 2 * limit


def test_rows_read_blocks_across_shards(meshfit):
    """A slice spanning shards comes back whole, in order, on one
    device; ``take`` returns any rows in the order asked."""
    x = jnp.arange(40 * 3, dtype=jnp.float32).reshape(40, 3)
    rows = meshfit.Rows(x)
    rows.shards = [(0, x[:16]), (16, x[16:32]), (32, x[32:])]
    np.testing.assert_array_equal(np.asarray(rows[10:35]),
                                  np.asarray(x[10:35]))
    np.testing.assert_array_equal(rows.take([33, 2, 17]),
                                  np.asarray(x)[[33, 2, 17]])
    assert rows[0:100].shape == (40, 3)


# Two chips, two fits.  On each chip a fit's solver loop is its longest
# %while; all-reduce ops count when they start inside that loop, as a
# union of intervals clipped to its end.  Seeding's psum, the loop of
# another span and the closing evaluation's all-reduce stay out.
TRACE = {
    "window": [0, 10_000],
    "devices": {
        "/device:TPU:0": [
            ["%psum.1", 100, 50],            # seeding, before the loop
            ["%while.2", 200, 1000],         # fit 1's loop [200, 1200)
            ["%while.9", 250, 100],          # a shorter inner while
            ["%all-reduce.3", 300, 100],     # [300, 400)
            ["%all-reduce.3", 350, 100],     # overlaps: union [300, 450)
            ["%psum.86", 1150, 100],         # clipped to [1150, 1200)
            ["%all-reduce.5", 1300, 100],    # closing, after the loop
            ["%while.2", 5000, 2000],        # fit 2's loop
            ["%all-reduce-start.3", 5100, 200],
        ],
        "/device:TPU:1": [
            ["%while.2", 210, 1000],
            ["%all-reduce.3", 310, 100],
            ["%fusion.7", 400, 500],         # compute: not a collective
            ["%while.2", 5010, 2000],
        ],
    },
    "host": [
        ["bench.window", 0, 10_000, 0],
        ["bench.fit", 0, 2000, 0],
        ["bench.fit", 4900, 3000, 0],
    ],
}


def test_collective_share_of_the_solver_loops(harness, bench_path):
    reader = harness.load_module("metrics", "collective_pct.meshfit")
    shares, names = reader.loop_shares(TRACE)
    assert shares == pytest.approx([20.0, 10.0, 10.0, 0.0])
    assert names == ["%all-reduce-start.3", "%all-reduce.3", "%psum.86"]
    run = SimpleNamespace(trace=TRACE, log=lambda *a, **k: None)
    assert reader.read(run) == pytest.approx(10.0)
    # a trace without collectives (one chip, or no loop) reads nothing
    alone = {**TRACE, "devices": {"/device:TPU:1":
                                  TRACE["devices"]["/device:TPU:1"][::3]}}
    assert reader.read(SimpleNamespace(trace=alone, log=print)) is None


def test_traces_per_fit_reads_the_counter(harness):
    reader = harness.load_module("metrics", "traces_per_fit.meshfit")
    fits = [{"n_iter": 21}] * 8
    assert reader.read(SimpleNamespace(fits=fits, traces_before=3,
                                       traces_after=11)) == 1.0
    assert reader.read(SimpleNamespace(fits=fits, traces_before=3,
                                       traces_after=3)) == 0.0
    # a run that never read the counter reads nothing
    assert reader.read(SimpleNamespace(fits=fits)) is None
