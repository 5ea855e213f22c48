"""The reference and its control: exact float32 assignment against a
float64 oracle, and the three-pass bf16 product (``precision="high"``)
failing the label comparison on rows near a tie."""

import json

import numpy as np
import pytest


def test_reference_assignment_matches_float64(bench_path):
    from lib import reference
    rng = np.random.default_rng(0)
    x = rng.standard_normal((3000, 37)).astype(np.float32)
    c = rng.standard_normal((10, 37)).astype(np.float32)
    labels, mind = reference.assign(x, c, block=1024)
    d = ((x[:, None, :].astype(np.float64) - c[None]) ** 2).sum(-1)
    assert reference.label_gap(x, c, labels, d.argmin(1)) < 1e-6
    np.testing.assert_allclose(mind, d.min(1), rtol=1e-4, atol=1e-4)


def test_means_keep_empty_clusters(bench_path):
    from lib import reference
    x = np.array([[0.0, 0.0], [2.0, 0.0], [10.0, 10.0]], np.float32)
    c = np.array([[1.0, 0.0], [10.0, 10.0], [-5.0, -5.0]], np.float32)
    m = np.asarray(reference.means(x, np.array([0, 0, 1]), c))
    np.testing.assert_allclose(m, [[1.0, 0.0], [10.0, 10.0], [-5.0, -5.0]])


def _near_ties(n=4096, d=128, seed=0):
    """Rows that sit between two centroids, nearer to the first by a
    relative gap of 1e-7 to 1e-5 of |x|² + |c|²."""
    rng = np.random.default_rng(seed)
    c = rng.standard_normal((2, d)) * 3.0
    mid = (c[0] + c[1]) / 2
    u = (c[1] - c[0]) / np.linalg.norm(c[1] - c[0])
    noise = rng.standard_normal((n, d)) * 2.0
    noise -= np.outer(noise @ u, u)
    x = mid + noise
    scale = (x * x).sum(1) + (c * c).sum(1).max()
    gap = np.exp(rng.uniform(np.log(1e-7), np.log(1e-5), n)) * scale
    # |x - c1|² - |x - c0|² = 4·t·|c1 - c0|/2 along u
    x -= np.outer(gap / (2 * np.linalg.norm(c[1] - c[0])), u)
    return x.astype(np.float32), c.astype(np.float32)


@pytest.mark.parametrize("workload", ["table1-kddcup99.fit",
                                      "ivf4096-sift128.serve",
                                      "ivf4096-sift128.assign"])
def test_the_control_fails_the_label_comparison(bench_path, workload):
    from lib import reference
    limit = json.loads((bench_path / "workloads" / f"{workload}.json")
                       .read_text())["limits"]["label_gap"]
    x, c = _near_ties()
    exact, _ = reference.assign(x, c, "highest")
    control, _ = reference.assign(x, c, "high")
    assert reference.label_gap(x, c, exact, exact) == 0.0
    assert reference.label_gap(x, c, control, exact) > limit
