"""The codebook that the serve and assign cells hold: K fresh rows of the
configuration's data distribution (as K rows sampled from X), in an
estimator that is fitted by assignment, not by ``fit``."""

from __future__ import annotations

import jax

from lib import gen


def centroids(run):
    spec = run.config["data"]
    k = run.config["estimator"]["n_clusters"]
    return jax.block_until_ready(gen.fresh_rows(spec, run.seed, k, stream=1))


def model(run, c):
    """An ``AAKMeans`` that holds ``c`` as its fitted centroids."""
    from repro.core import AAKMeans
    est = run.config["estimator"]
    m = AAKMeans(n_clusters=est["n_clusters"],
                 backend=est.get("backend", "dense"))
    m.centroids_ = c
    return m
