"""Readings over the fits of a fit cell's window."""

from __future__ import annotations

from lib import work


def done(run) -> list:
    """The window's fits that returned a model."""
    return [f for f in run.fits if f["n_iter"] is not None]


def step_least_time(run) -> float:
    """Least time of one Lloyd step of the configuration on this chip."""
    data, k = run.config["data"], run.config["estimator"]["n_clusters"]
    return work.least_time(*work.lloyd_step(data["n"], k, data["d"]),
                           run.peaks)
