"""Whole runs of each cell at its tiny size on the CPU (``--tiny``): the
harness finds everything by name, the window runs end to end, the
answers are checked, and a rehearsal never prints a result.  Then the
timed path is broken underneath, once for each fault a cell can have,
and ``correct`` has to come out false."""

import jax.numpy as jnp
import numpy as np
import pytest

CELLS = ["table1-kddcup99.fit", "ivf4096-sift128.serve",
         "ivf4096-sift128.assign"]


@pytest.mark.parametrize("workload", CELLS)
def test_rehearsal_is_correct_and_prints_no_result(rehearse, capsys,
                                                   workload):
    rc, line, _ = rehearse(workload, trace=1)
    assert rc == 3
    assert capsys.readouterr().out == ""        # no result on stdout
    assert line["correct"] is True, line["checks"]
    assert line["attempted"] > 0 and line["failed"] == 0
    assert line["device"]["platform"] == "cpu"
    assert list(line)[-1] == "checks"


# -- faults, planted behind the public entry points the cells drive ----------
# (AAKMeans.fit, KMeansServer.submit, AAKMeans.predict), so that they
# stay valid while the program's internals change

def _assign(x, c):
    x, c = np.asarray(x, np.float64), np.asarray(c, np.float64)
    d = (x * x).sum(1)[:, None] - 2 * x @ c.T + (c * c).sum(1)[None]
    return d.argmin(1).astype(np.int32), d.min(1)


def _fit_fault(kind):
    from repro.core import AAKMeans
    orig = AAKMeans.fit

    def broken(self, x):
        if kind == "state_unchanged":       # the iterate never moves
            rows = np.random.default_rng(self.seed).choice(
                x.shape[0], self.n_clusters, replace=False)
            c0 = np.asarray(x)[rows]
            labels, mind = _assign(x, c0)
            self.centroids_, self.labels_ = jnp.asarray(c0), labels
            self.energy_, self.n_iter_, self.n_accepted_ = \
                float(mind.sum()), 1, 0
            return self
        if kind == "half_batch":            # the fit sees half the rows
            h = x.shape[0] // 2
            orig(self, x[:h])
            self.labels_ = np.concatenate(
                [np.asarray(self.labels_), np.zeros(x.shape[0] - h,
                                                    np.int32)])
            return self
        orig(self, x)                       # an answer altered
        labels = np.asarray(self.labels_).copy()
        labels[0] = (labels[0] + 1) % self.n_clusters
        self.labels_ = labels
        return self
    return AAKMeans, "fit", broken


def _alter(labels, kind):
    """Half the labels lost, or the first moved to the next centroid (one
    past the last reads as a label no centroid has)."""
    out = np.asarray(labels).copy()
    if kind == "half_batch":
        out[out.shape[0] // 2:] = 0
    else:
        out[0] += 1
    return out


def _serve_fault(kind):
    from concurrent.futures import Future

    from repro.serving import KMeansServer
    orig = KMeansServer.submit

    def broken(self, rows, op="labels"):
        inner, outer = orig(self, rows, op), Future()
        inner.add_done_callback(
            lambda f: outer.set_result(_alter(f.result(), kind)))
        return outer
    return KMeansServer, "submit", broken


def _assign_fault(kind):
    from repro.core import AAKMeans
    orig = AAKMeans.predict

    def broken(self, x, *a, **kw):
        return _alter(orig(self, x, *a, **kw), kind)
    return AAKMeans, "predict", broken


FAULTS = [("table1-kddcup99.fit", _fit_fault, "state_unchanged"),
          ("table1-kddcup99.fit", _fit_fault, "half_batch"),
          ("table1-kddcup99.fit", _fit_fault, "answer_altered"),
          ("ivf4096-sift128.serve", _serve_fault, "half_batch"),
          ("ivf4096-sift128.serve", _serve_fault, "answer_altered"),
          ("ivf4096-sift128.assign", _assign_fault, "half_batch"),
          ("ivf4096-sift128.assign", _assign_fault, "answer_altered")]


@pytest.mark.parametrize("workload,plant,kind", FAULTS,
                         ids=[f"{w}-{k}" for w, _, k in FAULTS])
def test_a_broken_timed_path_is_not_correct(rehearse, monkeypatch, workload,
                                            plant, kind):
    owner, attr, broken = plant(kind)
    monkeypatch.setattr(owner, attr, broken)
    rc, line, err = rehearse(workload)
    assert rc == 3, err
    assert line["correct"] is False, line["checks"]
