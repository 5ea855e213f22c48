"""The chunked inference path (`_chunked_rows_apply`) keeps one chunk
ahead: chunk i+1 is put and dispatched before chunk i's answer is
fetched.  Its answers stay those of a chunk-at-a-time loop, bit for bit,
and never more than two chunks are unfetched."""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import api
from repro.core.api import AAKMeans, rows_chunks_ahead
from repro.data.synthetic import make_blobs

STEP = 100


@pytest.fixture(scope="module")
def model():
    x = make_blobs(900, 5, 8, seed=3, spread=2.0)
    m = AAKMeans(n_clusters=8, seed=0).fit(x)
    m.build_serving_index(n_candidates=4)   # C < K: the closure scan
    return m, np.asarray(x)


def _one_chunk_at_a_time(model, x, kind):
    """Put, run and fetch each padded chunk before the next is put, with
    the model's own jitted runner for ``kind``."""
    run = model._local_runners[(kind, model.backend)]
    extras = api._closure_extras(model) if "closure" in kind else ()
    c = jnp.asarray(model.centroids_)
    parts = []
    for i in range(0, x.shape[0], STEP):
        rows = x[i:i + STEP]
        m = rows.shape[0]
        xc = jnp.asarray(np.concatenate(
            [rows, np.repeat(rows[-1:], STEP - m, axis=0)]))
        parts.append(np.asarray(run(xc, c, *extras))[:m])
    return np.concatenate(parts)


@pytest.mark.parametrize("n", [37, 300, 250],
                         ids=["below-one-chunk", "exact-multiple",
                              "padded-tail"])
@pytest.mark.parametrize("kind", ["predict", "transform", "predict_closure",
                                  "transform_closure"])
def test_pipelined_answers_match_one_chunk_at_a_time(model, kind, n):
    m, x = model
    rows = x[:n]
    method = m.predict if kind.startswith("predict") else m.transform
    got = method(rows, chunk_size=STEP, approx="closure" in kind)
    want = _one_chunk_at_a_time(m, rows, kind)
    assert got.shape == want.shape and got.dtype == want.dtype
    np.testing.assert_array_equal(got, want)


class _Rows:
    """Host rows that log each chunk the path reads (its put)."""

    def __init__(self, x, log):
        self.x, self.log, self.shape = x, log, x.shape

    def __getitem__(self, rows):
        self.log.append(("put", rows.start // STEP))
        return self.x[rows]


class _Answer:
    """A chunk's answer that logs when the host fetches it."""

    def __init__(self, chunk, value, log):
        self.chunk, self.value, self.log = chunk, value, log

    def __array__(self, dtype=None, copy=None):
        self.log.append(("fetch", self.chunk))
        return np.asarray(self.value, dtype)


@pytest.mark.parametrize("n", [37, 300, 250, 801])
def test_chunk_i_plus_one_is_put_and_dispatched_before_chunk_i_is_fetched(
        model, n):
    m, x = model
    log, answers = [], []

    def run(xc, c):
        assert xc.shape == (STEP, x.shape[1])     # one shape, tail padded
        log.append(("run", len(answers)))
        answers.append(np.asarray(xc[:, 0] > 0, np.int32))
        return _Answer(len(answers) - 1, answers[-1], log)

    runners = m.__dict__.setdefault("_local_runners", {})
    runners[("recorded", m.backend)] = run
    before = rows_chunks_ahead()
    out = api._chunked_rows_apply(m, _Rows(x[:n], log), "recorded",
                                  None, np.int32, chunk_size=STEP)
    chunks = -(-n // STEP)
    assert rows_chunks_ahead() - before == chunks - 1
    at = {e: i for i, e in enumerate(log)}
    assert len(log) == len(at)                   # each event once
    assert sorted(at) == sorted((e, k) for e in ("put", "run", "fetch")
                                for k in range(chunks))
    for k in range(chunks):
        assert at[("put", k)] < at[("run", k)] < at[("fetch", k)]
        if k + 1 < chunks:
            assert at[("run", k + 1)] < at[("fetch", k)]
        if k > 0:
            assert at[("fetch", k - 1)] < at[("fetch", k)]
    unfetched = np.cumsum([{"put": 1, "fetch": -1}.get(e, 0)
                           for e, _ in log])
    assert unfetched.max() == min(chunks, 2) and unfetched[-1] == 0
    np.testing.assert_array_equal(out, np.concatenate(answers)[:n])
    np.testing.assert_array_equal(out, x[:n, 0] > 0)
