"""The open-loop load generator: requests are timed from when they were
due, so a stall of the server shows in the tail even when the generator
itself stays on time."""

import time

import numpy as np


def test_schedule_is_one_multiset_in_another_order(bench_path):
    from lib import gen  # noqa: F401  (bench on the path)
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "serve_traffic", bench_path / "traffic" / "serve.py")
    serve = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(serve)
    params = {"rate_per_s": 400, "single_share": 0.8, "batch_rows": [16, 512],
              "traffic_seed": 11}
    due_a, sizes_a = serve.schedule(params, 30.0, seed=1)
    due_b, sizes_b = serve.schedule(params, 30.0, seed=2 ** 33 + 5)
    assert np.all(np.diff(due_a) >= 0) and due_a[-1] < 30.0
    assert abs(len(due_a) - len(due_b)) < 0.02 * len(due_a)
    n = min(len(sizes_a), len(sizes_b))
    assert not np.array_equal(sizes_a[:n], sizes_b[:n])
    assert sizes_a.min() >= 1 and sizes_a.max() <= 512
    assert abs(np.mean(sizes_a == 1) - 0.8) < 0.03


def test_a_stalled_server_shows_in_the_p95(drive, monkeypatch):
    from repro.serving import server
    orig = server.ServingModel.labels
    calls = {"n": 0}

    def stalling(self, xb):
        calls["n"] += 1
        if calls["n"] == 40:       # one stall of 0.4 s, once under load
            time.sleep(0.4)
        return orig(self, xb)

    monkeypatch.setattr(server.ServingModel, "labels", stalling)
    run, serve, e2e = drive("ivf4096-sift128.serve", seconds=3.0)
    lat = serve.latencies_ms(run)
    lag = (run.sent - run.due) * 1e3
    assert calls["n"] > 40
    # the generator kept to its schedule while the server stood still
    assert np.percentile(lag, 95) < 50
    # requests due during the stall waited for it: the tail shows it,
    # the median does not
    assert e2e["metrics"]["serve_p95_ms"][0] > 100
    assert np.median(lat) < 50


def test_single_row_mix_sends_one_row_per_request(bench_path):
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "serve_traffic", bench_path / "traffic" / "serve.py")
    serve = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(serve)
    params = {"rate_per_s": 5000, "single_share": 1.0, "traffic_seed": 7}
    due, sizes = serve.schedule(params, 10.0, seed=2 ** 40 + 3)
    assert np.all(sizes == 1)
    assert abs(len(due) - 50000) < 0.02 * 50000
