"""repro.runtime: prefetching chunk pipeline, background checkpoint
writer + manifest/retention lifecycle, metrics sinks — and their wiring
through the segmented drivers (DESIGN.md §Runtime)."""

import json
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import latest_snapshot, resume_point
from repro.core import serialize
from repro.core.init_schemes import kmeanspp_init
from repro.core.kmeans import KMeansConfig, aa_kmeans
from repro.data.streaming import chunk_dataset, stream_chunks
from repro.data.synthetic import make_blobs
from repro.runtime.metrics import (CollectMetrics, JsonlMetrics, NullMetrics,
                                   StdoutMetrics, TeeMetrics, as_metrics)
from repro.runtime.prefetch import (IngestMeter, prefetch_to_device,
                                    tree_nbytes)
from repro.runtime.writer import (CheckpointWriter, cleanup_orphans,
                                  read_manifest, snapshot_name,
                                  write_snapshot)


def _problem(n=400, d=4, k=5, max_iter=30, seed=0):
    x = jnp.asarray(make_blobs(n, d, k, seed=seed, spread=1.0))
    c0 = kmeanspp_init(jax.random.PRNGKey(seed), x, k)
    return x, c0, KMeansConfig(k=k, max_iter=max_iter)


# ---------------------------------------------------------------------------
# prefetch
# ---------------------------------------------------------------------------

def test_prefetch_preserves_order_and_values(rng):
    chunks = [rng.standard_normal((8, 3)).astype(np.float32)
              for _ in range(7)]
    for size in (1, 2, 4, 16):   # 16 > len: whole stream in flight
        out = list(prefetch_to_device(iter(chunks), size=size))
        assert len(out) == len(chunks)
        for a, b in zip(chunks, out):
            assert isinstance(b, jax.Array)
            np.testing.assert_array_equal(a, np.asarray(b))


def test_prefetch_rejects_size_zero():
    with pytest.raises(ValueError, match="size"):
        list(prefetch_to_device(iter([np.zeros(2)]), size=0))


def test_prefetch_meter_counts_bytes(rng):
    chunks = [rng.standard_normal((16, 4)).astype(np.float32)
              for _ in range(5)]
    meter = IngestMeter()
    list(prefetch_to_device(iter(chunks), size=2, meter=meter))
    assert meter.chunks == 5
    assert meter.bytes == 5 * 16 * 4 * 4 == sum(map(tree_nbytes, chunks))
    assert meter.gbps > 0
    s = meter.scalars()
    assert s["ingest_bytes"] == meter.bytes and s["ingest_chunks"] == 5


def test_stream_chunks_host_array_matches_host_chunk_stream(rng):
    from repro.data.streaming import host_chunk_stream
    x = rng.standard_normal((100, 3)).astype(np.float32)
    ref = list(host_chunk_stream(x, 32, epochs=2, seed=3))
    out = list(stream_chunks(x, 32, epochs=2, seed=3))
    assert len(out) == len(ref)
    for a, b in zip(ref, out):
        np.testing.assert_array_equal(a, np.asarray(b))


def test_stream_chunks_device_chunks_passthrough(rng):
    x = rng.standard_normal((96, 3)).astype(np.float32)
    dc = chunk_dataset(x, 32)
    out = list(stream_chunks(dc))
    assert len(out) == dc.chunks.shape[0]
    for i, ch in enumerate(out):
        np.testing.assert_array_equal(np.asarray(dc.chunks[i]),
                                      np.asarray(ch))
    with pytest.raises(ValueError, match="storage order"):
        stream_chunks(dc, chunk_size=32)


def test_stream_chunks_requires_chunk_size_for_arrays(rng):
    with pytest.raises(ValueError, match="chunk_size"):
        stream_chunks(rng.standard_normal((10, 2)))


def test_stream_chunks_device_chunks_rejects_all_stream_params(rng):
    """Regression (ISSUE 8): ``seed=``/``drop_remainder=`` used to slip
    past the DeviceChunks guard and be silently ignored — a caller's
    "my shuffle seed works" was a no-op.  The documented contract (all
    stream params at defaults) is now enforced for every parameter."""
    dc = chunk_dataset(rng.standard_normal((96, 3)).astype(np.float32), 32)
    for bad in ({"seed": 7}, {"drop_remainder": True}, {"epochs": 2},
                {"start_chunk": 1}, {"chunk_size": 32}):
        with pytest.raises(ValueError, match="storage order"):
            stream_chunks(dc, **bad)
    assert len(list(stream_chunks(dc))) == dc.chunks.shape[0]


# ---------------------------------------------------------------------------
# metrics sinks
# ---------------------------------------------------------------------------

def test_as_metrics_normalisation():
    assert isinstance(as_metrics(None), NullMetrics)
    assert isinstance(as_metrics("null"), NullMetrics)
    assert isinstance(as_metrics("stdout"), StdoutMetrics)
    sink = CollectMetrics()
    assert as_metrics(sink) is sink
    with pytest.raises(ValueError, match="unknown metrics sink"):
        as_metrics("wandb")
    with pytest.raises(TypeError, match="log_scalars"):
        as_metrics(42)


def test_collect_and_tee_and_jsonl(tmp_path):
    c1, c2 = CollectMetrics(), CollectMetrics()
    jl = JsonlMetrics(tmp_path / "m.jsonl")
    tee = TeeMetrics(c1, c2, jl)
    tee.log_scalars(1, {"e": jnp.asarray(2.5), "n": 3})
    tee.log_scalars(2, {"e": 1.25})
    tee.close()
    assert c1.records == c2.records == [(1, {"e": 2.5, "n": 3.0}),
                                        (2, {"e": 1.25})]
    lines = [json.loads(ln) for ln in
             (tmp_path / "m.jsonl").read_text().splitlines()]
    assert lines == [{"step": 1, "e": 2.5, "n": 3.0},
                     {"step": 2, "e": 1.25}]


def test_early_stop_hook_trips_on_stall():
    from repro.runtime.metrics import EarlyStopHook, should_stop
    hook = EarlyStopHook(rel_tol=1e-3, patience=2, min_records=1)
    hook.log_scalars(0, {"energy": 100.0})
    hook.log_scalars(1, {"energy": 50.0})     # big improvement: no stall
    assert not hook.should_stop
    hook.log_scalars(2, {"energy": 49.999})   # stall 1
    assert not hook.should_stop
    hook.log_scalars(3, {"energy": 49.998})   # stall 2 -> trip
    assert hook.should_stop and hook.stopped_at == 3
    assert should_stop(hook)
    # monotone: later improvement does not un-trip
    hook.log_scalars(4, {"energy": 1.0})
    assert hook.should_stop
    # records kept for inspecting the decision (CollectMetrics base)
    assert len(hook.records) == 5


def test_early_stop_hook_metric_fallbacks_and_nonfinite():
    from repro.runtime.metrics import EarlyStopHook, should_stop
    hook = EarlyStopHook(rel_tol=1e-3, patience=1, min_records=1)
    hook.log_scalars(0, {"segment_s": 0.5})            # no watched metric
    hook.log_scalars(1, {"e_val": float("nan")})       # ignored
    hook.log_scalars(2, {"energy_best": 10.0})         # batched spelling
    assert not hook.should_stop
    hook.log_scalars(3, {"energy_best": 10.0})
    assert hook.should_stop
    # plain sinks never stop a driver; a Tee fan-out is searched
    assert not should_stop(CollectMetrics())
    assert should_stop(TeeMetrics(CollectMetrics(), hook))


def test_early_stop_hook_halts_segmented_driver():
    """Wired as the metrics= sink of the segmented single-solve driver:
    an impossible improvement bar stops the host loop before max_iter."""
    from repro.runtime.metrics import EarlyStopHook
    x, c0, cfg = _problem(max_iter=200)
    hook = EarlyStopHook(rel_tol=10.0, patience=1, min_records=1)
    res = aa_kmeans(x, c0, cfg, checkpoint_every=1, metrics=hook)
    assert hook.should_stop
    assert int(res.n_iter) < 200


def test_jsonl_is_thread_safe(tmp_path):
    jl = JsonlMetrics(tmp_path / "m.jsonl")

    def pump(tid):
        for i in range(50):
            jl.log_scalars(i, {"tid": tid})
    threads = [threading.Thread(target=pump, args=(t,)) for t in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    jl.close()
    lines = (tmp_path / "m.jsonl").read_text().splitlines()
    assert len(lines) == 200
    for ln in lines:
        json.loads(ln)     # every line intact (no interleaving)


# ---------------------------------------------------------------------------
# writer: manifest, retention, orphan cleanup
# ---------------------------------------------------------------------------

def _fake_state(step):
    return {"c": jnp.full((3, 2), float(step)), "t": jnp.asarray(step)}


def test_write_snapshot_builds_manifest(tmp_path):
    for t in (2, 4, 6):
        write_snapshot(tmp_path, _fake_state(t), kind="unit", step=t,
                       extra={"t": t})
    m = read_manifest(tmp_path)
    assert m is not None and m["kind"] == "unit"
    assert m["latest"] == snapshot_name(6)
    assert [e["step"] for e in m["snapshots"]] == [2, 4, 6]
    assert (tmp_path / m["latest"]).exists()


def test_retention_window_and_boundary_keep(tmp_path):
    # keep_last_n=2 with keep_every_m=10: a sliding window of 2 plus
    # every 10th boundary kept forever
    for t in range(5, 55, 5):
        write_snapshot(tmp_path, _fake_state(t), kind="unit", step=t,
                       keep_last_n=2, keep_every_m=10)
    kept = sorted(p.name for p in tmp_path.glob("it_*.npz"))
    want = sorted({snapshot_name(t) for t in (10, 20, 30, 40, 50, 45)})
    assert kept == want
    m = read_manifest(tmp_path)
    assert sorted(e["file"] for e in m["snapshots"]) == want
    # the manifest never references a deleted file
    for e in m["snapshots"]:
        assert (tmp_path / e["file"]).exists()


def test_retention_always_keeps_newest(tmp_path):
    # keep_every_m alone, newest step not on the boundary: still kept
    for t in (3, 6, 10, 13):
        write_snapshot(tmp_path, _fake_state(t), kind="unit", step=t,
                       keep_every_m=10)
    kept = {p.name for p in tmp_path.glob("it_*.npz")}
    assert kept == {snapshot_name(10), snapshot_name(13)}


def test_cleanup_orphans(tmp_path):
    (tmp_path / "it_00000001.npz.tmp").write_bytes(b"partial")
    (tmp_path / "manifest.json.tmp").write_bytes(b"{")
    keep = tmp_path / "it_00000002.npz"
    keep.write_bytes(b"complete")
    removed = cleanup_orphans(tmp_path)
    assert len(removed) == 2 and keep.exists()
    assert not list(tmp_path.glob("*.tmp"))


def test_latest_snapshot_uses_manifest_with_scan_fallback(tmp_path):
    for t in (1, 2):
        write_snapshot(tmp_path, _fake_state(t), kind="unit", step=t)
    assert latest_snapshot(tmp_path).name == snapshot_name(2)
    # corrupt manifest -> scan fallback still finds the newest artifact
    (tmp_path / "manifest.json").write_text("not json")
    assert latest_snapshot(tmp_path).name == snapshot_name(2)
    # manifest pointing at an externally deleted file -> fallback too
    write_snapshot(tmp_path, _fake_state(3), kind="unit", step=3)
    (tmp_path / snapshot_name(3)).unlink()
    assert latest_snapshot(tmp_path).name == snapshot_name(2)


def test_latest_snapshot_fallback_orders_by_step_not_name(tmp_path):
    """Regression (ISSUE 8): the manifest-less fallback sorted snapshot
    file NAMES, so lexicographic it_9.npz beat it_10.npz and a ``.tmp``
    filter aimed at ``*.npz.tmp`` never matched its own glob.  The
    fallback now parses the integer step and ignores orphans/garbage."""
    from repro.checkpoint import latest_snapshot as latest
    for step in (9, 10, 2):
        (tmp_path / f"it_{step}.npz").write_bytes(b"snap")
    (tmp_path / "it_11.npz.tmp").write_bytes(b"orphan")    # interrupted
    (tmp_path / "it_xx.npz").write_bytes(b"garbage")       # unparseable
    assert latest(tmp_path).name == "it_10.npz"
    # directory with ONLY orphans/garbage: no snapshot, not a crash
    empty = tmp_path / "empty"
    empty.mkdir()
    (empty / "it_1.npz.tmp").write_bytes(b"orphan")
    assert latest(empty) is None


# ---------------------------------------------------------------------------
# writer: async lifecycle
# ---------------------------------------------------------------------------

def test_writer_async_matches_sync_artifacts(tmp_path):
    sync_dir, async_dir = tmp_path / "sync", tmp_path / "async"
    states = {t: jax.device_get(_fake_state(t)) for t in (1, 2, 3)}
    for t, st in states.items():
        write_snapshot(sync_dir, st, kind="unit", step=t, extra={"t": t})
    with CheckpointWriter(async_dir, kind="unit") as w:
        for t, st in states.items():
            w.submit(st, t, {"t": t})
    assert w.n_written == 3
    for t in states:
        a, _ = serialize.load(sync_dir / snapshot_name(t))
        b, _ = serialize.load(async_dir / snapshot_name(t))
        assert a["t"] == b["t"] == t
        _, pa = serialize.load(sync_dir / snapshot_name(t))
        _, pb = serialize.load(async_dir / snapshot_name(t))
        for k in pa:
            np.testing.assert_array_equal(pa[k], pb[k])
    ma, mb = read_manifest(sync_dir), read_manifest(async_dir)
    assert ma["latest"] == mb["latest"]
    assert [e["step"] for e in ma["snapshots"]] == \
        [e["step"] for e in mb["snapshots"]]


def test_writer_propagates_write_errors(tmp_path, monkeypatch):
    import repro.runtime.writer as W
    w = CheckpointWriter(tmp_path, kind="unit")
    monkeypatch.setattr(W, "write_snapshot",
                        lambda *a, **kw: (_ for _ in ()).throw(
                            OSError("disk full")))
    w.submit(jax.device_get(_fake_state(1)), 1)
    with pytest.raises(OSError, match="disk full"):
        w.drain()
    # close() after a surfaced error is clean (error already consumed)
    w.close()


def test_writer_emits_write_latency_metric(tmp_path):
    mx = CollectMetrics()
    with CheckpointWriter(tmp_path, kind="unit", metrics=mx) as w:
        w.submit(jax.device_get(_fake_state(7)), 7)
    assert any(step == 7 and "checkpoint_write_s" in rec
               for step, rec in mx.records)


def test_writer_refuses_submit_after_close(tmp_path):
    w = CheckpointWriter(tmp_path, kind="unit")
    w.close()
    with pytest.raises(RuntimeError, match="closed"):
        w.submit(jax.device_get(_fake_state(1)), 1)
    w.close()      # idempotent


# ---------------------------------------------------------------------------
# drivers: async checkpointing end-to-end
# ---------------------------------------------------------------------------

def test_driver_async_checkpoints_match_sync(tmp_path):
    x, c0, cfg = _problem()
    ref = aa_kmeans(x, c0, cfg)
    sync_dir, async_dir = tmp_path / "sync", tmp_path / "async"
    aa_kmeans(x, c0, cfg, checkpoint_every=7, checkpoint_dir=sync_dir,
              sync_writes=True)
    aa_kmeans(x, c0, cfg, checkpoint_every=7, checkpoint_dir=async_dir)
    names_s = sorted(p.name for p in sync_dir.glob("it_*.npz"))
    names_a = sorted(p.name for p in async_dir.glob("it_*.npz"))
    assert names_s == names_a and names_s
    for name in names_s:     # bit-identical artifacts either way
        _, pa = serialize.load(sync_dir / name)
        _, pb = serialize.load(async_dir / name)
        assert pa.keys() == pb.keys()
        for k in pa:
            np.testing.assert_array_equal(pa[k], pb[k])
    # resume from the async run's manifest-reported latest: bit-identical
    res = aa_kmeans(x, c0, cfg, resume_from=latest_snapshot(async_dir))
    assert float(res.energy) == float(ref.energy)
    np.testing.assert_array_equal(np.asarray(res.centroids),
                                  np.asarray(ref.centroids))


def test_driver_killed_midrun_resumes_from_manifest(tmp_path):
    """A run that dies mid-solve (exception at a boundary) still drains
    the writer on the way out, so the manifest names a durable snapshot
    and resuming from it reproduces the uninterrupted result bit for
    bit."""
    x, c0, cfg = _problem(max_iter=40)
    ref = aa_kmeans(x, c0, cfg)

    class Die(RuntimeError):
        pass

    boundaries = []

    def killer(state, t):
        boundaries.append(t)
        if len(boundaries) >= 2:       # die at the second boundary
            raise Die("simulated preemption")

    with pytest.raises(Die):
        aa_kmeans(x, c0, cfg, checkpoint_every=3, checkpoint_dir=tmp_path,
                  checkpoint_cb=killer)
    p, meta = resume_point(tmp_path)       # reads manifest.json
    assert p is not None and meta["t"] == boundaries[-1]
    assert read_manifest(tmp_path)["latest"] == p.name
    res = aa_kmeans(x, c0, cfg, resume_from=p)
    assert float(res.energy) == float(ref.energy)
    np.testing.assert_array_equal(np.asarray(res.centroids),
                                  np.asarray(ref.centroids))


def test_driver_failed_write_fails_run(tmp_path, monkeypatch):
    import repro.runtime.writer as W
    x, c0, cfg = _problem()
    monkeypatch.setattr(W, "write_snapshot",
                        lambda *a, **kw: (_ for _ in ()).throw(
                            OSError("disk full")))
    with pytest.raises(OSError, match="disk full"):
        aa_kmeans(x, c0, cfg, checkpoint_every=5, checkpoint_dir=tmp_path)


def test_driver_retention_flows_through(tmp_path):
    x, c0, cfg = _problem(max_iter=40)
    aa_kmeans(x, c0, cfg, checkpoint_every=4, checkpoint_dir=tmp_path,
              keep_last_n=2)
    snaps = sorted(tmp_path.glob("it_*.npz"))
    assert len(snaps) == 2
    m = read_manifest(tmp_path)
    assert len(m["snapshots"]) == 2
    # resume from the retained window still reproduces the full solve
    res = aa_kmeans(x, c0, cfg, resume_from=snaps[-1])
    ref = aa_kmeans(x, c0, cfg)
    assert float(res.energy) == float(ref.energy)


def test_driver_metrics_emission(tmp_path):
    x, c0, cfg = _problem()
    mx = CollectMetrics()
    aa_kmeans(x, c0, cfg, checkpoint_every=7, checkpoint_dir=tmp_path,
              metrics=mx)
    seg_records = [(s, r) for s, r in mx.records if "energy" in r]
    assert seg_records
    for _, rec in seg_records:
        assert {"energy", "n_accepted", "segment_s"} <= set(rec)
    # the writer contributed its write-latency stream to the same sink
    assert any("checkpoint_write_s" in r for _, r in mx.records)
    # metrics alone (no checkpointing) also routes through the host loop
    mx2 = CollectMetrics()
    res = aa_kmeans(x, c0, cfg, metrics=mx2)
    ref = aa_kmeans(x, c0, cfg)
    assert mx2.records
    assert float(res.energy) == float(ref.energy)


# -- persistent compilation cache placement --------------------------------

@pytest.mark.parametrize("env_set", [True, False], ids=["env", "repo"])
def test_compile_cache_dir_placed_from_outside(env_set, tmp_path,
                                               monkeypatch):
    """A set JAX_COMPILATION_CACHE_DIR is the cache directory; unset, the
    cache lives at the fixed <repo>/.jax_cache."""
    from pathlib import Path
    from repro.runtime import compile_cache
    repo = Path(__file__).resolve().parents[1]
    if env_set:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        want = str(tmp_path)
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        want = str(repo / ".jax_cache")
    assert compile_cache.cache_dir() == want
    before = jax.config.jax_compilation_cache_dir
    try:
        assert compile_cache.enable_compile_cache() == want
        assert jax.config.jax_compilation_cache_dir == want
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
