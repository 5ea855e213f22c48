"""The harness finds configurations, cells, traffic modules and metric
readers by name, agrees with BENCHMARK.json, and refuses to print a
result anywhere but on a known TPU."""

import json
import shutil
import subprocess
import sys

from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
BENCH = ROOT / "bench"

SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
TRAFFIC_API = ("setup", "window", "end_to_end", "release", "check")


# every cell file, including one held out of BENCHMARK.json until it is
# measured on the chip
CELLS = sorted(p.stem for p in (BENCH / "workloads").glob("*.json"))
SPEC_CELLS = {w["name"]: w for w in SPEC["workloads"]}
READERS = sorted(p.stem for p in (BENCH / "metrics").glob("*.py"))
SPEC_METRICS = {m["name"]: m for m in SPEC["per_layer"]}


def test_every_benchmark_cell_and_metric_has_its_files():
    assert set(SPEC_CELLS) <= set(CELLS)
    assert set(SPEC_METRICS) <= set(READERS)


@pytest.mark.parametrize("name", CELLS)
def test_every_cell_is_found_by_name(harness, name):
    workload, config = harness.cell(name)
    traffic = harness.load_module("traffic", workload["traffic"])
    assert all(callable(getattr(traffic, f)) for f in TRAFFIC_API)
    assert config is not None and "setup_s" in workload["end_to_end"]
    for metric in workload["per_layer"]:
        harness.load_module("metrics", metric)
    if name not in SPEC_CELLS:
        return
    cell = SPEC_CELLS[name]
    assert workload["config"] == cell["config"]
    assert workload["traffic"] == cell["traffic"]
    assert workload["chips"] == cell["chips"]
    # the cell reports setup_s and the end-to-end metrics that name it
    e2e = {m["name"] for m in SPEC["end_to_end"]
           if name in m.get("workloads", [name])}
    assert set(workload["end_to_end"]) == e2e and "setup_s" in e2e
    # and every per-layer metric that lists it, each moving one of those
    layer = {m["name"] for m in SPEC["per_layer"] if name in m["workloads"]}
    assert set(workload["per_layer"]) == layer and layer
    for m in SPEC["per_layer"]:
        if m["name"] in layer:
            assert m["moves"] in e2e


@pytest.mark.parametrize("metric", READERS)
def test_every_per_layer_metric_has_a_reader(harness, metric):
    reader = harness.load_module("metrics", metric)
    assert callable(reader.read)
    if metric in SPEC_METRICS:
        assert reader.UNIT == SPEC_METRICS[metric]["unit"]
    else:
        assert reader.UNIT


@pytest.mark.parametrize("config", SPEC["configs"],
                         ids=[c["name"] for c in SPEC["configs"]])
def test_every_configuration_file_is_the_one_named(config):
    doc = json.loads((ROOT / config["file"]).read_text())
    assert doc["source"] == config["source"]
    assert doc["reduced"] == config["reduced"]
    assert (ROOT / config["file"]).parent == BENCH / "configs"


def test_unknown_names_are_refused(harness):
    with pytest.raises(harness.Refused):
        harness.cell("no-such-cell")
    with pytest.raises(harness.Refused):
        harness.load_module("metrics", "no_such_metric")


def test_no_tpu_prints_no_result(harness, capsys):
    rc = harness.main(["--workload", "table1-kddcup99.fit", "--seed", "1",
                       "--seconds", "1"])
    out = capsys.readouterr()
    assert rc == 2 and out.out == ""
    assert "no TPU" in out.err


class _FakeTpu:
    platform = "tpu"
    device_kind = "TPU v99 unknown"


def test_unknown_device_kind_prints_no_result(harness, capsys, monkeypatch):
    import jax
    monkeypatch.setattr(jax, "devices", lambda *a: [_FakeTpu()])
    rc = harness.main(["--workload", "table1-kddcup99.fit", "--seed", "1",
                       "--seconds", "1"])
    out = capsys.readouterr()
    assert rc == 2 and out.out == ""
    assert "peaks table" in out.err


def test_benchmark_files_alone_print_no_result(tmp_path):
    """A directory holding only BENCHMARK.json and the benchmark's paths
    (no program) exits non-zero with no result."""
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    for p in SPEC["paths"]:
        shutil.copytree(ROOT / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    r = subprocess.run([sys.executable, *SPEC["command"][1:], "--workload",
                        "table1-kddcup99.fit", "--seed", "1", "--seconds",
                        "1", "--trace", "0"], cwd=tmp_path,
                       capture_output=True, text=True, timeout=120)
    assert r.returncode != 0 and r.stdout == ""
