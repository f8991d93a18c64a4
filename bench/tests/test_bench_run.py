"""The harness: no result without a TPU or without the program, and cells,
configurations, mixes and metrics found by name from files of their own."""
import json
import os
import shutil
import subprocess
import sys

import pytest

import _paths
import harness


def _run(root, workload="log1d.serve"):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", workload, "--seed",
         str(2 ** 31 + 7), "--seconds", "1", "--trace", "0"],
        cwd=root, env=env, capture_output=True, text=True, timeout=300)


def test_exits_nonzero_without_a_tpu():
    p = _run(_paths.ROOT)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_refuses_a_device_kind_missing_from_the_peaks_table(monkeypatch):
    class Dev:
        platform, device_kind = "tpu", "TPU v99"

    import jax

    monkeypatch.setattr(jax, "devices", lambda *a: [Dev()])
    with pytest.raises(harness.Refused, match="not in bench/peaks.json"):
        harness.require_chip(1)


def test_exits_nonzero_with_only_the_benchmark_files(tmp_path):
    shutil.copy(_paths.ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(_paths.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", ".trace"))
    p = _run(tmp_path)
    assert p.returncode != 0 and p.stdout.strip() == ""
    assert "no program" in p.stderr


def test_new_files_are_found_by_name(tmp_path):
    """A later cell adds a config, a mix, limits and a metric reader, and
    entries in BENCHMARK.json; nothing that exists changes."""
    shutil.copytree(_paths.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", ".trace"))
    bench = json.loads((_paths.ROOT / "BENCHMARK.json").read_text())
    cfg = json.loads((_paths.BENCH / "configs" / "log1d-l11.json")
                     .read_text())
    cfg.update(name="tod-l12", chart="regular_chart", rho=8.0,
               args={"shape0": 1024, "n_levels": 12, "n_csz": 3,
                     "n_fsz": 2, "delta0": 1.0, "boundary": "reflect"})
    (tmp_path / "bench/configs/tod-l12.json").write_text(json.dumps(cfg))
    mix = {"mode": "open", "rate": 8.0, "checked": 4,
           "mix": {"sample": {"share": 1.0, "n": [1, 2]}}}
    (tmp_path / "bench/traffic/burst_open.json").write_text(json.dumps(mix))
    (tmp_path / "bench/limits/tod.serve.json").write_text(
        json.dumps({"sample_gap": 1e-5, "moments_gap": 1e-5}))
    (tmp_path / "bench/metrics/rows.tod.py").write_text(
        "def read(r):\n    return r['counters']['rows']\n")
    bench["configs"].append({"name": "tod-l12", "source": "x",
                             "file": "bench/configs/tod-l12.json",
                             "reduced": ["n_levels"], "why": "x"})
    bench["workloads"].append({"name": "tod.serve", "config": "tod-l12",
                               "traffic": "burst_open", "chips": 1,
                               "why": "x"})
    next(m for m in bench["end_to_end"]
         if m["name"] == "request_p50_s")["workloads"].append("tod.serve")
    bench["per_layer"].append({"name": "rows.tod", "unit": "rows",
                               "better": "higher",
                               "source": "program_counter",
                               "layer": "serving", "moves": "request_p50_s",
                               "workloads": ["tod.serve"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))

    cell = harness.load_cell(tmp_path, "tod.serve")
    assert cell.config["rho"] == 8.0 and cell.traffic["rate"] == 8.0
    assert [m["name"] for m in cell.end_to_end] == ["request_p50_s",
                                                    "setup_s"]
    names = [m["name"] for m in cell.per_layer]
    assert names == ["rows.tod"]
    got = harness.per_layer(cell, {"counters": {"rows": 12}})
    assert got == {"rows.tod": {"value": 12.0, "unit": "rows"}}


def test_latency_quantiles_are_nearest_rank_with_a_missing_answer_infinite():
    import serve

    lat = [0.1 * i for i in range(20, 0, -1)]
    assert serve.quantile(lat, 0.5) == pytest.approx(1.0)
    assert serve.p90(lat) == pytest.approx(1.8)
    assert serve.p90(lat[:-3] + [float("inf")] * 3) == float("inf")
    read = harness.reader(_paths.ROOT, "latency_p90.tail")
    assert read({"latency": {"p90": 0.7}}) == 0.7
    assert read({"counters": {"rows": 1}}) is None  # a closed-loop cell


def test_a_metric_without_a_file_of_its_own_takes_its_stems_reader(
        tmp_path):
    metrics = tmp_path / "bench" / "metrics"
    metrics.mkdir(parents=True)
    (metrics / "rows.py").write_text("def read(r):\n    return 1.0\n")
    (metrics / "rows.b.py").write_text("def read(r):\n    return 2.0\n")
    assert harness.reader(tmp_path, "rows.a")({}) == 1.0
    assert harness.reader(tmp_path, "rows.b")({}) == 2.0
    with pytest.raises(harness.Refused, match="no reader"):
        harness.reader(tmp_path, "cols.a")


def test_every_declared_metric_has_a_reader_and_every_cell_its_files():
    bench = json.loads((_paths.ROOT / "BENCHMARK.json").read_text())
    for m in bench["per_layer"]:
        assert callable(harness.reader(_paths.ROOT, m["name"]))
    for w in bench["workloads"]:
        cell = harness.load_cell(_paths.ROOT, w["name"])
        assert {m["name"] for m in cell.end_to_end} >= {"setup_s"}
        assert len(cell.end_to_end) >= 2 and cell.per_layer
