"""The reduction from trace events to busy time, kernel time and idle
gaps: by hand on synthetic events, and on a trace recorded on the chip."""
import gzip
import json

import pytest

import _paths
import counts
import harness
import oracle
import tracing

DATA = _paths.BENCH / "tests" / "data"


def _events():
    ms = 1e6  # ns
    return {
        "spans": [["bench.window", 0.0, 100 * ms],
                  ["bench.step", 0.0, 60 * ms],
                  ["bench.wait", 60 * ms, 40 * ms]],
        "ops": {"/device:TPU:0": [
            ["refine_a", 10 * ms, 10 * ms, True],
            ["fusion.1", 15 * ms, 10 * ms, False],  # overlaps the kernel
            ["copy.2", 40 * ms, 5 * ms, False],
            ["fusion.1", 95 * ms, 10 * ms, False],  # runs past the window
            ["while.3", 38 * ms, 8 * ms, False],  # holds copy.2: a loop
        ]},
    }


def test_reduce_by_hand():
    red = tracing.reduce(_events())
    assert red["window_s"] == pytest.approx(0.1)
    # busy: [10, 25] + [40, 45] + [95, 100] ms; the loop holding copy.2
    # counts only through its body
    assert red["busy_s"] == pytest.approx(0.025)
    assert red["kernel_s"] == pytest.approx(0.010)
    assert red["other_s"] == pytest.approx(0.020)
    assert red["device_ops"][0] == ["fusion.1", pytest.approx(0.015)]
    gaps = {(n, round(s, 6)) for n, s in red["idle_gaps"]}
    # [0,10] and [25,40] in the step; [45,95] named by its middle, waiting
    assert gaps == {("bench.step", 0.01), ("bench.step", 0.015),
                    ("bench.wait", 0.05)}


def test_leaves_drop_control_flow_only():
    ev = [["while.7", 0.0, 10.0, False], ["fusion.1", 1.0, 2.0, False],
          ["call.2", 4.0, 2.0, False], ["refine_x.3", 4.0, 1.0, True],
          ["conditional.4", 12.0, 5.0, False], ["copy.5", 14.0, 5.0, False]]
    assert [e[0] for e in tracing.leaves(ev)] == ["fusion.1", "refine_x.3",
                                                  "copy.5"]


def test_reduce_needs_one_window():
    ev = _events()
    ev["spans"] = ev["spans"][1:]
    with pytest.raises(RuntimeError):
        tracing.reduce(ev)


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    """A 1.5-second log1d.serve window traced on a TPU v5e (one chip)."""
    path = tmp_path_factory.mktemp("trace") / "t.xplane.pb"
    path.write_bytes(gzip.decompress(
        (DATA / "log1d_serve.xplane.pb.gz").read_bytes()))
    return tracing.extract(str(path))


def test_recorded_trace_extracts_ops_kernels_and_spans(recorded):
    assert list(recorded["ops"]) == ["/device:TPU:0"]
    ops = recorded["ops"]["/device:TPU:0"]
    assert len(ops) == 1902
    kernels = [o for o in ops if o[3]]
    assert len(kernels) == 21
    assert {o[0].rsplit(".", 1)[0] for o in kernels} == {
        "refine_pyramid", "refine_charted_fwd"}
    assert not any(o[0].startswith("refine_") for o in ops if not o[3])
    names = sorted({s[0] for s in recorded["spans"]})
    assert names == ["bench.step", "bench.wait", "bench.window"]


def test_recorded_trace_reduces(recorded):
    red = tracing.reduce(recorded)
    assert red["chips"] == 1
    assert red["window_s"] == pytest.approx(1.230729866)
    assert red["busy_s"] == pytest.approx(0.690796681)
    assert red["kernel_s"] == pytest.approx(0.002022072)
    assert red["kernel_s"] + red["other_s"] >= red["busy_s"]
    assert red["device_ops"][0] == ["fusion.35", pytest.approx(0.146109581)]
    assert red["idle_gaps"][0] == ["bench.wait", pytest.approx(0.507612308)]
    assert len(red["device_ops"]) == len(red["idle_gaps"]) == tracing.TOP
    json.dumps(red)  # the result line carries it as it is


def test_recorded_trace_per_layer_readings(recorded):
    """The log1d.serve readers on the recorded window: three slabs of
    eight rows, one refine_pyramid launch each."""
    cell = harness.load_cell(_paths.ROOT, "log1d.serve")
    geom = oracle.geometry(cell.config)
    peaks = harness.peaks()["TPU v5 lite"]
    slabs = sum(o[0].startswith("refine_pyramid")
                for o in recorded["ops"]["/device:TPU:0"])
    assert slabs == 3
    red = tracing.reduce(recorded)
    reading = {"trace": red, "peaks": peaks,
               "counters": {"rows": 20, "slabs": slabs, "capacity": 8},
               "work": {"refine": counts.refine_work(geom, 8),
                        "step": counts.served_slab_work(geom, 8)}}
    got = {k: v["value"] for k, v in harness.per_layer(cell, reading).items()}
    least = counts.least_seconds(counts.refine_work(geom, 8), peaks)
    assert got["refine_roofline.tail"] == pytest.approx(
        100 * 3 * least / red["kernel_s"])
    assert 0 < got["refine_roofline.tail"] < 100
    assert 0 < got["step_mfu.tail"] < got["refine_roofline.tail"]
    assert got["kernel_ms.tail"] == pytest.approx(1e3 * red["kernel_s"] / 3)
    assert got["xla_ms.tail"] == pytest.approx(1e3 * red["other_s"] / 3)
    assert got["slab_fill.tail"] == pytest.approx(100 * 20 / 24)
    assert got["idle_share.tail"] == pytest.approx(
        100 * (1 - red["busy_s"] / red["window_s"]))
    json.dumps(got)
