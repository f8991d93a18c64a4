"""Every configuration file builds the geometry it names, in the bench
reference and in the program alike, at the sizes the benchmark states."""
import json

import numpy as np
import pytest

import _paths
import oracle
import program

BENCHMARK = json.loads((_paths.ROOT / "BENCHMARK.json").read_text())
CONFIGS = {c["name"]: c for c in BENCHMARK["configs"]}
STATED = {  # final points, excitation values
    "dust-pod-1chip": (256 * 2048 * 256, 153_391_104),
    "log1d-l11": (2_097_152, None),
}


def _cfg(name):
    return json.loads((_paths.ROOT / CONFIGS[name]["file"]).read_text())


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_config_builds_its_geometry(name):
    cfg = _cfg(name)
    assert cfg["name"] == name
    assert sorted(cfg["reduced"]) == sorted(CONFIGS[name]["reduced"])
    geom = oracle.geometry(cfg)
    chart = program.chart(cfg)
    for lvl in range(chart.n_levels + 1):
        assert geom.shape(lvl) == chart.shape(lvl)
    for lvl in range(chart.n_levels):
        assert geom.families(lvl) == tuple(
            chart.family_count(lvl, a) for a in range(chart.ndim))
        for a in range(chart.ndim):
            fams = [0, chart.family_count(lvl, a) - 1]
            np.testing.assert_array_equal(
                geom.coarse_windows(lvl, a, fams),
                chart.axis_coarse_windows(lvl, a, fams))
            np.testing.assert_array_equal(
                geom.fine_windows(lvl, a, fams),
                chart.axis_fine_windows(lvl, a, fams))
    assert geom.invariant == tuple(chart.invariant)
    size, xi = STATED[name]
    assert geom.size == chart.size == size
    if xi is not None:
        assert sum(int(np.prod(s)) for s in geom.xi_shapes()) == xi


def test_reference_chart_maps_match_the_program():
    for name in sorted(CONFIGS):
        cfg = _cfg(name)
        geom, chart = oracle.geometry(cfg), program.chart(cfg)
        pts = np.stack([geom.axis_coords(1, a)[:5]
                        for a in range(geom.ndim)], -1)
        np.testing.assert_allclose(geom.map_to_d(pts), chart.map_to_D(pts),
                                   rtol=0, atol=0)
