"""Plan-introspection smoke checks (run as their own CI step, fp32 and
bf16 separately), all at the real VMEM budget: the VMEM-resident pyramid
covers the flagship dust-map chart's first two levels and the whole
serving dust chart (zero inter-level field traffic); with the pyramid
disabled every level runs the single-launch megakernel, forward and
backward; and the ``dispatch.plan`` byte estimates agree with the roofline
traffic model at BOTH storage dtypes — with bf16 reporting >= 1.9x fewer
bytes per level than fp32.
"""
import numpy as np
import pytest

from repro.core.charts import galactic_dust_chart
from repro.core.refine import LevelGeom
from repro.kernels import dispatch
from repro.roofline import refine_level_traffic

# the examples/dust_map_3d.py chart: its first two levels fit the
# pyramid together (16.4 MiB), all three do not (126.5 MiB)
CHART = galactic_dust_chart((8, 16, 16), n_levels=3)
# the serving scenarios' dust chart: both levels fit the pyramid together
PYRAMID_CHART = galactic_dust_chart((6, 8, 8), n_levels=2)
DTYPES = ["float32", "bfloat16"]


def _plan(dtype, **kw):
    return dispatch.plan(CHART, platform="cpu", dtype=dtype, **kw)


def _pyr_plan(dtype, **kw):
    return dispatch.plan(PYRAMID_CHART, platform="cpu", dtype=dtype, **kw)


@pytest.mark.parametrize("dtype", DTYPES)
def test_dust_map_pyramid_covers_every_level(dtype):
    """The serving dust chart's levels fit VMEM together: the whole chart
    is ONE pyramid launch, at either dtype, and its backward runs the
    adjoint kernels. On the example chart the pyramid covers the first two
    levels and the megakernel runs the third."""
    entries = _pyr_plan(dtype)
    assert [e["route"] for e in entries] \
        == [dispatch.ROUTE_PYRAMID] * PYRAMID_CHART.n_levels
    assert all(e["dtype"] == dtype for e in entries)
    assert all(e["vjp"]["route"] == dispatch.ROUTE_PYRAMID + "-adjoint"
               for e in entries)
    assert [e["route"] for e in _plan(dtype)] \
        == [dispatch.ROUTE_PYRAMID] * 2 + [dispatch.ROUTE_ND_FUSED]


@pytest.mark.parametrize("dtype", DTYPES)
def test_pyramid_off_falls_back_to_megakernel(dtype):
    """With the pyramid disabled every level still runs the single-launch
    megakernel — forward AND backward, never the jnp reference."""
    for chart in (CHART, PYRAMID_CHART):
        for e in dispatch.plan(chart, platform="cpu", dtype=dtype,
                               pyramid=False):
            assert e["route"] == dispatch.ROUTE_ND_FUSED, e
            assert e["vjp"]["route"] == dispatch.ROUTE_ND_FUSED + "-adjoint", e
        # the route labels above pin the *structure*; the backend column is
        # the executor (the jnp oracle of that same structure on CPU)
        assert e["vjp"]["backend"] == e["backend"]


@pytest.mark.parametrize("dtype", DTYPES)
def test_pyramid_zero_interlevel_field_traffic(dtype):
    """ISSUE 4 acceptance: covered levels move no field bytes through HBM
    except the first's coarse read and the last's fine write."""
    entries = _pyr_plan(dtype)
    k = len(entries)
    assert [e["route"] for e in entries] == [dispatch.ROUTE_PYRAMID] * k
    for e in entries:
        geom = LevelGeom.for_level(PYRAMID_CHART, e["level"])
        br = refine_level_traffic(geom, "pyramid", dtype=dtype,
                                  first=e["level"] == 0,
                                  last=e["level"] == k - 1)
        assert e["hbm_bytes"]["pyramid"] == br["total"]
        assert e["hbm_bytes"]["selected"] == br["total"]
        if e["level"] > 0:
            assert br["field_read"] == 0, e
        if e["level"] < k - 1:
            assert br["fine_write"] == 0, e
        assert br["xi_read"] > 0 and br["dtype"] == dtype


def test_bf16_at_least_1p9x_fewer_bytes_per_level():
    """ISSUE 4 acceptance: >= 1.9x fewer modeled HBM bytes per large level
    in bf16 vs fp32 — on the selected route and on every candidate."""
    for pyramid in (True, False):
        p32 = _plan("float32", pyramid=pyramid)
        p16 = _plan("bfloat16", pyramid=pyramid)
        for e32, e16 in zip(p32, p16):
            assert set(e32["hbm_bytes"]) == set(e16["hbm_bytes"])
            for route, b32 in e32["hbm_bytes"].items():
                assert b32 >= 1.9 * e16["hbm_bytes"][route], (route, e32)


@pytest.mark.parametrize("dtype", DTYPES)
def test_plan_bytes_match_roofline_within_10pct(dtype):
    """plan() must report the roofline model's numbers at each dtype (and
    the model must be dominated by the minimal-traffic terms)."""
    itemsize = np.dtype(dtype).itemsize
    for e in _plan(dtype, pyramid=False):
        geom = LevelGeom.for_level(CHART, e["level"])
        for route in (dispatch.ROUTE_ND_FUSED, dispatch.ROUTE_AXES_ND,
                      dispatch.ROUTE_REFERENCE):
            model = refine_level_traffic(geom, route, dtype=dtype)["total"]
            got = e["hbm_bytes"][route]
            assert abs(got - model) <= 0.10 * model, (route, got, model)
        # sanity: the fused launch itself moves within 35% of the
        # irreducible field + ξ + output traffic (matrices are a rounding
        # error here); the phase-plane relayouts around it read and write
        # each of those arrays once more
        n_out = int(np.prod(geom.fine_shape))
        minimal = itemsize * (int(np.prod(geom.coarse_shape)) + 2 * n_out)
        br = refine_level_traffic(geom, dispatch.ROUTE_ND_FUSED, dtype=dtype)
        launch = br["total"] - br["relayout"]
        assert launch <= 1.35 * minimal, (launch, minimal)
        assert br["relayout"] == 2 * launch, br


def test_plan_quantifies_fused_and_pyramid_wins():
    """The traffic reductions that motivate the megakernel (>= 2x vs
    per-axis on every 3-D level, relayouts included on both; its launch
    alone >= 2x below the window-materializing reference) and the pyramid
    (a covered level drops the field term it does not own) are visible
    straight from plan()."""
    per_level = _plan("float32", pyramid=False)
    for e in per_level:
        hb = e["hbm_bytes"]
        assert hb[dispatch.ROUTE_ND_FUSED] * 2 <= hb[dispatch.ROUTE_AXES_ND]
        br = refine_level_traffic(LevelGeom.for_level(CHART, e["level"]),
                                  dispatch.ROUTE_ND_FUSED)
        assert (br["total"] - br["relayout"]) * 2 \
            <= hb[dispatch.ROUTE_REFERENCE]
    # covered levels: the first writes no fine field, the last reads no
    # coarse one — the intermediate field's write and read (4 bytes each
    # per element, at least) drop out of the chain
    per_level = _pyr_plan("float32", pyramid=False)
    covered = _pyr_plan("float32")
    for e_pl, e_py in zip(per_level, covered):
        assert e_py["route"] == dispatch.ROUTE_PYRAMID
        assert e_py["hbm_bytes"]["selected"] \
            < e_pl["hbm_bytes"]["selected"]
    inter = int(np.prod(LevelGeom.for_level(PYRAMID_CHART, 0).fine_shape))
    saved = sum(e["hbm_bytes"]["selected"] for e in per_level) \
        - sum(e["hbm_bytes"]["selected"] for e in covered)
    assert saved >= 2 * 4 * inter, (saved, inter)


def test_pyramid_budget_fallback():
    """A budget too small for two levels disables the overlay — plan then
    shows the per-level megakernel routing (the §11 fallback rule)."""
    assert dispatch.pyramid_cover(PYRAMID_CHART, vmem_budget=1024) is None
    entries = dispatch.plan(PYRAMID_CHART, platform="cpu", vmem_budget=1024)
    assert [e["route"] for e in entries] \
        == [dispatch.ROUTE_ND_FUSED] * PYRAMID_CHART.n_levels


def test_pyramid_partial_coverage_on_deeper_chart():
    """Deeper levels bust the budget: the prefix stays covered, the big
    tail levels run the megakernel."""
    deep = galactic_dust_chart((8, 16, 16), n_levels=4)
    cover = dispatch.pyramid_cover(deep, itemsize=4)
    assert cover is not None and cover[0] == 2
    routes = [e["route"] for e in dispatch.plan(deep, platform="cpu")]
    assert routes == [dispatch.ROUTE_PYRAMID] * 2 \
        + [dispatch.ROUTE_ND_FUSED] * 2
