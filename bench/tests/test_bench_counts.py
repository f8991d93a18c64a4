"""The least-work counts against hand counts on tiny charts."""
import _paths  # noqa: F401

import counts
import oracle


def _geom(chart, **args):
    cfg = {"chart": chart, "args": dict(boundary="reflect", **args)}
    return oracle.geometry(cfg)


def test_1d_level_flops_and_bytes_by_hand():
    # regular chart 8 points, 1 level, n_csz 3, n_fsz 2: 8 families of 2
    g = _geom("regular_chart", shape0=8, n_levels=1, n_csz=3, n_fsz=2,
              delta0=1.0)
    fine = 16
    # 3 window MACs and 2 noise MACs per fine point, 2 FLOPs a MAC
    assert counts.level_flops(g, 0) == 2 * (fine * 3 + fine * 2)
    w = counts.refine_work(g)
    # xi 16 + level-0 field 8 + final 16, the one shared (R, sqrtD): 2*(3+2)
    assert w["bytes"] == 4 * (16 + 8 + 16 + 2 * (3 + 2))
    assert w["flops"] == counts.level_flops(g, 0)


def test_nd_counts_take_the_cheapest_axis_order():
    # dust chart 6x8x6, one level: fine 12x16x12, window 5, family 4
    g = _geom("galactic_dust_chart", shape0=[6, 8, 6], n_levels=1, n_csz=5,
              n_fsz=4, delta_logr=0.02, origin_logr=0.0, angular_extent=1.0)
    c, f = (6, 8, 6), (12, 16, 12)
    # any order expands one axis at a time: 1/4, 1/2, 1 of the fine size
    macs = 5 * (f[0] * c[1] * c[2] + f[0] * f[1] * c[2] + f[0] * f[1] * f[2])
    noise = 3 * (12 * 16 * 12) * 4
    assert counts.level_flops(g, 0) == 2 * (macs + noise)
    # per-axis matrices: axis 0 per family (3 families), axes 1, 2 shared
    assert counts.matrix_elems(g, 0) == (3 + 1 + 1) * 4 * (5 + 4)


def test_served_slab_counts_the_whole_step_once_per_slab():
    g = _geom("regular_chart", shape0=8, n_levels=1, n_csz=3, n_fsz=2,
              delta0=1.0)
    r, l0 = counts.refine_work(g, 3), counts.level0_work(g, 3)
    step = counts.served_slab_work(g, 3)
    assert l0["flops"] == 2 * 64 * 3 and l0["bytes"] == 4 * (64 + 16 * 3)
    assert step["flops"] == r["flops"] + l0["flops"]
    # matrices 2*(3+2), the 8x8 factor, mean and std of 8 + 16
    # excitations, three fields of 16 written
    assert step["bytes"] == 4 * (10 + 64 + 2 * 24 + 3 * 16)


def test_least_seconds_is_the_larger_bound():
    peaks = {"peak_flops": 1e12, "hbm_bw": 1e9}
    assert counts.least_seconds({"flops": 2e12, "bytes": 1e9}, peaks) == 2.0
    assert counts.least_seconds({"flops": 1e9, "bytes": 3e9}, peaks) == 3.0
