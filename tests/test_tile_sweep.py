"""Seeded-random tile sweep: verifier + numerical parity at off-default
tiles (DESIGN.md §14).

The autotuners pick ONE tile per geometry, so CI would only ever
exercise that point of the (block_families, batch_block, sample_block)
space. This sweep draws seeded-random *valid* configs per route, exports
the launch plans at those tiles (``level_launch_plans`` /
``chart_launch_plans`` overrides — the same records the kernel impls
launch through), requires every static verifier pass to hold, and checks
numerical parity of the interpret-mode run against the jnp reference at
the same tile.
"""
import numpy as np
import pytest

import jax.numpy as jnp

from repro.analysis import kernel_verify as kv
from repro.analysis.scenarios import SCENARIOS
from repro.core import matern32
from repro.core.refine import LevelGeom, axis_refinement_matrices_level
from repro.kernels import dispatch as dsp
from repro.kernels.nd_fused import refine_nd_fused
from repro.kernels.pyramid import refine_pyramid

SEED = 20260808
SAMPLES = 4


def scenario(label):
    return next(s for s in SCENARIOS() if s.label == label)


def draw_1d_configs(rng, t, floor, n):
    """Valid (block_families, batch_block) pairs, non-powers included.

    Families ride the lanes: a block is the whole level or a multiple of
    128 families at or above the floor. The batch rides the sublanes, so
    a slab of SAMPLES (fewer than one sublane tile) is one block."""
    choices = [t] + [b for b in range(128, t, 128) if b >= floor]
    picks = rng.choice(len(choices), size=min(n, len(choices)),
                       replace=False)
    return sorted((choices[int(i)], SAMPLES) for i in picks)


def assert_plans_clean(plans, geom, route, *, label):
    for plan in plans:
        findings = kv.verify_plan(plan, geom=geom, route=route,
                                  samples=SAMPLES, scenario=label)
        assert findings == [], "\n".join(str(f) for f in findings)


class TestSweep1D:
    def test_stationary_1d_tiles(self):
        scn = scenario("tod-fp32")
        chart = scn.chart()
        kernel = matern32.with_defaults(rho=scn.rho)()
        geom = LevelGeom.for_level(chart, 2)
        t = geom.T[0]
        floor = dsp.block1d_floor(t, geom.n_csz, geom.n_fsz)
        rng = np.random.default_rng(SEED)
        rs, ds = axis_refinement_matrices_level(chart, kernel, 2)
        r, d = jnp.asarray(rs[0]), jnp.asarray(ds[0])
        field = jnp.asarray(
            rng.normal(size=(SAMPLES,) + tuple(geom.coarse_shape)),
            jnp.float32)
        xi = jnp.asarray(rng.normal(size=(SAMPLES, t, geom.n_fsz)),
                         jnp.float32)
        want = dsp.refine(field, xi, r, d, geom,
                          backend=dsp.BACKEND_REFERENCE, sample_axis=True)
        for b_f, b_b in draw_1d_configs(rng, t, floor, 4):
            plans = dsp.level_launch_plans(
                geom, samples=SAMPLES, dtype="float32",
                block_families=b_f, sample_block=b_b)
            assert plans[0].params["b_f"] == b_f
            assert plans[0].params["b_b"] == b_b
            assert_plans_clean(plans, geom, dsp.route_for(geom),
                               label=f"tod b_f={b_f} b_b={b_b}")
            got = dsp.refine(field, xi, r, d, geom,
                             backend=dsp.BACKEND_INTERPRET,
                             block_families=b_f, sample_block=b_b,
                             sample_axis=True)
            np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                       rtol=1e-5, atol=1e-5)


class TestSweepFused:
    def test_nd_fused_tiles(self):
        scn = scenario("image-fp32")
        chart = scn.chart()
        kernel = matern32.with_defaults(rho=scn.rho)()
        geom = LevelGeom.for_level(chart, 1)
        route = dsp.route_for(geom, have_axis_mats=True)
        assert route == dsp.ROUTE_ND_FUSED
        rng = np.random.default_rng(SEED + 1)
        rs, ds = axis_refinement_matrices_level(chart, kernel, 1)
        rs = [jnp.asarray(r) for r in rs]
        ds = [jnp.asarray(d) for d in ds]
        nd = len(geom.coarse_shape)
        field = jnp.asarray(
            rng.normal(size=(SAMPLES,) + tuple(geom.coarse_shape)),
            jnp.float32)
        xi = jnp.asarray(
            rng.normal(size=(SAMPLES, int(np.prod(geom.T)),
                             geom.n_fsz ** nd)), jnp.float32)
        want = refine_nd_fused(field, xi, rs, ds, geom,
                               interpret="reference", sample_axis=True)
        q_max = (geom.n_csz - 1) // max(1, geom.n_fsz // 2)
        cfgs = {(int(rng.integers(max(q_max, 1), geom.T[0] + 1)),
                 int(rng.integers(1, SAMPLES + 1))) for _ in range(3)}
        for b_f, s_b in sorted(cfgs):
            plans = dsp.level_launch_plans(
                geom, route, samples=SAMPLES, dtype="float32",
                block_families=b_f, sample_block=s_b)
            assert_plans_clean(plans, geom, route,
                               label=f"image b_f={b_f} s_b={s_b}")
            got = refine_nd_fused(field, xi, rs, ds, geom, interpret=True,
                                  block_families=b_f, sample_block=s_b,
                                  sample_axis=True)
            np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                       rtol=1e-5, atol=1e-5)


class TestSweepPyramid:
    @pytest.mark.parametrize("s_b", [1, 3])
    def test_pyramid_cover_sample_blocks(self, s_b):
        scn = scenario("dust-fp32")
        chart = scn.chart()
        kernel = matern32.with_defaults(rho=scn.rho)()
        # the padded working set of a 3-D polyphase tile grows with the
        # slab: size the sweep's VMEM budget so every drawn slab is valid
        budget = dsp.pyramid_bytes(
            [LevelGeom.for_level(chart, lvl) for lvl in range(2)],
            s_b=s_b, itemsize=4)
        groups = dsp.chart_launch_plans(chart, samples=SAMPLES,
                                        dtype="float32", sample_block=s_b,
                                        vmem_budget=budget)
        grp = groups[0]
        assert grp["route"] == dsp.ROUTE_PYRAMID
        plan = grp["plans"][0]
        assert plan.params["s_b"] == s_b
        geoms = grp["geom"]
        findings = kv.verify_plan(plan, samples=SAMPLES, vmem_budget=budget,
                                  scenario=f"dust s_b={s_b}")
        assert findings == [], "\n".join(str(f) for f in findings)

        rng = np.random.default_rng(SEED + 2)
        mats, xis = [], []
        for lvl, g in enumerate(geoms):
            rs, ds = axis_refinement_matrices_level(chart, kernel, lvl)
            mats.append(([jnp.asarray(r) for r in rs],
                         [jnp.asarray(d) for d in ds]))
            nd = len(g.coarse_shape)
            xis.append(jnp.asarray(
                rng.normal(size=(SAMPLES, int(np.prod(g.T)),
                                 g.n_fsz ** nd)), jnp.float32))
        field = jnp.asarray(
            rng.normal(size=(SAMPLES,) + tuple(geoms[0].coarse_shape)),
            jnp.float32)
        want = refine_pyramid(field, xis, mats, geoms,
                              interpret="reference", sample_axis=True)
        got = refine_pyramid(field, xis, mats, geoms, interpret=True,
                             sample_block=s_b, sample_axis=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)
