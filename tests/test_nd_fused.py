"""Single-launch fused N-D level megakernel (DESIGN.md §10).

Acceptance (ISSUE 3): the fused route is exact vs the per-axis passes and
the joint ``refine_level`` reference at 1e-5 for 2-D/3-D, both boundaries,
mixed stationary/charted axes, including gradients through the custom VJP;
the plan() HBM-bytes model shows >= 2x traffic reduction per 3-D level.
All kernels run in interpret mode on CPU (exact BlockSpec tiling).
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import matern32, regular_chart
from repro.core.charts import Chart, galactic_dust_chart
from repro.core.refine import (
    LevelGeom,
    axis_refinement_matrices_level,
    refine_level,
)
from repro.kernels import dispatch, nd, nd_fused
from repro.roofline import refine_level_traffic


# this module covers the kernel tiling: pin the interpret backend through
# dispatch/ICR (the production CPU default is the jnp oracle)
pytestmark = pytest.mark.usefixtures("interpret_backend")

ND_CHARTS = [
    (lambda: regular_chart((12, 10), 2, boundary="shrink"), "2d-shrink"),
    (lambda: regular_chart((12, 16), 2, boundary="reflect"), "2d-reflect"),
    (lambda: Chart(  # 2-D, charted (log) axis 0, invariant axis 1
        shape0=(14, 12), n_levels=2, delta0=(0.05, 1.0), boundary="shrink",
        phi_inv=lambda x: jnp.stack(
            [jnp.exp(x[..., 0]), x[..., 1]], axis=-1),
        invariant=(False, True)), "2d-mixed-shrink"),
    (lambda: regular_chart((8, 8, 12), 1, boundary="shrink"), "3d-shrink"),
    (lambda: galactic_dust_chart((6, 8, 8), n_levels=2), "3d-dust-reflect"),
]
IDS = [n for _, n in ND_CHARTS]


def _level_data(c, lvl, seed_name):
    k = matern32.with_defaults(rho=3.0)()
    geom = LevelGeom.for_level(c, lvl)
    rs, ds = axis_refinement_matrices_level(c, k, lvl)
    rng = np.random.default_rng([lvl, *seed_name.encode()])
    field = jnp.asarray(rng.normal(size=geom.coarse_shape), jnp.float32)
    f = int(np.prod(geom.T))
    xi = jnp.asarray(
        rng.normal(size=(f, geom.n_fsz ** len(geom.T))), jnp.float32)
    return geom, rs, ds, field, xi, rng


def _kron_joint(rs, ds):
    """Joint (*kept_T, fsz^d, csz^d) matrices from per-axis factors."""
    rs = [m if m.ndim == 3 else m[None] for m in rs]
    ds = [m if m.ndim == 3 else m[None] for m in ds]
    kept = tuple(m.shape[0] for m in rs)

    def build(mats):
        out = mats[0]
        for m in mats[1:]:
            out = jnp.einsum("...FC,tfc->...tFfCc", out, m)
            sh = out.shape
            out = out.reshape(sh[:-4] + (sh[-4] * sh[-3], sh[-2] * sh[-1]))
        return out

    r = build(rs)
    d = build(ds)
    return r.reshape(kept + r.shape[1:]), d.reshape(kept + d.shape[1:])


@pytest.mark.parametrize("chartf,name", ND_CHARTS, ids=IDS)
def test_fused_matches_axes_and_joint(chartf, name):
    """Megakernel == per-axis passes == joint refine_level (Kronecker
    matrices), every level, both boundaries, mixed axes — pinned 1e-5."""
    c = chartf()
    for lvl in range(c.n_levels):
        geom, rs, ds, field, xi, _ = _level_data(c, lvl, name)
        got = nd_fused.refine_nd_fused(field, xi, rs, ds, geom,
                                       interpret=True)
        assert got.shape == geom.fine_shape
        axes = nd.refine_axes(field, xi, rs, ds, geom, interpret=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(axes),
                                   rtol=1e-5, atol=1e-5)
        r_j, d_j = _kron_joint(rs, ds)
        joint = refine_level(field, xi, r_j, d_j, geom)
        np.testing.assert_allclose(np.asarray(got), np.asarray(joint),
                                   rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("chartf,name", ND_CHARTS, ids=IDS)
def test_fused_vjp_matches_axes(chartf, name):
    """jax.grad through the megakernel's custom VJP (fixed matrices: the
    hand-composed 1-D adjoint chain) == grad through the per-axis passes."""
    c = chartf()
    for lvl in range(c.n_levels):
        geom, rs, ds, field, xi, rng = _level_data(c, lvl, name)
        v = jnp.asarray(rng.normal(size=geom.fine_shape), jnp.float32)
        loss_f = lambda fl, x: jnp.sum(
            nd_fused.refine_nd_fused(fl, x, rs, ds, geom, interpret=True) * v)
        loss_a = lambda fl, x: jnp.sum(
            nd.refine_axes(fl, x, rs, ds, geom, interpret=True) * v)
        got = jax.grad(loss_f, argnums=(0, 1))(field, xi)
        want = jax.grad(loss_a, argnums=(0, 1))(field, xi)
        for a, b in zip(want, got):
            np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                       rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("chartf,name", [ND_CHARTS[1], ND_CHARTS[-1]],
                         ids=["2d-reflect", "3d-dust-reflect"])
def test_fused_matrix_cotangents(chartf, name):
    """Learned-θ path: perturbing the factors flips the backward onto the
    jnp-reference VJP — matrix cotangents must match the per-axis route."""
    c = chartf()
    geom, rs, ds, field, xi, rng = _level_data(c, 0, name)
    v = jnp.asarray(rng.normal(size=geom.fine_shape), jnp.float32)
    g_f = jax.grad(lambda rr, dd: jnp.sum(
        nd_fused.refine_nd_fused(field, xi, rr, dd, geom, interpret=True)
        * v), argnums=(0, 1))(rs, ds)
    g_a = jax.grad(lambda rr, dd: jnp.sum(
        nd.refine_axes(field, xi, rr, dd, geom, interpret=True) * v),
        argnums=(0, 1))(rs, ds)
    for a, b in zip(jax.tree_util.tree_leaves(g_a),
                    jax.tree_util.tree_leaves(g_f)):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                   rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("block", [2, 4, 1024])
def test_fused_block_size_invariance(block):
    """Output must not depend on the axis-0 family tile size."""
    c = galactic_dust_chart((6, 8, 8), n_levels=2)
    geom, rs, ds, field, xi, _ = _level_data(c, 1, "blocks")
    base = nd_fused.refine_nd_fused(field, xi, rs, ds, geom, interpret=True,
                                    block_families=8)
    got = nd_fused.refine_nd_fused(field, xi, rs, ds, geom, interpret=True,
                                   block_families=block)
    np.testing.assert_allclose(np.asarray(got), np.asarray(base), rtol=1e-6)


@pytest.mark.parametrize("s_blk", [1, 2, 8])
def test_fused_sample_block_invariance(s_blk):
    """Sample-slab size must not change values; parity vs per-sample loop."""
    c = regular_chart((12, 16), 1, boundary="reflect")
    geom, rs, ds, _, _, rng = _level_data(c, 0, "samples")
    n_s = 5
    field = jnp.asarray(rng.normal(size=(n_s,) + geom.coarse_shape),
                        jnp.float32)
    f = int(np.prod(geom.T))
    xi = jnp.asarray(rng.normal(size=(n_s, f, geom.n_fsz**2)), jnp.float32)
    got = nd_fused.refine_nd_fused(field, xi, rs, ds, geom, interpret=True,
                                   sample_axis=True, sample_block=s_blk)
    want = jnp.stack([
        nd_fused.refine_nd_fused(field[i], xi[i], rs, ds, geom,
                                 interpret=True)
        for i in range(n_s)
    ])
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-6,
                               atol=1e-6)


class TestDispatchFused:
    def test_dust_routes_fused_everywhere(self):
        # pyramid=False: the per-level routing this suite pins (the pyramid
        # overlay on top of it is covered by test_pyramid/test_plan_smoke)
        c = galactic_dust_chart((8, 16, 16), n_levels=3)
        for e in dispatch.plan(c, platform="cpu", pyramid=False):
            assert e["route"] == dispatch.ROUTE_ND_FUSED, e
            assert e["vjp"]["route"] == dispatch.ROUTE_ND_FUSED + "-adjoint"

    def test_vmem_fallback_rule(self):
        """A tile that busts the budget falls back to the per-axis passes;
        the autotuner is the single source of the decision."""
        geom = LevelGeom.for_level(
            galactic_dust_chart((6, 8, 8), n_levels=2), 0)
        assert dispatch.autotune_nd_fused(geom) is not None
        assert dispatch.autotune_nd_fused(geom, vmem_budget=256) is None
        assert dispatch.route_for(geom, have_axis_mats=True) \
            == dispatch.ROUTE_ND_FUSED

    def test_autotune_blocks_bounded(self):
        geom = LevelGeom.for_level(
            galactic_dust_chart((8, 16, 16), n_levels=3), 2)
        b_f, s_b = dispatch.autotune_nd_fused(geom, samples=16)
        assert 1 <= b_f <= geom.T[0]
        assert 1 <= s_b <= 16
        # the chosen tile obeys the working-set model
        charted = tuple(k > 1 for k in geom.kept_T)
        assert dispatch._fused_tile_bytes(geom, charted, b_f, s_b, 4) \
            <= dispatch.VMEM_BUDGET_BYTES

    def test_refine_routes_fused(self):
        """dispatch.refine end-to-end on the fused route == reference
        refine_level with Kronecker-joint matrices."""
        c = regular_chart((12, 16), 1, boundary="reflect")
        geom, rs, ds, field, xi, _ = _level_data(c, 0, "dispatch")
        out = dispatch.refine(field, xi, None, None, geom,
                              axis_mats=(rs, ds),
                              backend=dispatch.BACKEND_INTERPRET)
        r_j, d_j = _kron_joint(rs, ds)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(refine_level(field, xi, r_j, d_j,
                                                     geom)),
            rtol=1e-5, atol=1e-5)


class TestTrafficModel:
    def test_3d_level_traffic_reduction(self):
        """Acceptance: >= 2x modeled HBM traffic reduction per 3-D level,
        fused vs per-axis."""
        c = galactic_dust_chart((8, 16, 16), n_levels=3)
        for e in dispatch.plan(c, platform="cpu"):
            hb = e["hbm_bytes"]
            assert hb["nd-fused"] * 2 <= hb["nd-axes"], hb
            assert hb["selected"] == hb[e["route"]]

    def test_model_matches_first_principles(self):
        """The fused estimate is read L + read ξ + write N + matrices for
        the launch, and a read+write of each of those arrays for the
        phase-plane relayouts around it — recomputed here from the chart
        shapes alone (guards the plan wiring against drifting from the
        roofline model)."""
        c = galactic_dust_chart((6, 8, 8), n_levels=2)
        for lvl in range(c.n_levels):
            geom = LevelGeom.for_level(c, lvl)
            got = refine_level_traffic(geom, "nd-fused")["total"]
            s = geom.n_fsz // 2
            q = (geom.n_csz - 1) // s
            read_l = 1
            for a, n in enumerate(geom.coarse_shape):
                read_l *= max(n + 2 * geom.b, (geom.T[a] + q) * s)
            n_out = int(np.prod(geom.fine_shape))
            # field + ξ + fine, f32: the launch, then the relayouts
            approx = 3 * 4 * (read_l + 2 * n_out)
            assert abs(got - approx) / approx < 0.10, (got, approx)

    def test_samples_amortize_matrices(self):
        geom = LevelGeom.for_level(galactic_dust_chart((6, 8, 8), 2), 1)
        one = refine_level_traffic(geom, "nd-fused", samples=1)
        many = refine_level_traffic(geom, "nd-fused", samples=8)
        assert many["matrices"] == one["matrices"]
        assert many["fine_write"] == 8 * one["fine_write"]
