"""VMEM-resident pyramid route (ISSUE 4 tentpole / DESIGN.md §11).

Acceptance: the single-launch pyramid is exact vs the per-level megakernel
chain (and the 1-D kernel chain) at 1e-5 — forward, fixed-matrix VJP and
learned-θ matrix cotangents — for 1-D/2-D/3-D charts, both boundaries,
sample batches and every sample-block size; the residency autotuner covers
exactly the prefix whose §11 working-set model fits the budget. All
kernels run in interpret mode on CPU (exact BlockSpec machinery).
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import ICR, matern32, regular_chart
from repro.core.charts import galactic_dust_chart, log_chart
from repro.core.refine import (
    LevelGeom,
    axis_refinement_matrices_level,
    refinement_matrices_level,
)
from repro.kernels import dispatch, nd_fused, pyramid


# this module covers the kernel tiling: pin the interpret backend through
# dispatch/ICR (the production CPU default is the jnp oracle)
pytestmark = pytest.mark.usefixtures("interpret_backend")

CHARTS = [
    ("1d-stationary", lambda: regular_chart(32, 3, boundary="reflect"), 10.0),
    ("1d-charted", lambda: log_chart(32, 3, n_csz=5, n_fsz=4, delta0=0.05),
     1.0),
    ("2d-shrink", lambda: regular_chart((12, 10), 2, boundary="shrink"), 4.0),
    ("2d-reflect", lambda: regular_chart((12, 16), 2, boundary="reflect"),
     4.0),
    # 3-D tiles merge their two innermost position dims: the charted
    # radial axis as the inner, then the outer, of the merged pair, and
    # the zero-filled shrink boundary across the merged rows
    ("3d-charted-inner",
     lambda: galactic_dust_chart((12, 6, 6), n_levels=2), 0.5),
    ("3d-charted-outer",
     lambda: galactic_dust_chart((8, 6, 12), n_levels=2), 0.5),
    ("3d-shrink", lambda: regular_chart((12, 10, 10), 2, boundary="shrink"),
     4.0),
    ("3d-dust-reflect", lambda: galactic_dust_chart((6, 8, 8), n_levels=2),
     0.5),
]
IDS = [n for n, _, _ in CHARTS]


def _pyramid_inputs(c, rho, seed, *, batch=None):
    """(geoms, mats, field, xis): per-axis factor convention for any ndim."""
    k = matern32.with_defaults(rho=rho)()
    geoms = [LevelGeom.for_level(c, l) for l in range(c.n_levels)]
    mats = []
    for l in range(c.n_levels):
        if c.ndim > 1:
            mats.append(axis_refinement_matrices_level(c, k, l))
        else:
            r, d = refinement_matrices_level(c, k, l)
            if r.shape[0] == 1:
                r, d = r.reshape(r.shape[-2:]), d.reshape(d.shape[-2:])
            mats.append(([r], [d]))
    rng = np.random.default_rng(seed)
    lead = () if batch is None else (batch,)
    field = jnp.asarray(
        rng.normal(size=lead + tuple(geoms[0].coarse_shape)), jnp.float32)
    xis = [jnp.asarray(rng.normal(
        size=lead + (int(np.prod(g.T)), g.n_fsz ** c.ndim)), jnp.float32)
        for g in geoms]
    return geoms, mats, field, xis


def _chain(field, xis, mats, geoms):
    """Per-level ground truth: the megakernel on N-D levels, dispatch's 1-D
    kernels on 1-D levels — what the pyramid must reproduce exactly."""
    x = field
    for l, geom in enumerate(geoms):
        if len(geom.coarse_shape) > 1:
            x = nd_fused.refine_nd_fused(x, xis[l], mats[l][0], mats[l][1],
                                         geom, interpret=True)
        else:
            r, d = mats[l]
            x = dispatch.refine(x, xis[l], r[0], d[0], geom,
                                backend=dispatch.BACKEND_INTERPRET)
    return x


@pytest.mark.parametrize("name,chartf,rho", CHARTS, ids=IDS)
def test_pyramid_matches_per_level_chain(name, chartf, rho):
    c = chartf()
    geoms, mats, field, xis = _pyramid_inputs(c, rho, [1, *name.encode()])
    got = pyramid.refine_pyramid(field, xis, mats, geoms, interpret=True)
    want = _chain(field, xis, mats, geoms)
    assert got.shape == tuple(geoms[-1].fine_shape)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("name,chartf,rho", CHARTS, ids=IDS)
def test_pyramid_vjp_matches_chain(name, chartf, rho):
    """Fixed matrices: grad w.r.t. (field, every level's ξ) through the
    pyramid's custom VJP == grad through the per-level chain."""
    c = chartf()
    geoms, mats, field, xis = _pyramid_inputs(c, rho, [2, *name.encode()])
    rng = np.random.default_rng([3, *name.encode()])
    v = jnp.asarray(rng.normal(size=geoms[-1].fine_shape), jnp.float32)
    g_p = jax.grad(lambda f, xs: jnp.sum(
        pyramid.refine_pyramid(f, xs, mats, geoms, interpret=True) * v),
        argnums=(0, 1))(field, xis)
    g_c = jax.grad(lambda f, xs: jnp.sum(
        _chain(f, xs, mats, geoms) * v), argnums=(0, 1))(field, xis)
    for a, b in zip(jax.tree.leaves(g_c), jax.tree.leaves(g_p)):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                   rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("name,chartf,rho",
                         [CHARTS[1], CHARTS[-1]],
                         ids=["1d-charted", "3d-dust-reflect"])
def test_pyramid_matrix_cotangents(name, chartf, rho):
    """Learning θ: perturbing the factors flips the backward onto the
    reference VJP — matrix cotangents must match the per-level chain."""
    c = chartf()
    geoms, mats, field, xis = _pyramid_inputs(c, rho, [4, *name.encode()])
    rng = np.random.default_rng([5, *name.encode()])
    v = jnp.asarray(rng.normal(size=geoms[-1].fine_shape), jnp.float32)
    g_p = jax.grad(lambda ms: jnp.sum(
        pyramid.refine_pyramid(field, xis, ms, geoms, interpret=True) * v)
        )(mats)
    g_c = jax.grad(lambda ms: jnp.sum(
        _chain(field, xis, ms, geoms) * v))(mats)
    for a, b in zip(jax.tree.leaves(g_c), jax.tree.leaves(g_p)):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                   rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("s_blk", [1, 2, 8])
def test_pyramid_sample_block_invariance(s_blk):
    """Sample-slab size must not change values; parity vs per-sample calls."""
    c = galactic_dust_chart((6, 8, 8), n_levels=2)
    geoms, mats, field, xis = _pyramid_inputs(c, 0.5, 7, batch=5)
    got = pyramid.refine_pyramid(field, xis, mats, geoms, interpret=True,
                                 sample_axis=True, sample_block=s_blk)
    want = jnp.stack([
        pyramid.refine_pyramid(field[i], [x[i] for x in xis], mats, geoms,
                               interpret=True)
        for i in range(5)
    ])
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-6, atol=1e-6)


def test_pyramid_rejects_non_consecutive_levels():
    c = galactic_dust_chart((6, 8, 8), n_levels=2)
    geoms, mats, field, xis = _pyramid_inputs(c, 0.5, 8)
    with pytest.raises(ValueError, match="consecutive"):
        pyramid.refine_pyramid(field, [xis[0], xis[0]],
                               [mats[0], mats[0]], [geoms[0], geoms[0]],
                               interpret=True)


class TestICREndToEnd:
    def test_pyramid_on_equals_off(self):
        """ICR(use_pallas) with the pyramid overlay == per-level routing —
        the overlay is a pure execution-plan change."""
        c = galactic_dust_chart((6, 8, 8), n_levels=2)
        kern = matern32.with_defaults(rho=0.5)
        on = ICR(chart=c, kernel=kern, use_pallas=True)
        off = ICR(chart=c, kernel=kern, use_pallas=True, use_pyramid=False)
        xi = on.init_xi(jax.random.PRNGKey(0))
        mats = on.matrices()
        np.testing.assert_allclose(
            np.asarray(on.apply_sqrt(mats, xi)),
            np.asarray(off.apply_sqrt(mats, xi)), rtol=1e-5, atol=1e-5)

    def test_apply_sqrt_T_through_pyramid(self):
        """The Wiener-style transpose (VJP at the origin) runs through the
        pyramid backward and matches the pyramid-off adjoint chain."""
        c = galactic_dust_chart((6, 8, 8), n_levels=2)
        kern = matern32.with_defaults(rho=0.5)
        on = ICR(chart=c, kernel=kern, use_pallas=True)
        off = ICR(chart=c, kernel=kern, use_pallas=True, use_pyramid=False)
        mats = on.matrices()
        v = on.sample(jax.random.PRNGKey(2))
        for a, b in zip(on.apply_sqrt_T(mats, v), off.apply_sqrt_T(mats, v)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-4, atol=1e-5)

    def test_jit_grad_through_pyramid(self):
        """MAP-style jitted value_and_grad runs (and is finite) through the
        pyramid forward + replayed backward."""
        c = regular_chart(64, 3, boundary="reflect")
        icr = ICR(chart=c, kernel=matern32.with_defaults(rho=10.0),
                  use_pallas=True)
        mats = icr.matrices()
        xi = icr.init_xi(jax.random.PRNGKey(0))
        val, grad = jax.jit(jax.value_and_grad(
            lambda xs: 0.5 * jnp.sum(icr.apply_sqrt(mats, xs) ** 2)))(xi)
        assert bool(jnp.isfinite(val))
        assert all(bool(jnp.isfinite(g).all()) for g in jax.tree.leaves(grad))


class TestAutotunePyramid:
    def test_cover_is_prefix_and_budget_monotone(self):
        deep = galactic_dust_chart((8, 16, 16), n_levels=4)
        geoms = [LevelGeom.for_level(deep, l) for l in range(4)]
        ks = []
        for budget in (2**20, 8 * 2**20, 64 * 2**20, 2**40):
            cover = dispatch.autotune_pyramid(geoms, vmem_budget=budget)
            ks.append(0 if cover is None else cover[0])
        assert ks == sorted(ks) and ks[-1] == 4
        assert ks[2] == 2  # the default budget splits exactly at level 2

    def test_one_level_is_not_a_pyramid(self):
        geoms = [LevelGeom.for_level(galactic_dust_chart((6, 8, 8), 2), 0)]
        assert dispatch.autotune_pyramid(geoms) is None

    def test_sample_slab_bounded_and_modeled(self):
        c = galactic_dust_chart((6, 8, 8), n_levels=2)
        geoms = [LevelGeom.for_level(c, l) for l in range(2)]
        k, s_b = dispatch.autotune_pyramid(geoms, samples=16)
        assert k == 2 and 1 <= s_b <= 16
        # the slab is the largest the pyramid's own working-set model
        # holds: it fits, and doubling it (short of all 16) would not
        budget = dispatch.VMEM_BUDGET_BYTES
        assert dispatch.pyramid_bytes(geoms, s_b=s_b, itemsize=4) <= budget
        if s_b < 16:
            assert dispatch.pyramid_bytes(geoms, s_b=min(2 * s_b, 16),
                                          itemsize=4) > budget

    def test_reference_level_ends_the_prefix(self):
        """N-D chart without axis factors: nothing is structured, no
        pyramid (the cover respects route_for)."""
        c = galactic_dust_chart((6, 8, 8), n_levels=2)
        assert dispatch.pyramid_cover(c, have_axis_mats=False) is None
