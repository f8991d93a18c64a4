"""Per-kernel validation: sweep shapes/dtypes, assert_allclose vs ref.py.

Kernels execute in interpret mode on CPU (the body runs as pure jnp), which
checks the BlockSpec tiling, halo views and window construction exactly.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import ICR, log_chart, matern32, regular_chart
from repro.core.charts import Chart, galactic_dust_chart
from repro.core.refine import (
    LevelGeom,
    axis_refinement_matrices_level,
    refine_level,
    refine_level_T,
    refinement_matrices_level,
)
from repro.kernels import dispatch, nd
from repro.kernels import ref as R
from repro.kernels.icr_refine import (
    interleave,
    interleave_form,
    refine_charted_pallas,
    refine_stationary_pallas,
)


# this module covers the kernel tiling: pin the interpret backend through
# dispatch/ICR (the production CPU default is the jnp oracle)
pytestmark = pytest.mark.usefixtures("interpret_backend")


PARAMS = [(3, 2), (3, 4), (5, 2), (5, 4), (5, 6)]


def _rand(rng, shape, dtype):
    return jnp.asarray(rng.normal(size=shape), dtype)


@pytest.mark.parametrize("ncsz,nfsz", PARAMS)
@pytest.mark.parametrize("t", [7, 64, 300])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_stationary_matches_ref(ncsz, nfsz, t, dtype):
    rng = np.random.default_rng(ncsz * 100 + nfsz + t)
    batch = 3
    coarse = _rand(rng, (batch, R.coarse_len(t, ncsz, nfsz)), dtype)
    xi = _rand(rng, (batch, t, nfsz), dtype)
    r = _rand(rng, (nfsz, ncsz), dtype)
    d = _rand(rng, (nfsz, nfsz), dtype)
    want = R.refine_stationary_ref(coarse, xi, r, d)
    got = refine_stationary_pallas(coarse, xi, r, d, n_csz=ncsz, n_fsz=nfsz,
                                   block_families=32, interpret=True)
    tol = 1e-5 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("ncsz,nfsz", PARAMS)
@pytest.mark.parametrize("t", [9, 128])
def test_charted_matches_ref(ncsz, nfsz, t):
    rng = np.random.default_rng(ncsz * 10 + nfsz + t)
    coarse = _rand(rng, (2, R.coarse_len(t, ncsz, nfsz)), jnp.float32)
    xi = _rand(rng, (2, t, nfsz), jnp.float32)
    r = _rand(rng, (t, nfsz, ncsz), jnp.float32)
    d = _rand(rng, (t, nfsz, nfsz), jnp.float32)
    want = R.refine_charted_ref(coarse, xi, r, d)
    got = refine_charted_pallas(coarse, xi, r, d, n_csz=ncsz, n_fsz=nfsz,
                                block_families=32, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("block", [16, 64, 1024])
def test_block_size_invariance(block):
    """Output must not depend on the VMEM tile size."""
    rng = np.random.default_rng(0)
    ncsz, nfsz, t = 5, 4, 200
    coarse = _rand(rng, (1, R.coarse_len(t, ncsz, nfsz)), jnp.float32)
    xi = _rand(rng, (1, t, nfsz), jnp.float32)
    r = _rand(rng, (nfsz, ncsz), jnp.float32)
    d = _rand(rng, (nfsz, nfsz), jnp.float32)
    # the one-block launch (every family in one tile) is the baseline; the
    # kernel's own arithmetic must not change with the tiling
    base = refine_stationary_pallas(coarse, xi, r, d, n_csz=ncsz, n_fsz=nfsz,
                                    block_families=t, interpret=True)
    got = refine_stationary_pallas(coarse, xi, r, d, n_csz=ncsz, n_fsz=nfsz,
                                   block_families=block, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(base), rtol=1e-6)


class TestOpsIntegration:
    """dispatch.refine must agree with core.refine.refine_level end-to-end."""

    def test_stationary_shrink_end_to_end(self):
        c = regular_chart(64, 2, n_csz=5, n_fsz=4)
        icr_ref = ICR(chart=c, kernel=matern32.with_defaults(rho=8.0))
        icr_pal = ICR(chart=c, kernel=matern32.with_defaults(rho=8.0),
                      use_pallas=True)
        key = jax.random.PRNGKey(3)
        xi = icr_ref.init_xi(key)
        mats = icr_ref.matrices()
        a = icr_ref.apply_sqrt(mats, xi)
        b = icr_pal.apply_sqrt(mats, xi)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-5)

    def test_stationary_reflect_end_to_end(self):
        c = regular_chart(64, 2, boundary="reflect")
        icr_ref = ICR(chart=c, kernel=matern32.with_defaults(rho=8.0))
        icr_pal = ICR(chart=c, kernel=matern32.with_defaults(rho=8.0),
                      use_pallas=True)
        key = jax.random.PRNGKey(4)
        xi = icr_ref.init_xi(key)
        mats = icr_ref.matrices()
        np.testing.assert_allclose(
            np.asarray(icr_ref.apply_sqrt(mats, xi)),
            np.asarray(icr_pal.apply_sqrt(mats, xi)),
            rtol=1e-5, atol=1e-5,
        )

    def test_charted_op_matches_core(self):
        """Charted per-family kernel == core refine on the log chart."""
        c = log_chart(32, 1, n_csz=5, n_fsz=4, delta0=0.05)
        k = matern32.with_defaults(rho=1.0)()
        r, d = refinement_matrices_level(c, k, 0)
        geom = LevelGeom.for_level(c, 0)
        rng = np.random.default_rng(1)
        field = jnp.asarray(rng.normal(size=geom.coarse_shape), jnp.float32)
        t = geom.T[0]
        xi = jnp.asarray(rng.normal(size=(t, geom.n_fsz)), jnp.float32)
        from repro.core.refine import refine_level

        want = refine_level(field, xi, r, d, geom)
        got = dispatch.refine(field, xi, r, d, geom,
                              backend=dispatch.BACKEND_INTERPRET)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)

    def test_nd_falls_back_to_core(self):
        """Without per-axis factors, N-D joint matrices use the reference."""
        c = regular_chart((8, 8), 1)
        k = matern32.with_defaults(rho=4.0)()
        r, d = refinement_matrices_level(c, k, 0)
        geom = LevelGeom.for_level(c, 0)
        assert dispatch.route_for(geom) == dispatch.ROUTE_REFERENCE
        rng = np.random.default_rng(2)
        field = jnp.asarray(rng.normal(size=geom.coarse_shape), jnp.float32)
        f = int(np.prod(geom.T))
        xi = jnp.asarray(rng.normal(size=(f, geom.n_fsz**2)), jnp.float32)
        out = dispatch.refine(field, xi, r, d, geom)
        assert out.shape == geom.fine_shape
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(refine_level(field, xi, r, d, geom)),
            rtol=1e-6, atol=1e-6,
        )


# -- N-D fused path (per-axis passes, DESIGN.md §4) ----------------------------
def _kron_joint(rs, ds, geom):
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
    return r.reshape(kept + r.shape[1:]), (
        lambda d: d.reshape(kept + d.shape[1:]))(build(ds))


ND_CHARTS = [
    # (chart factory, id) — stationary / charted axes x shrink / reflect
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


@pytest.mark.parametrize("chartf,name", ND_CHARTS, ids=[n for _, n in ND_CHARTS])
def test_nd_axes_matches_refine_level(chartf, name):
    """Per-axis fused passes == joint refine_level on Kronecker matrices.

    This pins the implementation exactly: given factored matrices, the N-D
    Pallas path (interpret mode) must reproduce the joint jnp reference."""
    c = chartf()
    k = matern32.with_defaults(rho=3.0)()
    for lvl in range(c.n_levels):
        geom = LevelGeom.for_level(c, lvl)
        rs, ds = axis_refinement_matrices_level(c, k, lvl)
        rng = np.random.default_rng([lvl, *name.encode()])
        field = jnp.asarray(rng.normal(size=geom.coarse_shape), jnp.float32)
        f = int(np.prod(geom.T))
        xi = jnp.asarray(
            rng.normal(size=(f, geom.n_fsz ** len(geom.T))), jnp.float32)
        got = nd.refine_axes(field, xi, rs, ds, geom, interpret=True)
        r_j, d_j = _kron_joint(rs, ds, geom)
        want = refine_level(field, xi, r_j, d_j, geom)
        assert got.shape == geom.fine_shape
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("chartf,name", ND_CHARTS[:2] + ND_CHARTS[-1:],
                         ids=["2d-shrink", "2d-reflect", "3d-dust-reflect"])
def test_nd_axes_matches_oracle(chartf, name):
    """Fused N-D passes are bit-exact vs the independent jnp oracle."""
    c = chartf()
    k = matern32.with_defaults(rho=3.0)()
    geom = LevelGeom.for_level(c, 0)
    rs, ds = axis_refinement_matrices_level(c, k, 0)
    rng = np.random.default_rng(7)
    field = jnp.asarray(rng.normal(size=geom.coarse_shape), jnp.float32)
    f = int(np.prod(geom.T))
    xi = jnp.asarray(
        rng.normal(size=(f, geom.n_fsz ** len(geom.T))), jnp.float32)
    got = nd.refine_axes(field, xi, rs, ds, geom, interpret=True)
    want = R.refine_axes_ref(field, xi, rs, ds, T=geom.T, n_fsz=geom.n_fsz,
                             boundary=geom.boundary, b=geom.b)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("block", [8, 32, 1024])
def test_nd_block_size_invariance(block):
    c = regular_chart((16, 24), 1, boundary="reflect")
    k = matern32.with_defaults(rho=4.0)()
    geom = LevelGeom.for_level(c, 0)
    rs, ds = axis_refinement_matrices_level(c, k, 0)
    rng = np.random.default_rng(3)
    field = jnp.asarray(rng.normal(size=geom.coarse_shape), jnp.float32)
    f = int(np.prod(geom.T))
    xi = jnp.asarray(rng.normal(size=(f, geom.n_fsz**2)), jnp.float32)
    base = nd.refine_axes(field, xi, rs, ds, geom, interpret=True,
                          block_families=16)
    got = nd.refine_axes(field, xi, rs, ds, geom, interpret=True,
                         block_families=block)
    np.testing.assert_allclose(np.asarray(got), np.asarray(base), rtol=1e-6)


def test_icr_nd_use_pallas_end_to_end():
    """ICR(use_pallas=True) routes every dust-chart level through the fused
    path (no reference fallback) and produces a finite, correlated field."""
    c = galactic_dust_chart((6, 8, 8), n_levels=2)
    icr = ICR(chart=c, kernel=matern32.with_defaults(rho=0.5),
              use_pallas=True)
    for entry in dispatch.plan(c):
        assert entry["route"] != dispatch.ROUTE_REFERENCE, entry
    # the fused path skips the joint O(n_csz^3d) matrix build entirely
    mats = icr.matrices()
    assert "Rax" in mats and "R" not in mats
    assert "R" in icr.matrices(joint=True)
    s = icr.sample(jax.random.PRNGKey(0))
    assert s.shape == c.final_shape
    assert bool(jnp.isfinite(s).all())
    # the separable fast path is an approximation of the joint model; it
    # must stay within a few percent on this chart (non-separable Matérn)
    icr_ref = ICR(chart=c, kernel=matern32.with_defaults(rho=0.5))
    xi = icr.init_xi(jax.random.PRNGKey(0))
    sj = icr_ref.apply_sqrt(icr_ref.matrices(), xi)
    rel = float(jnp.linalg.norm(s - sj) / jnp.linalg.norm(sj))
    assert rel < 0.15, rel


# -- dispatch layer ------------------------------------------------------------
class TestDispatch:
    def test_routes(self):
        geom_1d_st = LevelGeom.for_level(regular_chart(32, 1), 0)
        assert dispatch.route_for(geom_1d_st) == dispatch.ROUTE_STATIONARY_1D
        geom_1d_ch = LevelGeom.for_level(log_chart(32, 1, delta0=0.05), 0)
        assert dispatch.route_for(geom_1d_ch) == dispatch.ROUTE_CHARTED_1D
        geom_2d = LevelGeom.for_level(regular_chart((8, 8), 1), 0)
        assert dispatch.route_for(geom_2d) == dispatch.ROUTE_REFERENCE
        # small N-D level: the single-launch megakernel fits VMEM
        assert (dispatch.route_for(geom_2d, have_axis_mats=True)
                == dispatch.ROUTE_ND_FUSED)
        # when the joint tile + halos bust the budget, fall back to the
        # per-axis passes (the DESIGN.md §10 fallback rule)
        assert dispatch.autotune_nd_fused(geom_2d, vmem_budget=64) is None

    def test_autotune_monotone_and_bounded(self):
        small = dispatch.autotune_block_families(10**6, 5, 4, charted=True)
        big = dispatch.autotune_block_families(10**6, 5, 4, charted=False)
        assert big >= small >= 8
        # charted per-family matrices dominate the working set at large b_f:
        # the block must fit the VMEM budget
        s, fsz, csz = 2, 4, 5
        per = (2 * small * s + 2 * small * fsz + fsz * csz + fsz * fsz
               + small * (fsz * csz + fsz * fsz))
        assert 2 * 4 * per <= dispatch.VMEM_BUDGET_BYTES

    def test_autotune_clamps_to_tiny_family_counts(self):
        """Regression: levels with t < 8 used to get the floor-8 block (pure
        padding); the block is now clamped to the family count."""
        assert dispatch.autotune_block_families(5, 5, 4, charted=False) == 5
        for t in range(1, 8):
            b = dispatch.autotune_block_families(t, 5, 4, charted=True)
            # one block over the whole level: its plane carries the window
            # overhang itself, no halo block is needed
            assert b == t
        # a big window over a tiny level (q_max = 4 > t): still one block,
        # and the kernel must stay correct
        rng = np.random.default_rng(5)
        ncsz, nfsz, t = 5, 2, 3
        b = dispatch.autotune_block_families(t, ncsz, nfsz, charted=False)
        assert b == t
        coarse = _rand(rng, (1, R.coarse_len(t, ncsz, nfsz)), jnp.float32)
        xi = _rand(rng, (1, t, nfsz), jnp.float32)
        r = _rand(rng, (nfsz, ncsz), jnp.float32)
        d = _rand(rng, (nfsz, nfsz), jnp.float32)
        got = refine_stationary_pallas(coarse, xi, r, d, n_csz=ncsz,
                                       n_fsz=nfsz, block_families=b,
                                       interpret=True)
        np.testing.assert_allclose(
            np.asarray(got),
            np.asarray(R.refine_stationary_ref(coarse, xi, r, d)),
            rtol=1e-5, atol=1e-5,
        )

    def test_plan_dust_chart(self):
        c = galactic_dust_chart((6, 8, 8), n_levels=2)
        # default plan: both tiny levels ride the VMEM-resident pyramid
        assert [e["route"] for e in dispatch.plan(c, platform="cpu")] \
            == [dispatch.ROUTE_PYRAMID] * 2
        # per-level view (pyramid off): the §10 megakernel everywhere
        plan = dispatch.plan(c, platform="cpu", pyramid=False)
        assert [e["route"] for e in plan] == [dispatch.ROUTE_ND_FUSED] * 2
        # off-TPU the production executor of the fused structure is the jnp
        # oracle (interpret emulation is slower than jnp on CPU)
        assert all(e["backend"] == dispatch.BACKEND_REFERENCE for e in plan)
        plan_tpu = dispatch.plan(c, platform="tpu", pyramid=False)
        assert all(e["backend"] == dispatch.BACKEND_PALLAS for e in plan_tpu)


# -- _stationary_level regression (the lvl-ignoring bug) -----------------------
class TestStationaryLevel:
    def test_mixed_invariant_charted_chart(self):
        """A chart with one charted + invariant axes is NOT stationary."""
        icr = ICR(chart=galactic_dust_chart((6, 8, 8), n_levels=2),
                  kernel=matern32.with_defaults(rho=0.5))
        assert not any(icr._stationary_level(l) for l in range(2))

    def test_fully_invariant_chart(self):
        icr = ICR(chart=regular_chart(64, 2), kernel=matern32)
        assert all(icr._stationary_level(l) for l in range(2))

    def test_charted_level_with_single_family_is_stationary(self):
        """Per-level behavior: a charted axis collapses to one shared matrix
        at levels with family count 1 (the old code ignored `lvl`)."""
        c = log_chart(3, 2, n_csz=3, n_fsz=4, delta0=0.05)
        icr = ICR(chart=c, kernel=matern32)
        geoms = [icr._stationary_level(l) for l in range(2)]
        assert geoms == [
            all(k == 1 for k in LevelGeom.for_level(c, l).kept_T)
            for l in range(2)
        ]
        assert any(geoms)  # T==1 levels exist on this tiny chart

    def test_charted_1d_use_pallas_end_to_end(self):
        """Charted levels now reach the charted kernel under use_pallas and
        agree with the reference (previously they silently fell back)."""
        c = log_chart(32, 2, n_csz=5, n_fsz=4, delta0=0.05)
        icr_ref = ICR(chart=c, kernel=matern32.with_defaults(rho=1.0))
        icr_pal = ICR(chart=c, kernel=matern32.with_defaults(rho=1.0),
                      use_pallas=True)
        xi = icr_ref.init_xi(jax.random.PRNGKey(2))
        mats = icr_ref.matrices()
        np.testing.assert_allclose(
            np.asarray(icr_ref.apply_sqrt(mats, xi)),
            np.asarray(icr_pal.apply_sqrt(mats, xi)),
            rtol=1e-5, atol=1e-5,
        )


# -- adjoint kernels / custom VJP (DESIGN.md §9) --------------------------------
def _vjp_all(fn, args, g):
    """All input cotangents of fn at args for output cotangent g."""
    _, vjp = jax.vjp(fn, *args)
    return vjp(g)


@pytest.mark.parametrize("ncsz,nfsz", PARAMS)
@pytest.mark.parametrize("t", [7, 64, 300])
def test_stationary_vjp_matches_ref(ncsz, nfsz, t):
    """jax.vjp of the fused kernel == jax.vjp of the jnp reference, all four
    cotangents (coarse / xi / R / sqrtD), pinned at 1e-5."""
    rng = np.random.default_rng(ncsz * 100 + nfsz + t)
    batch = 2
    coarse = _rand(rng, (batch, R.coarse_len(t, ncsz, nfsz)), jnp.float32)
    xi = _rand(rng, (batch, t, nfsz), jnp.float32)
    r = _rand(rng, (nfsz, ncsz), jnp.float32)
    d = _rand(rng, (nfsz, nfsz), jnp.float32)
    g = _rand(rng, (batch, t * nfsz), jnp.float32)
    want = _vjp_all(R.refine_stationary_ref, (coarse, xi, r, d), g)
    got = _vjp_all(
        lambda c, x, rr, dd: refine_stationary_pallas(
            c, x, rr, dd, n_csz=ncsz, n_fsz=nfsz, block_families=32,
            interpret=True),
        (coarse, xi, r, d), g)
    # the hand-derived oracle must agree with autodiff of the reference too
    oracle = R.refine_stationary_vjp_ref(coarse, xi, r, d, g)
    for name, a, b, o in zip("coarse xi r d".split(), want, got, oracle):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                   rtol=1e-4, atol=1e-5, err_msg=f"d{name}")
        np.testing.assert_allclose(np.asarray(o), np.asarray(a),
                                   rtol=1e-4, atol=1e-5, err_msg=f"d{name}")


@pytest.mark.parametrize("ncsz,nfsz", PARAMS)
@pytest.mark.parametrize("t", [9, 128])
def test_charted_vjp_matches_ref(ncsz, nfsz, t):
    rng = np.random.default_rng(ncsz * 10 + nfsz + t)
    coarse = _rand(rng, (2, R.coarse_len(t, ncsz, nfsz)), jnp.float32)
    xi = _rand(rng, (2, t, nfsz), jnp.float32)
    r = _rand(rng, (t, nfsz, ncsz), jnp.float32)
    d = _rand(rng, (t, nfsz, nfsz), jnp.float32)
    g = _rand(rng, (2, t * nfsz), jnp.float32)
    want = _vjp_all(R.refine_charted_ref, (coarse, xi, r, d), g)
    got = _vjp_all(
        lambda c, x, rr, dd: refine_charted_pallas(
            c, x, rr, dd, n_csz=ncsz, n_fsz=nfsz, block_families=32,
            interpret=True),
        (coarse, xi, r, d), g)
    oracle = R.refine_charted_vjp_ref(coarse, xi, r, d, g)
    for name, a, b, o in zip("coarse xi r d".split(), want, got, oracle):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                   rtol=1e-4, atol=1e-5, err_msg=f"d{name}")
        np.testing.assert_allclose(np.asarray(o), np.asarray(a),
                                   rtol=1e-4, atol=1e-5, err_msg=f"d{name}")


@pytest.mark.parametrize("block", [4, 16, 1024])
def test_adjoint_block_size_invariance(block):
    """The backward must not depend on the VMEM tile size either."""
    rng = np.random.default_rng(11)
    ncsz, nfsz, t = 5, 4, 200
    coarse = _rand(rng, (1, R.coarse_len(t, ncsz, nfsz)), jnp.float32)
    xi = _rand(rng, (1, t, nfsz), jnp.float32)
    r = _rand(rng, (nfsz, ncsz), jnp.float32)
    d = _rand(rng, (nfsz, nfsz), jnp.float32)
    g = _rand(rng, (1, t * nfsz), jnp.float32)
    base = R.refine_stationary_vjp_ref(coarse, xi, r, d, g)
    got = _vjp_all(
        lambda c, x, rr, dd: refine_stationary_pallas(
            c, x, rr, dd, n_csz=ncsz, n_fsz=nfsz, block_families=block,
            interpret=True),
        (coarse, xi, r, d), g)
    for a, b in zip(base, got):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                   rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("chartf,name", ND_CHARTS,
                         ids=[n for _, n in ND_CHARTS])
def test_nd_axes_vjp_matches_oracle(chartf, name):
    """jax.grad through the fused N-D per-axis passes == grad of the
    independent jnp oracle, every level, both boundaries."""
    c = chartf()
    k = matern32.with_defaults(rho=3.0)()
    for lvl in range(c.n_levels):
        geom = LevelGeom.for_level(c, lvl)
        rs, ds = axis_refinement_matrices_level(c, k, lvl)
        rng = np.random.default_rng([lvl, *name.encode()])
        field = jnp.asarray(rng.normal(size=geom.coarse_shape), jnp.float32)
        f = int(np.prod(geom.T))
        xi = jnp.asarray(
            rng.normal(size=(f, geom.n_fsz ** len(geom.T))), jnp.float32)
        v = jnp.asarray(rng.normal(size=geom.fine_shape), jnp.float32)
        loss_pal = lambda fl, x: jnp.sum(
            nd.refine_axes(fl, x, rs, ds, geom, interpret=True) * v)
        loss_ref = lambda fl, x: jnp.sum(
            R.refine_axes_ref(fl, x, rs, ds, T=geom.T, n_fsz=geom.n_fsz,
                              boundary=geom.boundary, b=geom.b) * v)
        got = jax.grad(loss_pal, argnums=(0, 1))(field, xi)
        want = jax.grad(loss_ref, argnums=(0, 1))(field, xi)
        for a, b in zip(want, got):
            np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                       rtol=1e-5, atol=1e-5)


class TestICRGradParity:
    """Acceptance: jax.grad through ICR(use_pallas=True).apply_sqrt matches
    the reference-path gradient on 1-D/2-D/3-D charts."""

    def _parity(self, icr_ref, icr_pal, mats, key, tol=1e-5):
        xi = icr_ref.init_xi(key)
        g_ref = jax.grad(
            lambda xs: 0.5 * jnp.sum(icr_ref.apply_sqrt(mats, xs) ** 2))(xi)
        g_pal = jax.grad(
            lambda xs: 0.5 * jnp.sum(icr_pal.apply_sqrt(mats, xs) ** 2))(xi)
        for a, b in zip(g_ref, g_pal):
            np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                       rtol=tol, atol=tol)

    @pytest.mark.parametrize("boundary", ["shrink", "reflect"])
    def test_1d_stationary(self, boundary):
        c = regular_chart(64, 2, boundary=boundary)
        kern = matern32.with_defaults(rho=8.0)
        icr_ref = ICR(chart=c, kernel=kern)
        icr_pal = ICR(chart=c, kernel=kern, use_pallas=True)
        self._parity(icr_ref, icr_pal, icr_ref.matrices(),
                     jax.random.PRNGKey(0))

    def test_1d_charted(self):
        c = log_chart(32, 2, n_csz=5, n_fsz=4, delta0=0.05)
        kern = matern32.with_defaults(rho=1.0)
        icr_ref = ICR(chart=c, kernel=kern)
        icr_pal = ICR(chart=c, kernel=kern, use_pallas=True)
        self._parity(icr_ref, icr_pal, icr_ref.matrices(),
                     jax.random.PRNGKey(1))

    def test_theta_gradient_through_matrices(self):
        """Learned-θ path: matrices are perturbed, so the VJP also carries
        the (R, sqrtD) cotangents — fused must match reference."""
        c = regular_chart(32, 2, boundary="reflect")
        kern = matern32.with_defaults(rho=8.0)
        icr_ref = ICR(chart=c, kernel=kern)
        icr_pal = ICR(chart=c, kernel=kern, use_pallas=True)
        xi = icr_ref.init_xi(jax.random.PRNGKey(3))
        theta = lambda lr: {"rho": jnp.exp(lr), "sigma": 1.0}
        g_ref = jax.grad(
            lambda lr: 0.5 * jnp.sum(icr_ref(xi, theta(lr)) ** 2))(
                jnp.asarray(2.0))
        g_pal = jax.grad(
            lambda lr: 0.5 * jnp.sum(icr_pal(xi, theta(lr)) ** 2))(
                jnp.asarray(2.0))
        np.testing.assert_allclose(float(g_pal), float(g_ref), rtol=1e-4)


class TestApplySqrtT:
    def test_adjoint_identity_3d_fused(self):
        """<sqrt(K) ξ, v> == <ξ, sqrt(K)ᵀ v> through the fused adjoints."""
        c = galactic_dust_chart((6, 8, 8), n_levels=2)
        icr = ICR(chart=c, kernel=matern32.with_defaults(rho=0.5),
                  use_pallas=True)
        mats = icr.matrices()
        xi = icr.init_xi(jax.random.PRNGKey(1))
        v = jax.random.normal(jax.random.PRNGKey(2), icr.out_shape)
        lhs = float(jnp.vdot(icr.apply_sqrt(mats, xi), v))
        back = icr.apply_sqrt_T(mats, v)
        assert [b.shape for b in back] == [tuple(s) for s in icr.xi_shapes()]
        rhs = float(sum(jnp.vdot(a, b) for a, b in zip(xi, back)))
        np.testing.assert_allclose(rhs, lhs, rtol=1e-4)

    def test_matches_reference_transpose_1d(self):
        """Fused apply_sqrt_T == reference apply_sqrt_T == per-level
        refine_level_T chain."""
        c = regular_chart(64, 2, boundary="reflect")
        kern = matern32.with_defaults(rho=8.0)
        icr_ref = ICR(chart=c, kernel=kern)
        icr_pal = ICR(chart=c, kernel=kern, use_pallas=True)
        mats = icr_ref.matrices()
        v = jax.random.normal(jax.random.PRNGKey(4), icr_ref.out_shape)
        want = icr_ref.apply_sqrt_T(mats, v)
        got = icr_pal.apply_sqrt_T(mats, v)
        for a, b in zip(want, got):
            np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                       rtol=1e-5, atol=1e-5)
        # hand-walk the levels in reverse with refine_level_T
        cot = v
        manual = []
        for lvl in reversed(range(c.n_levels)):
            geom = LevelGeom.for_level(c, lvl)
            cot, dxi = refine_level_T(cot, mats["R"][lvl],
                                      mats["sqrtD"][lvl], geom)
            manual.append(dxi)
        manual.append(mats["sqrt0"].T @ cot.reshape(-1))
        for a, b in zip(want, reversed(manual)):
            np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                       rtol=1e-5, atol=1e-5)

    def test_plan_reports_fused_vjp(self):
        c = galactic_dust_chart((6, 8, 8), n_levels=2)
        for entry in dispatch.plan(c, platform="cpu", pyramid=False):
            assert entry["vjp"]["route"] == dispatch.ROUTE_ND_FUSED + "-adjoint"
            assert entry["vjp"]["backend"] == entry["backend"]
            assert entry["vjp"]["block_families"] == entry["block_families"]


# -- interleave: the dense and scatter forms and the shape rule ----------------
def _interleave_ref(planes, length):
    """Strided-slice assignment in numpy: the permutation with no scatter."""
    p = np.asarray(planes)
    f = p.shape[0]
    out = np.empty(p.shape[1:-1] + (p.shape[-1] * f,), p.dtype)
    for i in range(f):
        out[..., i::f] = p[i]
    return out[..., :length]


# (f, rows, n, length, form the rule picks, poisoned row)
INTERLEAVE_CASES = [
    (4, 8, 4096, None, "dense", None),     # long lines: the 1-D routes
    (2, 8, 4096, None, "dense", None),     # the adjoint's s planes
    (4, 8, 1500, None, "dense", None),     # padded up to 12 lane groups
    (2, 8, 1500, 2995, "dense", None),     # cropped to length
    (4, 4096, 64, None, "scatter", None),  # many rows: N-D per-axis passes
    (2, 4096, 66, 130, "scatter", None),
    (2, 2048, 1024, None, "dense", None),  # many rows, long lines
    (4, 8, 4096, None, "dense", 3),        # one row holds a NaN
]


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("f,rows,n,length,form,nan_row", INTERLEAVE_CASES)
def test_interleave_matches_strided_reference(f, rows, n, length, form,
                                              nan_row, dtype):
    length = length or n * f
    assert interleave_form(f, n) == form
    rng = np.random.default_rng(f * rows + n)
    # normal magnitudes over many binades: the dense form flushes
    # subnormals and turns -0.0 into +0.0
    x = rng.normal(size=(f, rows, n)) * np.exp(
        rng.uniform(-20, 20, size=(f, rows, n)))
    planes = jnp.asarray(x, jnp.float32).astype(dtype)
    want = _interleave_ref(planes.astype(jnp.float32), length)
    if nan_row is not None:
        planes = planes.at[1, nan_row, n // 2].set(jnp.nan)
    got = jax.jit(interleave, static_argnums=1)(planes, length)
    assert got.dtype == dtype and got.shape == (rows, length)
    got = np.asarray(got.astype(jnp.float32))
    keep = [r for r in range(rows) if r != nan_row]
    assert np.array_equal(got[keep], want[keep])
    if nan_row is not None:
        assert np.isnan(got[nan_row, (n // 2) * f + 1])


def test_launch_plans_record_interleave_form():
    """The 1-D served levels of the log chart go dense; the dust chart's
    N-D per-axis level records what the rule chose for its planes."""
    log = log_chart(1024, 11, n_csz=5, n_fsz=4, delta0=0.02,
                    boundary="reflect")
    groups = [g for g in dispatch.chart_launch_plans(log, samples=8)
              if g["route"] == dispatch.ROUTE_CHARTED_1D]
    assert [g["level"] for g in groups] == list(range(5, 11))
    for g in groups:  # the pyramid covers levels 0-4
        plans = dispatch.level_launch_plans(g["geom"], g["route"], samples=8)
        assert [p.params["interleave"] for p in plans] == ["dense", "dense"]

    dust = galactic_dust_chart((8, 64, 8), 5)
    geom = LevelGeom.for_level(dust, dust.n_levels - 1)
    plans = dispatch.level_launch_plans(geom, dispatch.ROUTE_AXES_ND,
                                        samples=4)
    for p in plans:
        prm = p.params
        f = prm["n_fsz"] if prm["kind"] == "fwd" else prm["s"]
        n = p.outputs[0].array_shape[2]
        assert prm["interleave"] == interleave_form(f, n)
    assert [p.params["interleave"] for p in plans] == ["scatter"] * 6
