"""ICR core math vs. the exact GP (paper §4, validated per §5.1)."""
import math

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import (
    ICR,
    cov_errors,
    exact_cov,
    gauss_kl,
    kernel_matrix,
    log_chart,
    matern32,
    matern52,
    rbf,
    regular_chart,
)
from repro.core.refine import refinement_matrices_level


def paper_log_setup(n_csz=5, n_fsz=4, n_levels=5, target_n=200, span=50.0):
    """The paper's §5.1 experiment: ~200 log-spaced points whose
    nearest-neighbor distances span a factor `span` (2%·rho0 .. rho0)."""
    n0 = 3
    while True:
        try:
            c = log_chart(n0, n_levels, n_csz=n_csz, n_fsz=n_fsz, delta0=1.0)
            if c.final_shape[0] >= target_n:
                break
        except ValueError:
            pass
        n0 += 1
    n = c.final_shape[0]
    scale = math.log(span) / (n - 2) / c.delta(n_levels)[0]
    c = log_chart(n0, n_levels, n_csz=n_csz, n_fsz=n_fsz, delta0=scale)
    xs = np.asarray(c.grid_positions(n_levels))[:, 0]
    rho = float(np.diff(xs).max())  # max spacing = rho0
    return c, rho


class TestGeometry:
    def test_paper_size_recursion_3_2(self):
        # paper §4.2: N_{l+1} = 2 (N_l - 2) for (3, 2) shrink
        c = regular_chart(16, 3)
        assert [c.shape(l)[0] for l in range(4)] == [16, 28, 52, 100]

    def test_fine_grid_is_regular_and_consistent(self):
        # child coords produced family-wise must equal the next level's grid
        for (ncsz, nfsz) in [(3, 2), (5, 4), (5, 6), (3, 4)]:
            c = regular_chart(32, 2, n_csz=ncsz, n_fsz=nfsz)
            for lvl in range(2):
                fam = c.axis_fine_windows(lvl, 0).reshape(-1)
                grid = c.axis_coords(lvl + 1, 0)
                np.testing.assert_allclose(fam, grid, rtol=0, atol=1e-12)

    def test_reflect_boundary_doubles(self):
        c = regular_chart(32, 3, boundary="reflect")
        assert [c.shape(l)[0] for l in range(4)] == [32, 64, 128, 256]

    def test_reflect_matches_shrink_in_interior(self):
        """Interior refinement families are identical math under both
        boundary conditions — only O(b) border families differ."""
        k = matern32.with_defaults(rho=5.0)()
        cs = regular_chart(32, 1, boundary="shrink")
        cr = regular_chart(32, 1, boundary="reflect")
        rs, ds = refinement_matrices_level(cs, k, 0)
        rr, dr = refinement_matrices_level(cr, k, 0)
        # both stationary+invariant => single broadcast matrix, equal
        np.testing.assert_allclose(np.asarray(rs), np.asarray(rr), atol=1e-6)
        np.testing.assert_allclose(np.asarray(ds), np.asarray(dr), atol=1e-6)

    def test_bad_params_raise(self):
        with pytest.raises(ValueError):
            regular_chart(16, 1, n_csz=4)  # even coarse size
        with pytest.raises(ValueError):
            regular_chart(16, 1, n_fsz=3)  # odd fine size
        with pytest.raises(ValueError):
            regular_chart(3, 2)  # grid shrinks below n_csz


class TestRefinementMatrices:
    def test_matches_exact_conditional(self):
        """R and D must equal the closed-form conditional (paper Eq. 7/8)."""
        k = matern32.with_defaults(rho=3.0)()
        c = regular_chart(8, 1, n_csz=3, n_fsz=2)
        r, sqrt_d = refinement_matrices_level(c, k, 0)
        # hand-build for one family (stationary => same for all)
        cpos = c.axis_coarse_windows(0, 0)[0][:, None]
        fpos = c.axis_fine_windows(0, 0)[0][:, None]
        k_cc = kernel_matrix(k, jnp.asarray(cpos))
        k_fc = kernel_matrix(k, jnp.asarray(fpos), jnp.asarray(cpos))
        k_ff = kernel_matrix(k, jnp.asarray(fpos))
        r_ref = np.linalg.solve(np.asarray(k_cc) + 1e-6 * np.eye(3),
                                np.asarray(k_fc).T).T
        np.testing.assert_allclose(np.asarray(r)[0], r_ref, atol=1e-4)
        d_ref = np.asarray(k_ff) - r_ref @ np.asarray(k_fc).T
        d_built = np.asarray(sqrt_d)[0] @ np.asarray(sqrt_d)[0].T
        np.testing.assert_allclose(d_built, d_ref, atol=1e-4)

    def test_invariant_axis_collapses(self):
        k = matern32.with_defaults(rho=4.0)()
        c = regular_chart((16, 16), 1, boundary="reflect")
        r, sqrt_d = refinement_matrices_level(c, k, 0)
        assert r.shape[:2] == (1, 1)  # both axes invariant -> broadcast
        assert r.shape[2:] == (4, 9)  # (n_fsz^2, n_csz^2)


class TestImplicitCovariance:
    def test_regular_grid_accuracy(self):
        cov_icr, cov_true = _covs(regular_chart(16, 3), rho=8.0)
        errs = cov_errors(cov_icr, cov_true)
        assert float(errs["mae"]) < 2e-3
        assert float(errs["max_abs_err"]) < 1e-2

    def test_paper_log_chart_fig3(self):
        """Paper §5.1: (5,4), N=200, log spacing spanning 2%–100% of rho0:
        MAE 5.8e-3, max err 0.13, diag err <= 6.5e-2."""
        c, rho = paper_log_setup()
        assert c.final_shape[0] == 200
        cov_icr, cov_true = _covs(c, rho=rho)
        errs = {k: float(v) for k, v in cov_errors(cov_icr, cov_true).items()}
        assert errs["mae"] < 8e-3          # paper: 5.8e-3
        assert errs["max_abs_err"] < 0.2   # paper: 0.13
        assert errs["max_diag_err"] < 9e-2  # paper: 6.5e-2

    def test_paper_parameter_ranking(self):
        """(5,4) must beat (3,2) on the log chart (paper §5.1 KL selection)."""
        kls = {}
        for p in [(3, 2), (5, 4)]:
            c, rho = paper_log_setup(*p)
            cov_icr, cov_true = _covs(c, rho=rho)
            kls[p] = float(gauss_kl(cov_true, cov_icr, jitter=1e-8))
        assert kls[(5, 4)] < kls[(3, 2)]

    def test_2d_accuracy(self):
        c = regular_chart((6, 6), 2)
        cov_icr, cov_true = _covs(c, rho=6.0)
        errs = cov_errors(cov_icr, cov_true)
        assert float(errs["mae"]) < 5e-3

    def test_2d_reflect_accuracy(self):
        """Production (reflect/shardable) boundary: interior math identical,
        boundary families approximate => looser tolerance (DESIGN.md §5)."""
        c = regular_chart((6, 6), 2, boundary="reflect")
        cov_icr, cov_true = _covs(c, rho=6.0)
        errs = cov_errors(cov_icr, cov_true)
        assert float(errs["mae"]) < 3e-2

    @pytest.mark.parametrize("kernel", [matern32, matern52, rbf])
    def test_kernels(self, kernel):
        cov_icr, cov_true = _covs(regular_chart(12, 2), rho=6.0, kernel=kernel)
        assert float(cov_errors(cov_icr, cov_true)["mae"]) < 5e-3


def _covs(chart, rho, kernel=matern32):
    icr = ICR(chart=chart, kernel=kernel.with_defaults(rho=rho))
    cov_icr = icr.implicit_cov(dtype=jnp.float32)
    cov_true = exact_cov(chart, kernel.with_defaults(rho=rho)())
    return cov_icr, cov_true


class TestSampling:
    def test_sample_covariance_converges(self, key):
        """Empirical covariance of ICR samples ≈ implicit covariance."""
        c = regular_chart(12, 2)
        icr = ICR(chart=c, kernel=matern32.with_defaults(rho=6.0))
        mats = icr.matrices()
        n_samp = 4096
        keys = jax.random.split(key, n_samp)

        @jax.jit
        @jax.vmap
        def draw(k):
            return icr.apply_sqrt(mats, icr.init_xi(k)).reshape(-1)

        samples = draw(keys)
        emp = np.cov(np.asarray(samples).T)
        imp = np.asarray(icr.implicit_cov(dtype=jnp.float32))
        assert np.abs(emp - imp).mean() < 0.05

    def test_theta_differentiable(self):
        """Kernel parameters flow through matrices (paper: θ learned jointly)."""
        c = regular_chart(10, 1)
        icr = ICR(chart=c, kernel=matern32)

        def loss(log_rho):
            theta = {"rho": jnp.exp(log_rho), "sigma": 1.0}
            xi = icr.zero_xi()
            xi = [x + 1.0 for x in xi]
            return jnp.sum(icr(xi, theta) ** 2)

        g = jax.grad(loss)(jnp.asarray(0.5))
        assert np.isfinite(float(g)) and abs(float(g)) > 0


def test_charted_matrices_chunked_over_families(monkeypatch):
    """A 1-D charted level past ``FAMILY_CHUNK`` families builds its
    matrices chunk by chunk (bounding the padded batched linear algebra on
    the chip), identical to one batch, uneven last chunk included."""
    from repro.core import refine

    c = log_chart(32, 3, n_csz=5, n_fsz=4, delta0=0.05)
    k = matern32.with_defaults(rho=1.0)()
    lvl = 2
    whole = refinement_matrices_level(c, k, lvl)
    assert whole[0].shape[0] > 24 and whole[0].shape[0] % 24  # 3 chunks
    monkeypatch.setattr(refine, "FAMILY_CHUNK", 24)
    chunked = refinement_matrices_level(c, k, lvl)
    for a, b in zip(whole, chunked):
        assert a.shape == b.shape
        np.testing.assert_allclose(np.asarray(b), np.asarray(a), rtol=1e-6,
                                   atol=1e-7)


@pytest.mark.parametrize("boundary", ["reflect", "shrink"])
def test_family_subset_windows_match_full(boundary):
    """Windows built for a subset of families (the one representative
    family of an invariant axis) are those rows of the full windows, at
    both ends of the axis included."""
    c = regular_chart((20, 12), 3, n_csz=5, n_fsz=4, boundary=boundary)
    for lvl in range(c.n_levels):
        for a in range(c.ndim):
            t = c.family_count(lvl, a)
            fams = sorted({0, min(t - 1, c.b), t - 1})
            np.testing.assert_array_equal(
                c.axis_coarse_windows(lvl, a, fams),
                c.axis_coarse_windows(lvl, a)[fams])
            np.testing.assert_array_equal(
                c.axis_fine_windows(lvl, a, fams),
                c.axis_fine_windows(lvl, a)[fams])


def test_level0_sqrt_factored_on_host_off_cpu(monkeypatch):
    """Off the CPU a concrete level-0 matrix is factored on the host's CPU
    device and the factor comes back to the matrix's device: the same
    factor as in place, and θ-gradients (traced) still flow."""
    from repro.core import refine

    c = regular_chart(24, 1)
    k = matern32.with_defaults(rho=3.0)()
    here = refine.level0_sqrt(c, k)
    monkeypatch.setattr(refine.jax, "default_backend", lambda: "tpu")
    host = refine.level0_sqrt(c, k)
    assert host.devices() == here.devices()
    np.testing.assert_allclose(np.asarray(host), np.asarray(here),
                               rtol=1e-6, atol=1e-7)

    def loss(rho):
        kk = matern32.with_defaults(rho=rho)()
        return jnp.sum(refine.level0_sqrt(c, kk) ** 2)  # trace(K): smooth

    assert np.isfinite(float(jax.grad(loss)(3.0)))
