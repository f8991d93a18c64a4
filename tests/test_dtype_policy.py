"""Mixed-precision dtype policy (ISSUE 4 / DESIGN.md §11).

bf16-storage + fp32-accum must agree with the fp32 reference at
dtype-scaled tolerances on EVERY route — 1-D stationary/charted, the N-D
per-axis passes, the megakernel, the pyramid — forward and VJP, and the
byte accounting must scale exactly with the storage itemsize.

Tolerance note: bf16 has ~8 mantissa bits (eps ~ 7.8e-3); a multi-level
refinement chain rounds the field to bf16 once per level, so relative
errors of a few eps are expected and 5e-2 is the dtype-scaled bar
(the fp32 suites pin 1e-5 — that bar is untouched).
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import ICR, matern32, regular_chart
from repro.core.charts import galactic_dust_chart, log_chart
from repro.core.refine import LevelGeom, axis_refinement_matrices_level
from repro.kernels import dispatch, nd
from repro.kernels.policy import BF16, FP32, DtypePolicy, resolve
from repro.roofline import refine_level_traffic

BF16_TOL = 5e-2


# this module covers the kernel tiling: pin the interpret backend through
# dispatch/ICR (the production CPU default is the jnp oracle)
pytestmark = pytest.mark.usefixtures("interpret_backend")


def _rel_close(got_bf16, want_f32, tol=BF16_TOL):
    got = np.asarray(got_bf16, np.float32)
    want = np.asarray(want_f32, np.float32)
    scale = max(float(np.abs(want).max()), 1e-30)
    rel = float(np.abs(got - want).max()) / scale
    assert rel <= tol, rel


# -- the policy object ----------------------------------------------------------
class TestPolicyObject:
    def test_default_is_bf16_storage_f32_accum(self):
        pol = DtypePolicy()
        assert jnp.dtype(pol.storage_dtype) == jnp.bfloat16
        assert jnp.dtype(pol.accum_dtype) == jnp.float32
        assert pol.storage_itemsize == 2

    def test_fp32_opt_out_and_aliases(self):
        assert resolve("fp32") == FP32
        assert resolve("float32") == FP32
        assert resolve("bf16") == BF16
        assert resolve("mixed") == BF16
        assert resolve(None) == FP32  # back-compat: no policy == fp32
        assert resolve(BF16) is BF16
        with pytest.raises(ValueError, match="unknown dtype policy"):
            resolve("fp8")

    def test_cast_storage_passes_none_leaves(self):
        tree = {"a": jnp.ones(3, jnp.float32), "b": None}
        out = BF16.cast_storage(tree)
        assert out["a"].dtype == jnp.bfloat16 and out["b"] is None


# -- forward + VJP parity, every route ------------------------------------------
CASES = [
    ("stationary-1d", lambda: regular_chart(64, 3, boundary="reflect"),
     10.0, {}),
    ("charted-1d", lambda: log_chart(32, 3, n_csz=5, n_fsz=4, delta0=0.05),
     1.0, {}),
    ("pyramid", lambda: galactic_dust_chart((6, 8, 8), n_levels=2),
     0.5, {}),
    ("nd-fused", lambda: galactic_dust_chart((6, 8, 8), n_levels=2),
     0.5, {"use_pyramid": False}),
]
IDS = [c[0] for c in CASES]


def _models(chartf, rho, extra):
    kern = matern32.with_defaults(rho=rho)
    f32 = ICR(chart=chartf(), kernel=kern, use_pallas=True, **extra)
    b16 = ICR(chart=chartf(), kernel=kern, use_pallas=True,
              dtype_policy="bf16", **extra)
    return f32, b16


@pytest.mark.parametrize("name,chartf,rho,extra", CASES, ids=IDS)
def test_forward_parity(name, chartf, rho, extra):
    f32, b16 = _models(chartf, rho, extra)
    xi = f32.init_xi(jax.random.PRNGKey(0))
    out32 = f32.apply_sqrt(f32.matrices(), xi)
    mats16 = b16.matrices()
    out16 = b16.apply_sqrt(mats16, [x.astype(jnp.bfloat16) for x in xi])
    assert out16.dtype == jnp.bfloat16
    _rel_close(out16, out32)


@pytest.mark.parametrize("name,chartf,rho,extra", CASES, ids=IDS)
def test_vjp_parity(name, chartf, rho, extra):
    """jax.grad of the §3.2-style quadratic loss through each route: the
    bf16 adjoint chain tracks the fp32 one at the dtype-scaled bar."""
    f32, b16 = _models(chartf, rho, extra)
    xi32 = f32.init_xi(jax.random.PRNGKey(1))
    mats32, mats16 = f32.matrices(), b16.matrices()
    xi16 = [x.astype(jnp.bfloat16) for x in xi32]

    def loss(icr, mats, xs):
        s = icr.apply_sqrt(mats, xs).astype(jnp.float32)
        return 0.5 * jnp.sum(s * s)

    g32 = jax.grad(lambda xs: loss(f32, mats32, xs))(xi32)
    g16 = jax.grad(lambda xs: loss(b16, mats16, xs))(xi16)
    for a16, a32 in zip(g16, g32):
        assert a16.dtype == jnp.bfloat16
        _rel_close(a16, a32)


def test_nd_axes_route_parity():
    """The per-axis fallback route, bf16 vs fp32, forward + VJP (driven at
    the kernel layer: the dust chart prefers the megakernel, so the route
    is exercised directly)."""
    c = galactic_dust_chart((6, 8, 8), n_levels=2)
    k = matern32.with_defaults(rho=0.5)()
    geom = LevelGeom.for_level(c, 1)
    rs, ds = axis_refinement_matrices_level(c, k, 1)
    rng = np.random.default_rng(3)
    field = jnp.asarray(rng.normal(size=geom.coarse_shape), jnp.float32)
    xi = jnp.asarray(
        rng.normal(size=(int(np.prod(geom.T)), geom.n_fsz**3)), jnp.float32)
    bf = lambda t: jax.tree.map(lambda a: a.astype(jnp.bfloat16), t)

    out32 = nd.refine_axes(field, xi, rs, ds, geom, interpret=True)
    out16 = nd.refine_axes(bf(field), bf(xi), bf(rs), bf(ds), geom,
                           interpret=True)
    assert out16.dtype == jnp.bfloat16
    _rel_close(out16, out32)

    v = jnp.asarray(rng.normal(size=geom.fine_shape), jnp.float32)
    g32 = jax.grad(lambda f, x: jnp.sum(
        nd.refine_axes(f, x, rs, ds, geom, interpret=True) * v),
        argnums=(0, 1))(field, xi)
    g16 = jax.grad(lambda f, x: jnp.sum(
        nd.refine_axes(f, x, bf(rs), bf(ds), geom, interpret=True)
        .astype(jnp.float32) * v), argnums=(0, 1))(bf(field), bf(xi))
    for a16, a32 in zip(g16, g32):
        _rel_close(a16, a32)


def test_batched_sampling_bf16():
    """apply_sqrt_batch under the mixed policy: native sample slab == the
    per-sample loop, in bf16."""
    c = galactic_dust_chart((6, 8, 8), n_levels=2)
    icr = ICR(chart=c, kernel=matern32.with_defaults(rho=0.5),
              use_pallas=True, dtype_policy="bf16")
    mats = icr.matrices()
    xi = icr.init_xi(jax.random.PRNGKey(0), batch=3)
    assert xi[1].dtype == jnp.bfloat16
    batched = icr.apply_sqrt_batch(mats, xi)
    looped = jnp.stack([
        icr.apply_sqrt(mats, [x[i] for x in xi]) for i in range(3)])
    assert batched.dtype == jnp.bfloat16
    _rel_close(batched, looped.astype(jnp.float32), tol=1e-2)


# -- byte accounting ------------------------------------------------------------
class TestDtypeBytes:
    def test_traffic_scales_exactly_with_itemsize(self):
        """Regression: every term of every route's byte model is linear in
        the storage itemsize — bf16 totals are exactly half of fp32."""
        geom = LevelGeom.for_level(galactic_dust_chart((6, 8, 8), 2), 1)
        for route in ("nd-fused", "nd-axes", "reference", "pyramid"):
            t32 = refine_level_traffic(geom, route, dtype="float32")
            t16 = refine_level_traffic(geom, route, dtype="bfloat16")
            assert t32["total"] == 2 * t16["total"], route
            assert t16["dtype"] == "bfloat16"

    def test_autotune_is_itemsize_aware(self):
        """Half the bytes per element -> at least as many families per
        VMEM tile, strictly more when the fp32 block was budget-bound.
        The model halves the storage-dtype blocks; the kernel body's
        float32 values (the v5e VPU has no bf16 arithmetic) do not."""
        kw = dict(charted=True, block_families=1024)
        m32 = dispatch.block1d_bytes(10**6, 5, 4, itemsize=4, **kw)
        m16 = dispatch.block1d_bytes(10**6, 5, 4, itemsize=2, **kw)
        assert m16 < m32
        budget = (m16 + m32) // 2  # fp32 1024-block busts it, bf16 fits
        b32 = dispatch.autotune_block_families(10**6, 5, 4, charted=True,
                                               itemsize=4,
                                               vmem_budget=budget)
        b16 = dispatch.autotune_block_families(10**6, 5, 4, charted=True,
                                               itemsize=2,
                                               vmem_budget=budget)
        assert b16 >= 2 * b32

    def test_pyramid_cover_grows_at_bf16(self):
        """A chart whose fp32 working set busts the budget can still be
        fully covered at bf16 (the §11 residency criterion is dtype-aware).
        A 16-sample slab fills the bf16 sublane tile, so the bf16 blocks
        are half the fp32 ones (the f32 body values are not)."""
        deep = regular_chart(1024, 8, boundary="reflect")
        geoms = [LevelGeom.for_level(deep, l) for l in range(8)]
        budget = 128 * 2**20  # between the bf16 (~104 MiB) and fp32
        # (~152 MiB) working sets of all eight levels at 16 samples
        w32 = dispatch.pyramid_bytes(geoms, s_b=16, itemsize=4)
        w16 = dispatch.pyramid_bytes(geoms, s_b=16, itemsize=2)
        assert w16 <= budget < w32
        # so the autotuner covers all levels with the whole slab at bf16,
        # and has to halve the slab at fp32
        k32, s32 = dispatch.autotune_pyramid(geoms, samples=16, itemsize=4,
                                             vmem_budget=budget)
        k16, s16 = dispatch.autotune_pyramid(geoms, samples=16, itemsize=2,
                                             vmem_budget=budget)
        assert (k16, s16) == (8, 16) and k32 == 8 and s32 < s16
