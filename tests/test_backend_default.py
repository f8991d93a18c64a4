"""Production backend selection off-TPU (ISSUE 5 satellite).

Pallas interpret mode is slower than plain jnp on CPU, so the runtime
default off-TPU is the jnp reference/oracle backend; REPRO_BACKEND
overrides the runtime decision only (an explicit platform= stays a pure
what-would-run-there question). The oracle executes the SAME factored
structure the kernels run — parity pinned against the interpret backend
here, forward and gradient.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import ICR, matern32, regular_chart
from repro.core.charts import galactic_dust_chart
from repro.core.refine import LevelGeom, axis_refinement_matrices_level
from repro.kernels import dispatch


def test_select_backend_default_and_override(monkeypatch):
    monkeypatch.delenv("REPRO_BACKEND", raising=False)
    assert dispatch.select_backend() == dispatch.BACKEND_REFERENCE  # CPU CI
    assert dispatch.select_backend(platform="cpu") \
        == dispatch.BACKEND_REFERENCE
    assert dispatch.select_backend(platform="tpu") == dispatch.BACKEND_PALLAS
    monkeypatch.setenv("REPRO_BACKEND", "interpret")
    assert dispatch.select_backend() == dispatch.BACKEND_INTERPRET
    # explicit platform is introspection — the override must not leak in
    assert dispatch.select_backend(platform="tpu") == dispatch.BACKEND_PALLAS
    monkeypatch.setenv("REPRO_BACKEND", "bogus")
    with pytest.raises(ValueError):
        dispatch.select_backend()


def test_tpu_runs_pallas_or_fails(monkeypatch):
    """On a TPU the compiled kernels are the only backend: an override
    asking for another one is an error, not a silent fallback; a level
    without a kernel route fails on the pallas backend; any other
    platform has no backend at all."""
    monkeypatch.setattr(dispatch.jax, "default_backend", lambda: "tpu")
    monkeypatch.delenv("REPRO_BACKEND", raising=False)
    assert dispatch.select_backend() == dispatch.BACKEND_PALLAS
    monkeypatch.setenv("REPRO_BACKEND", "pallas")
    assert dispatch.select_backend() == dispatch.BACKEND_PALLAS
    for other in ("interpret", "reference"):
        monkeypatch.setenv("REPRO_BACKEND", other)
        with pytest.raises(RuntimeError, match="TPU"):
            dispatch.select_backend()
    with pytest.raises(RuntimeError, match="no kernel backend"):
        dispatch.select_backend(platform="gpu")
    c = regular_chart((8, 8), 1)
    geom = LevelGeom.for_level(c, 0)
    assert dispatch.route_for(geom) == dispatch.ROUTE_REFERENCE
    with pytest.raises(ValueError, match="no kernel route"):
        dispatch.refine(jnp.zeros(geom.coarse_shape), None, None, None,
                        geom, backend=dispatch.BACKEND_PALLAS)


def test_refine_oracle_branch_matches_interpret():
    """dispatch.refine on a factored N-D level with backend=reference (no
    joint matrices!) must equal the interpret megakernel at 1e-5 — the
    branch that used to raise ValueError."""
    c = regular_chart((12, 16), 1, boundary="reflect")
    geom = LevelGeom.for_level(c, 0)
    rs, ds = axis_refinement_matrices_level(
        c, matern32.with_defaults(rho=4.0)(), 0)
    rng = np.random.default_rng(0)
    field = jnp.asarray(rng.normal(size=geom.coarse_shape), jnp.float32)
    xi = jnp.asarray(rng.normal(size=(int(np.prod(geom.T)),
                                      geom.n_fsz**2)), jnp.float32)
    ref = dispatch.refine(field, xi, None, None, geom, axis_mats=(rs, ds),
                          backend=dispatch.BACKEND_REFERENCE)
    itp = dispatch.refine(field, xi, None, None, geom, axis_mats=(rs, ds),
                          backend=dispatch.BACKEND_INTERPRET)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(itp),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("use_pyramid", [True, False],
                         ids=["pyramid", "per-level"])
def test_default_backend_apply_and_grad_parity(monkeypatch, use_pyramid):
    """ICR(use_pallas=True) under the production CPU default (reference):
    forward and gradient match the interpret-backend run at 1e-5 on the 3-D
    chart — with the pyramid (one jnp jit region) and without (per-level
    oracle, the refine() branch jax.grad differentiates directly)."""
    c = galactic_dust_chart((6, 8, 8), n_levels=2)
    icr = ICR(chart=c, kernel=matern32.with_defaults(rho=0.5),
              use_pallas=True, use_pyramid=use_pyramid)
    mats = icr.matrices()
    xi = icr.init_xi(jax.random.PRNGKey(0))
    loss = lambda xs: 0.5 * jnp.sum(icr.apply_sqrt(mats, xs) ** 2)

    monkeypatch.delenv("REPRO_BACKEND", raising=False)
    out_ref = icr.apply_sqrt(mats, xi)
    g_ref = jax.grad(loss)(xi)
    monkeypatch.setenv("REPRO_BACKEND", "interpret")
    out_itp = icr.apply_sqrt(mats, xi)
    g_itp = jax.grad(loss)(xi)

    np.testing.assert_allclose(np.asarray(out_ref), np.asarray(out_itp),
                               rtol=1e-5, atol=1e-5)
    for a, b in zip(g_ref, g_itp):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-4)
