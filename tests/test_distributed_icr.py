"""Distributed (shard_map + halo exchange) ICR == single-device ICR.

The multi-device checks run in a subprocess because
``--xla_force_host_platform_device_count`` must be set before jax
initializes, and the rest of the suite requires the real 1-device view.
"""
import os
import subprocess
import sys

import numpy as np
import pytest

import jax.numpy as jnp

from repro.core import ICR, matern32, regular_chart
from repro.core.distributed import DistributedICR
from repro.compat import use_mesh
from repro.launch.mesh import make_mesh

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.mark.slow
def test_sharded_equals_unsharded_8dev():
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run(
        [sys.executable, "-m", "repro.launch._dist_icr_check"],
        env=env, capture_output=True, text=True, timeout=600,
    )
    assert out.returncode == 0, out.stdout + out.stderr
    assert out.stdout.count("max_abs_diff") >= 4, out.stdout


def test_single_device_mesh_roundtrip(key):
    """DistributedICR on a trivial 1-device ring reduces to plain ICR."""
    icr = ICR(chart=regular_chart(32, 3, boundary="reflect"),
              kernel=matern32.with_defaults(rho=10.0))
    mesh = make_mesh((1,), ("space",))
    dist = DistributedICR(icr=icr, mesh=mesh, axis_names=("space",))
    with use_mesh(mesh):
        xi = dist.init_xi(key)
        mats = dist.matrices()
        sharded = dist.apply_sqrt(mats, xi)
    xi_flat = [xi[0]] + [x.reshape(-1, icr.chart.n_fsz) for x in xi[1:]]
    ref = icr.apply_sqrt(icr.matrices(), xi_flat)
    np.testing.assert_allclose(np.asarray(sharded), np.asarray(ref),
                               rtol=1e-6, atol=1e-6)


def test_single_device_mesh_pallas_interior(key):
    """ISSUE 4 satellite: the sharded body's interior compute goes through
    dispatch.refine when the wrapped ICR has use_pallas=True — same values
    as the jnp reference interior on the same ring."""
    kern = matern32.with_defaults(rho=10.0)
    chart = regular_chart(32, 3, boundary="reflect")
    mesh = make_mesh((1,), ("space",))
    outs = {}
    for pallas in (False, True):
        icr = ICR(chart=chart, kernel=kern, use_pallas=pallas)
        dist = DistributedICR(icr=icr, mesh=mesh, axis_names=("space",))
        with use_mesh(mesh):
            xi = dist.init_xi(key)
            mats = dist.matrices()
            outs[pallas] = np.asarray(dist.apply_sqrt(mats, xi))
    np.testing.assert_allclose(outs[True], outs[False], rtol=1e-5,
                               atol=1e-5)


def test_single_device_mesh_bf16_policy(key):
    """The dtype policy threads through the distributed interior: bf16
    sharded output matches the fp32 sharded reference at the dtype-scaled
    bar — on the fused 1-D route AND the N-D joint-reference route (which
    must upcast to the accum dtype, not run bf16 math)."""
    from repro.core.charts import galactic_dust_chart

    mesh = make_mesh((1,), ("space",))
    cases = [
        (regular_chart(32, 3, boundary="reflect"), 10.0, 0),
        (galactic_dust_chart((6, 8, 8), n_levels=2), 0.5, 1),
    ]
    for chart, rho, shard_axis in cases:
        kern = matern32.with_defaults(rho=rho)
        d32 = DistributedICR(icr=ICR(chart=chart, kernel=kern,
                                     use_pallas=True),
                             mesh=mesh, shard_axis=shard_axis)
        d16 = DistributedICR(icr=ICR(chart=chart, kernel=kern,
                                     use_pallas=True, dtype_policy="bf16"),
                             mesh=mesh, shard_axis=shard_axis)
        with use_mesh(mesh):
            xi = d32.init_xi(key)
            out32 = np.asarray(d32.apply_sqrt(d32.matrices(), xi))
            xi16 = [x.astype(jnp.bfloat16) for x in xi]
            out16 = d16.apply_sqrt(d16.matrices(), xi16)
        assert out16.dtype == jnp.bfloat16
        scale = max(float(np.abs(out32).max()), 1e-30)
        rel = float(np.abs(np.asarray(out16, np.float32)
                           - out32).max()) / scale
        assert rel <= 5e-2, (chart.ndim, rel)


def test_requires_reflect_boundary():
    icr = ICR(chart=regular_chart(32, 2, boundary="shrink"),
              kernel=matern32)
    mesh = make_mesh((1,), ("space",))
    with pytest.raises(ValueError, match="reflect"):
        DistributedICR(icr=icr, mesh=mesh)


def test_unshardable_raises():
    icr = ICR(chart=regular_chart(8, 1, boundary="reflect"),
              kernel=matern32)
    mesh = make_mesh((1,), ("space",))
    dist = DistributedICR(icr=icr, mesh=mesh)
    object.__setattr__(dist, "axis_names", ("space",))
    # fake a huge ring by monkeypatching n_dev via a tiny chart: family
    # count 4 is not divisible by 3 and block < b+1 for large rings
    big = DistributedICR(icr=icr, mesh=mesh, axis_names=("space",))
    assert big.first_sharded_level() == 0  # sanity on the real ring


def test_xi_specs_structure():
    icr = ICR(chart=regular_chart(64, 3, boundary="reflect"),
              kernel=matern32)
    mesh = make_mesh((1,), ("space",))
    dist = DistributedICR(icr=icr, mesh=mesh)
    specs = dist.xi_specs()
    shapes = dist.xi_structure()
    assert len(specs) == len(shapes) == icr.chart.n_levels + 1
    assert shapes[0] == (64,)
    assert shapes[1] == (64, 2)  # reflect: every stride-1 pixel anchors a family


def _dot_precisions(jaxpr, shape):
    """Precision params of every dot_general (recursing into sub-jaxprs)
    that contracts an operand of ``shape``."""
    from jax.extend import core as jcore

    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general" and any(
                tuple(v.aval.shape) == shape for v in eqn.invars):
            found.append(eqn.params["precision"])
        for p in eqn.params.values():
            subs = p if isinstance(p, (list, tuple)) else [p]
            for sub in subs:
                if isinstance(sub, jcore.ClosedJaxpr):
                    found += _dot_precisions(sub.jaxpr, shape)
                elif isinstance(sub, jcore.Jaxpr):
                    found += _dot_precisions(sub, shape)
    return found


@pytest.mark.parametrize("pallas", [False, True])
def test_chart_sharded_level0_at_highest_precision(key, pallas):
    """The chart-sharded program starts its field with the same
    full-precision level-0 matmul as one device (at the TPU's default
    precision it would be rounded to bf16)."""
    import jax
    from jax.lax import Precision

    icr = ICR(chart=regular_chart(32, 3, boundary="reflect"),
              kernel=matern32.with_defaults(rho=10.0), use_pallas=pallas)
    mesh = make_mesh((1,), ("space",))
    dist = DistributedICR(icr=icr, mesh=mesh, axis_names=("space",))
    with use_mesh(mesh):
        mats, xi = dist.matrices(), dist.init_xi(key)
        closed = jax.make_jaxpr(dist.apply_sqrt)(mats, xi)
    precs = _dot_precisions(closed.jaxpr, tuple(mats["sqrt0"].shape))
    assert precs, "no level-0 dot_general in the chart-sharded program"
    for prec in precs:
        assert prec is not None and all(
            p == Precision.HIGHEST for p in prec), precs
