"""Every kernel route compiles for a TPU v5e at the chip smoke's widths.

Interpret mode runs the kernel bodies as plain jnp, so it cannot tell
whether Mosaic (the TPU kernel compiler) accepts a block shape, a layout
or a primitive. These tests compile the launch plans of the widest level
of each route — forward and adjoint — for a described ``v5e:2x2`` topology
(no chip needed; the TPU compiler is installed) and require the compiled
program to hold the kernel (``tpu_custom_call``).

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and it keeps it until exit.
"""
import os

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.core import regular_chart
from repro.core.charts import galactic_dust_chart, log_chart
from repro.kernels import dispatch
from repro.kernels.launch import run_plan

SAMPLES = 4  # the smoke's slab

CHARTS = {
    "tod": lambda: regular_chart(1024, 12, boundary="reflect"),
    "log": lambda: log_chart(1024, 11, n_csz=5, n_fsz=4, delta0=0.02,
                             boundary="reflect"),
    "dust": lambda: galactic_dust_chart((8, 64, 8), 5),
    # the smoke has no 2-D chart: the serving scenarios' image chart
    "image": lambda: regular_chart((32, 32), 2, boundary="reflect"),
}
CASES = [
    ("stationary-1d", "tod"),
    ("charted-1d", "log"),
    ("nd-fused", "dust"),
    ("nd-axes", "dust"),
    ("pyramid", "tod"),
    ("pyramid", "log"),
    ("pyramid", "image"),
    ("pyramid", "dust"),
]


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler installed
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.experimental.compilation_cache import compilation_cache as cc

    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    old = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", old)


def _widest_group(route: str, chart, dtype):
    groups = [g for g in dispatch.chart_launch_plans(
        chart, samples=SAMPLES, dtype=dtype) if g["route"] == route]
    assert groups, f"{route} is not a route of this chart"
    return groups[-1]


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("route,chart", CASES,
                         ids=[f"{r}-{c}" for r, c in CASES])
def test_route_compiles_for_v5e(route, chart, dtype, one_chip):
    grp = _widest_group(route, CHARTS[chart](), dtype)
    kinds = set()
    for plan in grp["plans"]:
        shapes = [jax.ShapeDtypeStruct(op.array_shape, jnp.dtype(op.dtype),
                                       sharding=one_chip)
                  for op in plan.inputs]
        kern = dispatch.plan_kernel(plan)
        compiled = jax.jit(
            lambda *ops, p=plan, k=kern: run_plan(k, p, ops,
                                                  interpret=False)
        ).lower(*shapes).compile()
        assert "tpu_custom_call" in compiled.as_text(), plan.kernel
        kinds.add(plan.params["kind"])
    # every route's backward launches adjoint kernels too
    assert kinds == {"fwd", "bwd"}, kinds


def test_log_level11_interleave_is_dense_for_v5e(one_chip):
    """The served log chart's last level (8 rows, 524,288 families):
    the forward launch carries no scatter, and its interleave needs no
    temporary beyond its output (a stack-and-reshape pads the 4-wide
    minor dimension to 128 lanes: 1 GiB)."""
    from repro.kernels.icr_refine import interleave, refine_charted_pallas

    (grp,) = [g for g in dispatch.chart_launch_plans(
        CHARTS["log"](), samples=8) if g["level"] == 10]
    plan = grp["plans"][0]
    p = plan.params
    assert (p["kind"], p["batch"], p["t"], p["interleave"]) == (
        "fwd", 8, 524288, "dense")
    t, b, csz, fsz = p["t"], p["batch"], p["n_csz"], p["n_fsz"]

    def sds(shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)

    launch = jax.jit(lambda *a: refine_charted_pallas(
        *a, n_csz=csz, n_fsz=fsz, block_families=p["b_f"],
        batch_block=p["b_b"])).lower(
        sds((b, p["coarse_len"])), sds((b, t, fsz)), sds((t, fsz, csz)),
        sds((t, fsz, fsz))).compile()
    text = launch.as_text()
    assert "tpu_custom_call" in text
    assert "scatter" not in text

    planes = sds((fsz, b, plan.operand("fine").array_shape[2]))
    mem = jax.jit(lambda x: interleave(x, t * fsz)).lower(
        planes).compile().memory_analysis()
    assert mem.temp_size_in_bytes <= mem.output_size_in_bytes
