"""Launch-plan verifier (DESIGN.md §14): injected-regression self-tests.

The verifier is itself verified: each class of defect it exists to catch
is injected into a real exported plan (shifted index map, dropped halo
view, swapped forward/adjoint, out-of-bounds read, busted byte budget,
nonlinear forward, missing preferred_element_type) and must be caught by
the *named* pass. Clean cells must verify clean — the full 6-cell matrix
runs as ``python -m repro.analysis verify`` in the CI static-analysis
job; the fast cells are asserted clean here too.
"""
import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.analysis import kernel_verify as kv
from repro.analysis.scenarios import SCENARIOS
from repro.core import matern32
from repro.core.refine import LevelGeom
from repro.kernels import dispatch as dsp
from repro.kernels.launch import IndexMap


def scenario(label):
    return next(s for s in SCENARIOS() if s.label == label)


@pytest.fixture(scope="module")
def tod_plans():
    """Forward + adjoint 1-D plans of the tod quick chart's last level,
    tiled at one 128-family lane tile so they carry halo views."""
    scn = scenario("tod-fp32")
    geom = LevelGeom.for_level(scn.chart(), 2)
    fwd, adj = dsp.level_launch_plans(geom, samples=scn.samples,
                                     dtype="float32", block_families=128)
    return geom, fwd, adj


def passes(findings):
    return {f.pass_name for f in findings}


class TestInjectedRegressions:
    """The three canonical injections, each caught by its named pass."""

    def test_shifted_index_map_is_a_coverage_finding(self, tod_plans):
        _, fwd, _ = tod_plans
        out = fwd.outputs[0]
        ndim = len(out.block_shape)
        shifted = IndexMap("(b, i + 1)",
                           lambda i, b: (b, i + 1) + (0,) * (ndim - 2))
        doctored = dataclasses.replace(
            fwd, outputs=(dataclasses.replace(out, index_map=shifted),))
        findings = kv.check_coverage(doctored)
        assert findings, "shifted output index map went unnoticed"
        assert passes(findings) == {"coverage"}
        text = " ".join(f.message for f in findings)
        assert "never written" in text or "out-of-range" in text
        # the untouched plan is clean
        assert kv.check_coverage(fwd) == []

    def test_dropped_halo_view_is_a_halo_finding(self, tod_plans):
        _, fwd, adj = tod_plans
        for plan in (fwd, adj):
            doctored = dataclasses.replace(
                plan,
                inputs=tuple(op for op in plan.inputs if not op.halo_of))
            findings = kv.check_halo(doctored)
            assert findings, f"{plan.kernel}: dropped halo went unnoticed"
            assert passes(findings) == {"halo"}
            assert "not covered" in findings[0].message
            assert kv.check_halo(plan) == []

    def test_swapped_adjoint_is_a_transpose_finding(self):
        rng = np.random.default_rng(0)
        A = jnp.asarray(rng.normal(size=(16, 16)), jnp.float32)

        @jax.custom_vjp
        def apply(x):
            return A @ x

        # BUG under test: the backward applies A, not A.T
        apply.defvjp(lambda x: (A @ x, None), lambda _res, g: (A @ g,))

        x = jnp.asarray(rng.normal(size=(16,)), jnp.float32)
        findings = kv.transpose_dot_check(apply, (x,), rtol=1e-4)
        assert passes(findings) == {"transpose"}
        assert "not the transpose" in findings[0].message

        # the fixed pair passes
        apply.defvjp(lambda x: (A @ x, None), lambda _res, g: (A.T @ g,))
        assert kv.transpose_dot_check(apply, (x,), rtol=1e-4) == []


class TestPermanentNegatives:
    """One negative fixture per remaining pass, kept as regression guards."""

    def test_out_of_bounds_read_is_a_bounds_finding(self, tod_plans):
        _, fwd, _ = tod_plans
        op = fwd.inputs[0]
        ndim = len(op.block_shape)
        way_out = IndexMap("(b, i + 99)",
                           lambda i, b: (b, i + 99) + (0,) * (ndim - 2))
        doctored = dataclasses.replace(
            fwd, inputs=(dataclasses.replace(op, index_map=way_out),)
            + fwd.inputs[1:])
        findings = kv.check_bounds(doctored)
        assert passes(findings) == {"bounds"}
        assert "outside the padded operand extent" in findings[0].message
        assert kv.check_bounds(fwd) == []

    def test_budget_bust_is_a_bytes_finding(self):
        scn = scenario("image-fp32")
        geom = LevelGeom.for_level(scn.chart(), 0)
        plan = dsp.level_launch_plans(geom, samples=scn.samples,
                                      dtype="float32")[0]
        assert plan.kernel == "refine_nd_fused"
        findings = kv.check_bytes(plan, geom=geom, route=dsp.ROUTE_ND_FUSED,
                                  samples=scn.samples, vmem_budget=1)
        assert "bytes" in passes(findings)
        assert any("exceeds the VMEM budget" in f.message for f in findings)

    def test_model_undercount_is_a_bytes_finding(self, tod_plans):
        _, fwd, _ = tod_plans
        op = fwd.inputs[0]
        bloated = dataclasses.replace(
            op, block_shape=tuple(64 * b for b in op.block_shape))
        doctored = dataclasses.replace(fwd, inputs=(bloated,)
                                       + fwd.inputs[1:])
        findings = kv.check_bytes(doctored)
        assert any("block1d_bytes" in f.message for f in findings)

    def test_nonlinear_forward_is_caught_by_the_taint_walk(self):
        x = jnp.ones((8,), jnp.float32)
        findings = kv.check_linearity(lambda v: v * v, (x,))
        assert findings and "bilinear" in findings[0].message
        findings = kv.check_linearity(jnp.exp, (x,))
        assert findings and "not linear" in findings[0].message
        assert kv.check_linearity(lambda v: 3.0 * v + 1.0, (x,)) == []

    def test_hygiene_flags_pet_and_control_flow(self):
        x = jnp.ones((8, 8), jnp.float32)

        def bad(v):
            y = jax.lax.dot(v, v)  # no preferred_element_type
            return jax.lax.while_loop(lambda c: jnp.sum(c) < 0.0,
                                      lambda c: c + 1.0, y)

        findings = kv.check_hygiene(bad, (x,))
        text = " ".join(f.message for f in findings)
        assert "preferred_element_type" in text
        assert "control flow" in text


class TestCleanCells:
    """Exported plans of the fast cells verify clean end to end."""

    @pytest.mark.parametrize("label", ["tod-fp32", "tod-bf16"])
    def test_cell_is_clean(self, label):
        findings = kv.verify_scenario(scenario(label))
        assert findings == [], "\n".join(str(f) for f in findings)


class TestAxesRoute:
    """The per-axis N-D route has no quick-chart cell; verify it
    explicitly so its plans and custom VJP stay covered."""

    def test_axes_nd_group_verifies_clean(self):
        scn = scenario("image-fp32")
        chart = scn.chart()
        geom = LevelGeom.for_level(chart, 0)
        plans = dsp.level_launch_plans(geom, dsp.ROUTE_AXES_ND,
                                       samples=scn.samples,
                                       dtype="float32")
        assert len(plans) == 4  # fwd + adjoint per axis
        grp = {"level": 0, "route": dsp.ROUTE_AXES_ND, "geom": geom,
               "plans": plans}
        kernel = matern32.with_defaults(rho=scn.rho)()
        findings = kv.verify_group(grp, chart, kernel,
                                   samples=scn.samples,
                                   storage=jnp.float32,
                                   scenario=scn.label)
        assert findings == [], "\n".join(str(f) for f in findings)


class TestRebaselineGate:
    """tools/update_fingerprints.py refuses --update while the verifier
    reports findings (unless --force)."""

    def _load_tool(self):
        import importlib.util
        import pathlib
        path = (pathlib.Path(__file__).resolve().parents[1] / "tools"
                / "update_fingerprints.py")
        spec = importlib.util.spec_from_file_location("upd_fp_tool", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod

    def test_gate_refuses_on_findings(self, monkeypatch, capsys):
        from repro.analysis import kernel_verify
        from repro.analysis.lint import LintFinding
        tool = self._load_tool()
        monkeypatch.setattr(
            kernel_verify, "verify_scenario",
            lambda scn, **kw: [LintFinding("coverage", scn.label, "level=0",
                                           "injected")])
        assert tool._verifier_gate(["--scenario", "tod-fp32"]) == 1
        err = capsys.readouterr().err
        assert "refusing to re-baseline" in err
        assert "injected" in err

    def test_gate_passes_clean(self, monkeypatch):
        from repro.analysis import kernel_verify
        tool = self._load_tool()
        monkeypatch.setattr(kernel_verify, "verify_scenario",
                            lambda scn, **kw: [])
        assert tool._verifier_gate([]) == 0
