"""Compile-artifact static analysis: fingerprints, diff, lint passes.

The lowering-based tests pin the tod-bf16 cell (the cheapest chart) and
share one base fingerprint document via a module fixture; the lint tests
are pure geometry (no lowering) and sweep the whole scenario matrix.
"""
import copy

import pytest

from repro.analysis import (
    SCENARIOS,
    canonical_json,
    diff_docs,
    dtype_element_counts,
    fingerprint_scenario,
    format_diff,
    hlo_fingerprint,
    lint_dtype_hlo,
    lint_route_coverage,
    lint_vmem,
)
from repro.analysis.diff import ADDED, CHANGED, REMOVED
from repro.core.charts import regular_chart
from repro.core.refine import LevelGeom
from repro.kernels import dispatch


def scenario(label):
    return next(s for s in SCENARIOS() if s.label == label)


# -- fingerprint extraction on synthetic HLO (no lowering) ---------------------

SYNTH_HLO = """
HloModule jit_x, entry_computation_layout={...}

ENTRY %main {
  %p0 = bf16[8,128]{1,0} parameter(0)
  %p1 = bf16[128,64]{1,0} parameter(1)
  %d = f32[8,64]{1,0} dot(%p0, %p1), lhs_contracting_dims={1}, rhs_contracting_dims={0}
  %c = bf16[8,64]{1,0} convert(%d)
  %k = bf16[8,64]{1,0} custom-call(%c), custom_call_target="tpu_custom_call"
  ROOT %o = bf16[8,64]{1,0} custom-call(%k), custom_call_target="SomeOpaqueThing"
}
"""


def test_hlo_fingerprint_shape():
    fp = hlo_fingerprint(SYNTH_HLO)
    assert fp["ops"] == {"convert": 1, "custom-call": 2, "dot": 1,
                         "parameter": 2}
    assert fp["dtypes"] == {"bf16": 5, "f32": 1}
    assert fp["custom_calls"] == {"SomeOpaqueThing": 1, "tpu_custom_call": 1}
    assert fp["cost"]["flops"] == 2 * 8 * 64 * 128
    assert isinstance(fp["cost"]["bytes"], int)


def test_dtype_element_counts():
    counts = dtype_element_counts(SYNTH_HLO)
    assert 8 * 128 in counts["bf16"]
    assert counts["f32"] == {8 * 64}


# -- structured diff ------------------------------------------------------------

def test_diff_docs_kinds_and_format():
    golden = {"plan": [{"route": "pyramid", "b": 1}], "x": {"a": 1, "b": 2}}
    current = {"plan": [{"route": "nd-fused", "b": 1}, {"route": "ref"}],
               "x": {"a": 1, "c": 3}}
    diffs = diff_docs(golden, current)
    by_path = {p: (k, o, n) for p, k, o, n in diffs}
    assert by_path["plan[0].route"] == (CHANGED, "pyramid", "nd-fused")
    assert by_path["plan[1]"][0] == ADDED
    assert by_path["x.b"] == (REMOVED, 2, None)
    assert by_path["x.c"] == (ADDED, None, 3)
    text = format_diff(diffs)
    assert "~ plan[0].route: 'pyramid' -> 'nd-fused'" in text
    assert "- x.b: 2" in text
    assert diff_docs(golden, copy.deepcopy(golden)) == []


# -- lint: VMEM budget (pure geometry, full matrix) ------------------------------

@pytest.mark.parametrize("label", [s.label for s in SCENARIOS()])
def test_vmem_and_route_lint_clean_on_production_plans(label):
    """Zero false positives: every autotuner output across the scenario
    matrix passes the budget re-derivation, and no level routes to the
    jnp reference on the TPU path."""
    scn = scenario(label)
    chart = scn.chart()
    dtype = scn.icr().policy.storage_name
    assert lint_vmem(chart, dtype=dtype, samples=scn.samples,
                     label=label) == []
    assert lint_route_coverage(chart, dtype=dtype, samples=scn.samples,
                               label=label) == []


def test_vmem_lint_flags_oversized_tile():
    """A deliberately oversized tile (far past what the working-set model
    allows) must be flagged — over-budget AND autotuner mismatch."""
    chart = scenario("tod-fp32").chart()
    entries = dispatch.plan_signature(chart, platform="tpu", samples=4,
                                      pyramid=False)
    doctored = copy.deepcopy(entries)
    victim = doctored[-1]  # the widest level: 1024 families
    victim["block_families"]["0"] = 1 << 24  # absurd: ~16M families/tile
    # a request past the level is one block over the whole level; under a
    # budget that block does not fit, the lint must flag it
    t = LevelGeom.for_level(chart, victim["level"]).T[0]
    small = dispatch.block1d_bytes(t, chart.n_csz, chart.n_fsz,
                                   charted=False, block_families=t,
                                   batch_block=victim["sample_block"] or 1
                                   ) - 1
    findings = lint_vmem(chart, samples=4, entries=doctored, label="t",
                         vmem_budget=small)
    assert any("exceeds VMEM budget" in f.message for f in findings)
    # and the untouched plan is clean
    assert lint_vmem(chart, samples=4, entries=entries, label="t") == []


def test_vmem_lint_flags_degenerate_tile():
    """A tile smaller than the autotuner's answer is silent occupancy
    loss — the mismatch arm must catch it."""
    chart = scenario("image-fp32").chart()
    entries = dispatch.plan_signature(chart, platform="tpu", samples=4,
                                      pyramid=False)
    doctored = copy.deepcopy(entries)
    victim = next(e for e in doctored
                  if e["route"] == dispatch.ROUTE_ND_FUSED)
    victim["sample_block"] = 1  # autotuner fits the full slab here
    findings = lint_vmem(chart, samples=4, entries=doctored, label="t")
    assert any("degenerate" in f.message for f in findings)


def test_vmem_lint_flags_overbudget_pyramid():
    """Shrinking the budget below the pyramid's residency total must trip
    the combined-residency check against a stored cover."""
    chart = scenario("tod-fp32").chart()
    entries = dispatch.plan_signature(chart, platform="tpu", samples=4)
    assert any(e["route"] == dispatch.ROUTE_PYRAMID for e in entries)
    findings = lint_vmem(chart, samples=4, entries=entries,
                         vmem_budget=1024, label="t")
    assert any("pyramid residency" in f.message for f in findings)


def test_route_lint_flags_reference_fallback():
    """An N-D chart without axis factors routes every level to the jnp
    reference — exactly the silent fallback the pass exists to forbid."""
    chart = scenario("image-fp32").chart()
    findings = lint_route_coverage(chart, samples=4, have_axis_mats=False,
                                   label="t")
    assert findings and all("reference" in f.message for f in findings)
    assert {f.pass_name for f in findings} == {"route"}


# -- lint: dtype policy over lowered HLO -----------------------------------------

@pytest.fixture(scope="module")
def tod_bf16_doc():
    return fingerprint_scenario(scenario("tod-bf16"))


def test_dtype_lint_clean_on_policy_respecting_hlo():
    """fp32 storage has nothing to violate; and a synthetic module whose
    level fields exist at bf16 passes."""
    chart = regular_chart(64, 3)
    assert lint_dtype_hlo(SYNTH_HLO, chart=chart, policy=None) == []
    # intermediate fine_shape counts for this chart are 124 and 244
    hlo = """
ENTRY %m {
  %a = bf16[124]{0} parameter(0)
  %b = bf16[244]{0} exponential(%a)
  ROOT %c = f32[244]{0} convert(%b)
}
"""
    assert lint_dtype_hlo(hlo, chart=chart, policy="bf16") == []


def test_dtype_lint_flags_f32_resident_field():
    """A level-field-sized tensor that exists only at f32 under a bf16
    policy is a silent storage upcast."""
    chart = regular_chart(64, 3)  # intermediate fields: 124, 244 elements
    hlo = """
ENTRY %m {
  %a = bf16[64]{0} parameter(0)
  %b = f32[124]{0} exponential(%a)
  ROOT %c = f32[244]{0} add(%b, %b)
}
"""
    findings = lint_dtype_hlo(hlo, chart=chart, policy="bf16", entry="e")
    assert len(findings) == 2  # both intermediate levels f32-resident
    assert all("f32-resident" in f.message for f in findings)


def test_dtype_lint_flags_low_precision_dot():
    chart = regular_chart(64, 3)
    hlo = """
ENTRY %m {
  %a = bf16[128,4]{1,0} parameter(0)
  %w = bf16[4,2]{1,0} parameter(1)
  %d = bf16[128,2]{1,0} dot(%a, %w), lhs_contracting_dims={1}, rhs_contracting_dims={0}
  ROOT %o = bf16[256]{0} bitcast(%d)
}
"""
    findings = lint_dtype_hlo(hlo, chart=chart, policy="bf16")
    assert any("accumulates at bf16" in f.message for f in findings)


# -- fingerprints: determinism + injected regressions ----------------------------

def test_fingerprint_noop_relower_is_byte_identical(tod_bf16_doc):
    """The whole guard rests on this: a second lowering of the same
    scenario in the same process serializes byte-for-byte."""
    again = fingerprint_scenario(scenario("tod-bf16"))
    assert canonical_json(tod_bf16_doc) == canonical_json(again)


def test_fingerprint_catches_reference_route_regression(tod_bf16_doc):
    """use_pallas=False sends every level through the jnp reference —
    the plan signature AND the lowered op histograms must both move."""
    doc = fingerprint_scenario(scenario("tod-bf16"), use_pallas=False)
    diffs = diff_docs(tod_bf16_doc, doc)
    paths = [p for p, *_ in diffs]
    assert any(p.startswith("plan.tpu") and p.endswith(".route")
               for p in paths)
    assert any(p.startswith("entries.apply_sqrt.") for p in paths)


def test_fingerprint_catches_disabled_pyramid(tod_bf16_doc):
    """use_pyramid=False dissolves the VMEM-resident prefix back into
    per-level launches — visible in both plan routes and entry HLO."""
    doc = fingerprint_scenario(scenario("tod-bf16"), use_pyramid=False)
    diffs = diff_docs(tod_bf16_doc, doc)
    by_path = {p: (o, n) for p, _k, o, n in diffs}
    route_flips = {p: v for p, v in by_path.items()
                   if p.startswith("plan.tpu") and p.endswith(".route")
                   and ".vjp" not in p}
    assert route_flips and all(o == "pyramid" for o, _n in
                               route_flips.values())
    assert any(p.startswith("entries.") for p in by_path)


def test_fingerprint_catches_bf16_to_f32_drop(tod_bf16_doc):
    """Silently losing the bf16 policy shows up as the bf16 census
    draining out of every entry (and the plan dtype column flipping)."""
    doc = fingerprint_scenario(scenario("tod-bf16"), policy=None,
                               _policy_set=True)
    diffs = diff_docs(tod_bf16_doc, doc)
    by_path = {p: (k, o, n) for p, k, o, n in diffs}
    assert by_path["storage_dtype"] == (CHANGED, "bfloat16", "float32")
    assert any(p.endswith(".dtypes.bf16") and k == REMOVED
               for p, (k, _o, _n) in by_path.items())


def test_fingerprint_serving_section(tod_bf16_doc):
    """The serving executable-cache key rides along: deterministic digest,
    and the policy/backend it was keyed under are visible."""
    srv = tod_bf16_doc["serving"]
    assert srv["storage_dtype"] == "bfloat16"
    assert srv["backend"] == "interpret"
    assert len(srv["digest"]) == 16
    again = fingerprint_scenario(scenario("tod-bf16"))["serving"]
    assert again["digest"] == srv["digest"]
