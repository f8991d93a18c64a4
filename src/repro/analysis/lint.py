"""Golden-free lint passes over plans and lowered HLO (DESIGN.md §13).

Three checkers, each re-deriving an invariant from first principles so it
holds with no golden to compare against:

  :func:`lint_vmem`            every autotuned tile in a plan fits the
                               §10/§11 VMEM working-set byte models, and
                               no tile is *degenerately small* (an
                               autotuner that stopped growing while the
                               next power of two still fit has silently
                               lost occupancy)
  :func:`lint_dtype_hlo`       for a sub-f32 storage policy, the lowered
                               HLO actually carries the level fields at
                               the storage dtype (no silent f32
                               residency) and never accumulates a dot
                               below the accumulation width
  :func:`lint_route_coverage`  no level of the TPU plan silently routes
                               to the jnp ``reference`` oracle — the
                               O(N)-with-small-constant story requires
                               every level on a structured kernel route

Findings are :class:`LintFinding` records; an empty list is a pass.
:func:`lint_scenario` runs all three over one scenario cell.
"""
from __future__ import annotations

import dataclasses

from repro.dtypes import HLO_DTYPE_BYTES, hlo_name, itemsize as dtype_itemsize

__all__ = ["LintFinding", "lint_vmem", "lint_dtype_hlo",
           "lint_route_coverage", "lint_scenario"]


@dataclasses.dataclass(frozen=True)
class LintFinding:
    """One lint violation: which pass, where, and what went wrong."""

    pass_name: str   # vmem | dtype | route
    scenario: str    # scenario label (or caller-supplied context)
    location: str    # level/entry the finding points at
    message: str

    def __str__(self):
        return f"[{self.pass_name}] {self.scenario} {self.location}: " \
               f"{self.message}"


def _prod(xs):
    out = 1
    for x in xs:
        out *= int(x)
    return out


def lint_vmem(chart, *, dtype=None, samples: int = 1, entries=None,
              vmem_budget=None, have_axis_mats=None, pyramid: bool = True,
              label: str = "") -> list:
    """Check every autotuned tile of the TPU plan against the VMEM budget.

    Re-derives each reported tile's working set through the same byte
    models the autotuners grow against (``block1d_bytes`` for the 1-D
    routes, ``_fused_tile_bytes`` for the megakernel and the pyramid
    residency) and flags:

      * **over-budget**: the reported tile's working set exceeds the
        budget — the autotuner output and the model disagree;
      * **degenerate**: the tile stopped below its natural ceiling
        (``T_0`` / ``samples``) although the next power-of-two step still
        fits — occupancy silently left on the table;
      * **mismatch**: the reported 1-D tile differs from what the
        autotuner derives today for the same geometry.

    ``entries`` defaults to the TPU ``plan()`` of `chart`; pass a stored
    ``plan_signature`` (or a doctored one — the negative tests do) to
    lint a plan that was not derived in this process.
    """
    from repro.core.refine import LevelGeom
    from repro.kernels import dispatch as dsp

    itemsize = dtype_itemsize(dtype or "float32")
    budget = dsp.VMEM_BUDGET_BYTES if vmem_budget is None else vmem_budget
    if entries is None:
        entries = dsp.plan_signature(
            chart, platform="tpu", have_axis_mats=have_axis_mats,
            samples=samples, dtype=dtype, pyramid=pyramid,
            vmem_budget=budget)
    findings = []

    def find(loc, msg):
        findings.append(LintFinding("vmem", label or chart.boundary,
                                    loc, msg))

    pyramid_geoms, pyramid_s_b = [], None
    for e in entries:
        lvl = int(e["level"])
        loc = f"level={lvl}"
        geom = LevelGeom.for_level(chart, lvl)
        route = e["route"]
        blocks = {int(k): int(v)
                  for k, v in (e.get("block_families") or {}).items()}
        s_b = e.get("sample_block")
        s_b = None if s_b is None else int(s_b)

        if route == dsp.ROUTE_PYRAMID:
            pyramid_geoms.append(geom)
            pyramid_s_b = s_b
            continue
        if route == dsp.ROUTE_REFERENCE:
            continue  # no tiles on the oracle path

        if route == dsp.ROUTE_ND_FUSED:
            charted = tuple(k > 1 for k in geom.kept_T)
            b_f = blocks.get(0)
            if b_f is None or s_b is None:
                find(loc, "nd-fused entry is missing its (b_f, s_b) tile")
                continue
            ws = dsp._fused_tile_bytes(geom, charted, b_f, s_b, itemsize)
            if ws > budget:
                find(loc, f"nd-fused tile (b_f={b_f}, s_b={s_b}) working "
                          f"set {ws} B exceeds VMEM budget {budget} B")
            t0 = geom.T[0]
            if b_f < t0:
                nxt = min(2 * b_f, t0)
                if dsp._fused_tile_bytes(geom, charted, nxt, s_b,
                                         itemsize) <= budget:
                    find(loc, f"degenerate nd-fused family block: b_f={b_f} "
                              f"but b_f={nxt} still fits the budget")
            if s_b < samples:
                nxt = min(2 * s_b, samples)
                if dsp._fused_tile_bytes(geom, charted, b_f, nxt,
                                         itemsize) <= budget:
                    find(loc, f"degenerate nd-fused sample block: s_b={s_b} "
                              f"but s_b={nxt} still fits the budget")
            continue

        if route in (dsp.ROUTE_STATIONARY_1D, dsp.ROUTE_CHARTED_1D):
            charted = route == dsp.ROUTE_CHARTED_1D
            b_f = blocks.get(0)
            if b_f is None:
                find(loc, f"{route} entry is missing its family block")
                continue
            t0, csz, fsz = geom.T[0], geom.n_csz, geom.n_fsz
            floor = dsp.block1d_floor(t0, csz, fsz)
            ws = dsp.block1d_bytes(t0, csz, fsz, charted=charted,
                                   block_families=b_f,
                                   batch_block=s_b or 1, itemsize=itemsize)
            if b_f > floor and ws > budget:
                find(loc, f"1-D tile b_f={b_f} (floor {floor}) working set "
                          f"{ws} B exceeds VMEM budget {budget} B")
            want_bf = dsp.autotune_block_families(
                t0, csz, fsz, charted=charted, itemsize=itemsize,
                vmem_budget=budget)
            if b_f != want_bf:
                find(loc, f"1-D family block {b_f} != autotuner answer "
                          f"{want_bf} for this geometry")
            if s_b is not None:
                want_sb = dsp.autotune_batch_block(
                    samples, t0, csz, fsz, charted=charted,
                    block_families=b_f, itemsize=itemsize,
                    vmem_budget=budget)
                if s_b != want_sb:
                    find(loc, f"1-D sample block {s_b} != autotuner answer "
                              f"{want_sb}")
            continue

        if route == dsp.ROUTE_AXES_ND:
            for a in range(len(geom.T)):
                ag = geom.axis(a)
                b_f = blocks.get(a)
                if b_f is None:
                    find(loc, f"axes-nd entry is missing the axis-{a} block")
                    continue
                want = dsp.autotune_block_families(
                    ag.T[0], ag.n_csz, ag.n_fsz, charted=ag.kept_T[0] > 1,
                    itemsize=itemsize, vmem_budget=budget)
                if b_f != want:
                    find(loc, f"axis-{a} family block {b_f} != autotuner "
                              f"answer {want}")
                ws = dsp.block1d_bytes(ag.T[0], ag.n_csz, ag.n_fsz,
                                       charted=ag.kept_T[0] > 1,
                                       block_families=b_f,
                                       itemsize=itemsize)
                floor = dsp.block1d_floor(ag.T[0], ag.n_csz, ag.n_fsz)
                if b_f > floor and ws > budget:
                    find(loc, f"axis-{a} tile b_f={b_f} working set {ws} B "
                              f"exceeds VMEM budget {budget} B")
            continue

        find(loc, f"unknown route {route!r} — lint pass out of date?")

    if pyramid_geoms:
        from repro.kernels.pyramid import sample_slab

        s_b = pyramid_s_b or 1
        total = dsp.pyramid_bytes(pyramid_geoms, s_b=s_b, itemsize=itemsize)
        loc = f"pyramid[0..{len(pyramid_geoms) - 1}]"
        if total > budget:
            find(loc, f"pyramid residency {total} B at s_b={s_b} exceeds "
                      f"VMEM budget {budget} B")
        if len(pyramid_geoms) < 2:
            find(loc, "single-level pyramid cover — the cover rule requires "
                      "at least two resident levels")
        if s_b < samples:
            nxt = min(2 * s_b, samples)
            if len(pyramid_geoms[0].coarse_shape) == 1:
                nxt = sample_slab(nxt, samples, itemsize)
            grown = dsp.pyramid_bytes(pyramid_geoms, s_b=nxt,
                                      itemsize=itemsize)
            if nxt > s_b and grown <= budget:
                find(loc, f"degenerate pyramid sample block: s_b={s_b} but "
                          f"s_b={nxt} still fits the budget")
    return findings


def lint_dtype_hlo(hlo_text: str, *, chart, policy, samples: int = 1,
                   batched: bool = False, label: str = "",
                   entry: str = "") -> list:
    """Check a lowered module against the storage/accumulation contract.

    Only meaningful for sub-f32 storage policies (fp32 storage has
    nothing to violate — the pass returns no findings). Two invariants,
    both validated empirically against every chart × policy cell before
    being locked in here:

      * every intermediate level field (element count of
        ``LevelGeom.fine_shape`` for levels ``0..n_levels-2``, times
        ``samples`` when the entry is ``batched`` — a batched module's
        fields are slab-shaped, while its *unbatched* counts are the
        per-level posterior parameters / matrices, f32 by design) must
        appear at the storage dtype somewhere in the module; a count that
        appears **only** at f32 means the field is f32-resident — the
        §11 HBM-byte win silently gone;
      * no ``dot`` output may be narrower than the accumulation dtype —
        the kernels thread ``accum_dtype`` into every
        ``preferred_element_type`` and a bf16-output dot means bf16
        accumulation crept in.
    """
    from repro.core.refine import LevelGeom
    from repro.kernels.policy import resolve as resolve_policy

    from .fingerprint import _instructions, dtype_element_counts, _SHAPE_RE

    pol = resolve_policy(policy)
    if pol.storage_itemsize >= 4:
        return []
    storage = hlo_name(pol.storage_dtype)
    accum_width = dtype_itemsize(pol.accum_dtype)
    findings = []

    def find(loc, msg):
        findings.append(LintFinding("dtype", label, loc, msg))

    counts = dtype_element_counts(hlo_text)
    stored = counts.get(storage, set())
    f32 = counts.get("f32", set())
    for lvl in range(chart.n_levels - 1):
        n = _prod(LevelGeom.for_level(chart, lvl).fine_shape)
        c = samples * n if batched else n
        tag = f" (x{samples} samples)" if batched else ""
        if c in f32 and c not in stored:
            find(f"{entry or 'module'}/level={lvl}",
                 f"level field of {c} elements{tag} is f32-resident — "
                 f"expected {storage} storage under policy "
                 f"{pol.storage_name}/{pol.accum_name}")

    for out_type, kind, _line in _instructions(hlo_text):
        if kind != "dot":
            continue
        for dt, _dims in _SHAPE_RE.findall(out_type):
            if HLO_DTYPE_BYTES.get(dt, 4) < accum_width:
                find(entry or "module",
                     f"dot accumulates at {dt} (< {pol.accum_name} "
                     f"accumulation contract)")
    return findings


def lint_route_coverage(chart, *, dtype=None, samples: int = 1,
                        have_axis_mats=None, pyramid: bool = True,
                        label: str = "") -> list:
    """No level of the TPU plan may silently route to the jnp reference.

    ``plan(platform="tpu")`` is pure geometry (no lowering), so this pass
    answers the what-would-run-on-TPU question from any host. A level on
    ``route="reference"`` means the structured kernels declined the
    geometry — legitimate only as an explicit, visible decision, never as
    a silent fallback in a production scenario.
    """
    from repro.kernels import dispatch as dsp

    findings = []
    for e in dsp.plan(chart, platform="tpu", have_axis_mats=have_axis_mats,
                      samples=samples, dtype=dtype, pyramid=pyramid):
        if e["route"] == dsp.ROUTE_REFERENCE:
            findings.append(LintFinding(
                "route", label, f"level={e['level']}",
                "routes to the jnp reference on the TPU path — no "
                "structured kernel covers this level"))
        elif e["backend"] == dsp.BACKEND_REFERENCE \
                and e["route"] != dsp.ROUTE_REFERENCE:
            findings.append(LintFinding(
                "route", label, f"level={e['level']}",
                f"structured route {e['route']!r} reports the reference "
                f"backend on the TPU path"))
    return findings


def lint_scenario(scn, *, backend: str = "interpret") -> list:
    """All three passes over one scenario cell (see :mod:`.scenarios`).

    VMEM and route coverage lint the pure-geometry TPU plan; the dtype
    pass walks every lowered entry point's compiled HLO.
    """
    from .scenarios import lower_entries

    chart = scn.chart()
    icr = scn.icr()
    storage = icr.policy.storage_name
    have_axis = chart.ndim > 1
    findings = []
    findings += lint_vmem(chart, dtype=storage, samples=scn.samples,
                          have_axis_mats=have_axis, label=scn.label)
    findings += lint_route_coverage(chart, dtype=storage,
                                    samples=scn.samples,
                                    have_axis_mats=have_axis,
                                    label=scn.label)
    lowered = lower_entries(scn, backend=backend)
    lowered.pop("_serving", None)
    for name, low in sorted(lowered.items()):
        findings += lint_dtype_hlo(
            low.compile().as_text(), chart=chart, policy=scn.policy,
            samples=scn.samples,
            # condition_matvec is slab-shaped too: k RHS columns ride the
            # sample axis through apply_sqrt_batch and its VJP
            batched=("batch" in name or "slab" in name
                     or name == "condition_matvec"),
            label=scn.label, entry=name)
    return findings
