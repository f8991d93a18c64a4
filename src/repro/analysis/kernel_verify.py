"""Launch-plan verifier: prove properties of every exported Pallas launch.

DESIGN.md §14.  Every kernel entry point builds a declarative
:class:`~repro.kernels.launch.LaunchPlan` and launches *through* it
(``run_plan``), and ``dispatch.level_launch_plans`` /
``dispatch.chart_launch_plans`` export the same records — so proving a
property of the plan proves it of the launch.  For every route ×
autotuned tile × scenario cell this module checks:

* **coverage** — enumerate the grid, concretely evaluate every output
  index map, and require the multiset of written block indices to be
  exactly the cartesian block decomposition of the output array: no
  gaps, no double-writes, no out-of-range blocks, block shape divides
  the array shape.
* **bounds** — every input block fetched at every grid step lies inside
  the (padded) operand array.
* **halo** — for each overhang-carrying view, the union of the block
  intervals fetched by the view and its ``halo_of`` siblings covers the
  declared overhang at every grid step.
* **bytes** — the plan's double-buffered working set fits the VMEM lint
  budget (floor-exempt, like the autotuner); forward plans must not
  exceed the ``block1d_bytes`` / ``_fused_tile_bytes`` byte model the
  autotuner grew against; plan operand array bytes must dominate the
  ``roofline/level_traffic.py`` HBM model (the plan cannot claim to
  move fewer bytes than the roofline says the level needs).
* **transpose** — each registered custom_vjp pair is a true transpose:
  a taint-based jaxpr linearity walk of the forward in (field, ξ) at
  fixed matrices, plus an exact ``⟨Ax, y⟩ == ⟨x, Aᵀy⟩`` dot test run in
  interpret mode at the verified tile config and storage dtype.
* **hygiene** — every ``dot_general`` carries a
  ``preferred_element_type`` at least as wide as the accumulation
  dtype; no data-dependent control flow (``while``/``cond``). (Kernel
  bodies upcast bf16 storage to float32 on load by design: the v5e VPU
  has no bf16 arithmetic; the storage dtype is what the plans' HBM
  operands carry, which ``run_plan`` checks.)

Findings are :class:`~repro.analysis.lint.LintFinding` records with
``pass_name`` one of ``coverage | bounds | halo | bytes | transpose |
hygiene``.  ``python -m repro.analysis verify`` drives
:func:`verify_scenario` over every scenario cell and fails CI on any
finding; ``tools/update_fingerprints.py`` refuses to re-baseline the
compile-artifact goldens while the verifier reports findings.
"""
from __future__ import annotations

import itertools
import json
import math
from collections import Counter

import jax
import jax.numpy as jnp
import numpy as np

from repro.compat import Jaxpr
from repro.core import matern32
from repro.core.refine import LevelGeom, axis_refinement_matrices_level
from repro.kernels import dispatch as dsp
from repro.roofline.level_traffic import refine_level_traffic

from .lint import LintFinding
from .scenarios import SCENARIOS

__all__ = [
    "check_coverage", "check_bounds", "check_halo", "check_bytes",
    "check_linearity", "check_hygiene", "transpose_dot_check",
    "verify_plan", "verify_group", "verify_scenario", "verify_all",
]


def _grid_steps(grid):
    return itertools.product(*(range(int(n)) for n in grid))


def _eval_map(op, g):
    idx = op.index_map(*g)
    if not isinstance(idx, tuple):
        idx = (idx,)
    return tuple(int(i) for i in idx)


# ---------------------------------------------------------------- coverage

def check_coverage(plan, *, scenario: str = "", location: str = "") -> list:
    """Exact output coverage: each output block written exactly once."""
    findings = []

    def find(msg):
        findings.append(LintFinding("coverage", scenario, location,
                                    f"{plan.kernel}: {msg}"))

    for op in plan.outputs:
        nblocks = []
        ok = True
        for d, (asz, bsz) in enumerate(zip(op.array_shape, op.block_shape)):
            if asz % bsz:
                find(f"output {op.name!r} dim {d}: array extent {asz} is "
                     f"not a multiple of block extent {bsz}")
                ok = False
            nblocks.append(asz // bsz)
        if not ok:
            continue
        counts = Counter()
        for g in _grid_steps(plan.grid):
            idx = _eval_map(op, g)
            if len(idx) != len(op.block_shape):
                find(f"output {op.name!r}: index map {op.index_map.name!r} "
                     f"returns rank {len(idx)}, block is rank "
                     f"{len(op.block_shape)}")
                counts = None
                break
            counts[idx] += 1
        if counts is None:
            continue
        expected = set(itertools.product(*(range(n) for n in nblocks)))
        written = set(counts)
        missing = sorted(expected - written)
        extra = sorted(written - expected)
        dupes = sorted(k for k, v in counts.items()
                       if v > 1 and k in expected)
        if missing:
            find(f"output {op.name!r}: {len(missing)} block(s) never "
                 f"written by index map {op.index_map.name!r} "
                 f"(e.g. {missing[:3]}) — coverage gap")
        if extra:
            find(f"output {op.name!r}: index map {op.index_map.name!r} "
                 f"writes {len(extra)} out-of-range block(s) "
                 f"(e.g. {extra[:3]})")
        if dupes:
            find(f"output {op.name!r}: {len(dupes)} block(s) written more "
                 f"than once (e.g. {dupes[:3]}) — double-write")
    return findings


# ------------------------------------------------------------------ bounds

def check_bounds(plan, *, scenario: str = "", location: str = "") -> list:
    """Every input block read at every grid step is inside its array."""
    findings = []

    def find(msg):
        findings.append(LintFinding("bounds", scenario, location,
                                    f"{plan.kernel}: {msg}"))

    for op in plan.inputs:
        for g in _grid_steps(plan.grid):
            idx = _eval_map(op, g)
            bad = None
            for d, (i, bsz, asz) in enumerate(
                    zip(idx, op.block_shape, op.array_shape)):
                lo, hi = i * bsz, i * bsz + bsz
                if lo < 0 or hi > asz:
                    bad = (d, lo, hi, asz)
                    break
            if bad is not None:
                d, lo, hi, asz = bad
                find(f"input {op.name!r} at grid step {g}: index map "
                     f"{op.index_map.name!r} reads [{lo}, {hi}) on dim {d} "
                     f"outside the padded operand extent {asz}")
                break  # one finding per operand is enough
    return findings


# -------------------------------------------------------------------- halo

def check_halo(plan, *, scenario: str = "", location: str = "") -> list:
    """Halo groups cover the declared overhang at every grid step."""
    findings = []

    def find(msg):
        findings.append(LintFinding("halo", scenario, location,
                                    f"{plan.kernel}: {msg}"))

    mains = {op.name: op for op in plan.inputs if op.overhang}
    halos = {}
    for op in plan.inputs:
        if op.halo_of:
            halos.setdefault(op.halo_of, []).append(op)
    for name in halos:
        if name not in mains:
            find(f"halo view(s) {[h.name for h in halos[name]]} reference "
                 f"main view {name!r} which declares no overhang")

    for main in mains.values():
        group = [main] + halos.get(main.name, [])
        over_dims = [d for d, (lo, hi) in enumerate(main.overhang)
                     if lo or hi]
        if len(over_dims) != 1:
            find(f"main view {main.name!r} declares overhang on "
                 f"{len(over_dims)} dims — the halo checker only models "
                 f"single-axis overhang")
            continue
        d = over_dims[0]
        lo_ov, hi_ov = main.overhang[d]
        mismatched = [s for s in group[1:]
                      if s.block_shape != main.block_shape
                      or s.array_shape != main.array_shape]
        if mismatched:
            find(f"halo view(s) {[s.name for s in mismatched]} do not "
                 f"share {main.name!r}'s block/array shape")
            continue
        bsz = main.block_shape[d]
        for g in _grid_steps(plan.grid):
            idxs = [_eval_map(op, g) for op in group]
            midx = idxs[0]
            diverged = False
            for op, idx in zip(group[1:], idxs[1:]):
                if any(idx[e] != midx[e] for e in range(len(idx)) if e != d):
                    find(f"halo view {op.name!r} at grid step {g} diverges "
                         f"from main {main.name!r} on a non-overhang dim")
                    diverged = True
            if diverged:
                break
            need_lo = midx[d] * bsz - lo_ov
            need_hi = midx[d] * bsz + bsz + hi_ov
            spans = sorted((idx[d] * bsz, idx[d] * bsz + bsz)
                           for idx in idxs)
            cur = need_lo
            for s_lo, s_hi in spans:
                if s_lo <= cur:
                    cur = max(cur, s_hi)
            if cur < need_hi:
                find(f"main view {main.name!r} at grid step {g}: overhang "
                     f"window [{need_lo}, {need_hi}) on dim {d} not covered "
                     f"by the fetched blocks {spans} of group "
                     f"{[op.name for op in group]}")
                break  # one grid step is enough to name the defect
    return findings


# ------------------------------------------------------------------- bytes

_TRAFFIC_ROUTES = (dsp.ROUTE_STATIONARY_1D, dsp.ROUTE_CHARTED_1D,
                   dsp.ROUTE_ND_FUSED)


def check_bytes(plan, *, geom=None, route=None, samples: int = 1,
                dtype=None, vmem_budget=None,
                scenario: str = "", location: str = "") -> list:
    """Working set vs budget and the autotuner/roofline byte models."""
    budget = dsp.VMEM_BUDGET_BYTES if vmem_budget is None else vmem_budget
    findings = []

    def find(msg):
        findings.append(LintFinding("bytes", scenario, location,
                                    f"{plan.kernel}: {msg}"))

    p = dict(plan.params)
    ws = plan.block_bytes()
    itemsize = plan.outputs[0].itemsize
    onedim = {"t", "n_csz", "n_fsz", "b_f", "b_b", "charted"} <= p.keys()
    exempt = onedim and p["b_f"] <= dsp.block1d_floor(
        p["t"], p["n_csz"], p["n_fsz"])
    if ws > budget and not exempt:
        find(f"plan working set {ws} B exceeds the VMEM budget {budget} B")

    if onedim and p.get("kind") == "fwd":
        model = dsp.block1d_bytes(
            p["t"], p["n_csz"], p["n_fsz"], charted=p["charted"],
            block_families=p["b_f"], batch_block=p["b_b"],
            itemsize=itemsize)
        if ws > model:
            find(f"plan working set {ws} B exceeds the block1d_bytes "
                 f"model {model} B at its own tile (b_f={p['b_f']}, "
                 f"b_b={p['b_b']}) — the autotuner model undercounts")
    if plan.kernel == "refine_nd_fused" and geom is not None:
        model = dsp._fused_tile_bytes(geom, tuple(p["charted"]), p["b_f"],
                                      p["s_b"], itemsize)
        if ws > model:
            find(f"plan working set {ws} B exceeds the _fused_tile_bytes "
                 f"model {model} B at its own tile (b_f={p['b_f']}, "
                 f"s_b={p['s_b']})")

    # roofline cross-check: the plan's concrete operand arrays cannot be
    # smaller than what the HBM traffic model says the level must move
    if (p.get("kind") == "fwd" and geom is not None
            and route in _TRAFFIC_ROUTES):
        tr = refine_level_traffic(geom, route, dtype=dtype or "float32",
                                  samples=samples)
        need_in = tr["field_read"] + tr["xi_read"] + tr["matrices"]
        have_in = sum(op.array_bytes for op in plan.inputs
                      if not op.halo_of)
        if have_in < need_in:
            find(f"plan input arrays total {have_in} B but the "
                 f"level_traffic model reads {need_in} B "
                 f"(field+xi+matrices) — the plan is missing traffic")
        have_out = sum(op.array_bytes for op in plan.outputs)
        if have_out < tr["fine_write"]:
            find(f"plan output arrays total {have_out} B but the "
                 f"level_traffic model writes {tr['fine_write']} B")
    return findings


# ------------------------------------------------- linearity (taint walk)

_LINEAR_PRIMS = frozenset({
    "add", "add_any", "sub", "neg", "pad", "slice", "reshape", "transpose",
    "concatenate", "broadcast_in_dim", "squeeze", "expand_dims", "rev",
    "convert_element_type", "reduce_sum", "cumsum", "real", "imag", "copy",
    "gather",
})
# name -> number of leading operands the primitive is linear in; taint on
# any later operand (indices, denominator, ...) is a finding
_PREFIX_LINEAR = {"dynamic_slice": 1, "dynamic_update_slice": 2, "div": 1}
# (operand, indices, updates) primitives: linear in operand and updates
# jointly (an overwrite at fixed indices); tainted indices are a finding
_SCATTER = frozenset({"scatter", "scatter-add", "scatter_add"})
_BILINEAR = frozenset({"mul", "dot_general", "conv_general_dilated"})
_CALL_PRIMS = frozenset({
    "jit", "pjit", "closed_call", "core_call", "remat", "remat2",
    "checkpoint",
    "custom_jvp_call", "custom_jvp_call_jaxpr",
    "custom_vjp_call", "custom_vjp_call_jaxpr",
})


def _callee(params):
    for key in ("jaxpr", "call_jaxpr", "fun_jaxpr"):
        sub = params.get(key)
        if sub is not None:
            return getattr(sub, "jaxpr", sub)
    return None


def _linear_walk(jaxpr, in_taints, find, path):
    """Propagate taint; flag any nonlinear primitive touching taint.

    Returns ``(out_taints, final_invar_taints)`` — the latter carries the
    end-state of mutable refs so ``pallas_call`` output refs resolve.
    """
    from repro.compat import Literal

    env = {}
    for v, t in zip(jaxpr.invars, in_taints):
        env[v] = bool(t)
    for v in jaxpr.constvars:
        env[v] = False

    def rd(a):
        return False if isinstance(a, Literal) else env.get(a, False)

    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        ts = [rd(a) for a in eqn.invars]
        any_t = any(ts)
        if name in _CALL_PRIMS:
            sub = _callee(eqn.params)
            if sub is None:
                if any_t:
                    find(f"{path}: opaque call primitive {name} consumes "
                         f"tainted data")
                outs = [any_t] * len(eqn.outvars)
            else:
                outs, _ = _linear_walk(sub, ts, find, f"{path}/{name}")
            for v, t in zip(eqn.outvars, outs):
                env[v] = t
            continue
        if name == "pallas_call":
            sub = eqn.params["jaxpr"]
            n_extra = len(sub.invars) - len(ts)  # out refs (+ scratch)
            _, fin = _linear_walk(sub, ts + [False] * max(0, n_extra),
                                  find, f"{path}/pallas")
            out_t = fin[len(ts):len(ts) + len(eqn.outvars)]
            for v, t in zip(eqn.outvars, out_t):
                env[v] = t
            continue
        if name == "get":
            env[eqn.outvars[0]] = rd(eqn.invars[0])
            continue
        if name == "swap":
            ref, val = eqn.invars[0], eqn.invars[1]
            old = rd(ref)
            env[ref] = rd(ref) or rd(val)  # partial writes merge
            env[eqn.outvars[0]] = old
            continue
        if name == "addupdate":
            ref, val = eqn.invars[0], eqn.invars[1]
            env[ref] = rd(ref) or rd(val)
            continue
        if not any_t:
            for v in eqn.outvars:
                env[v] = False
            continue
        out_t = True
        if name in _LINEAR_PRIMS:
            pass
        elif name in _BILINEAR:
            if sum(1 for t in ts if t) > 1:
                find(f"{path}: bilinear {name} has more than one tainted "
                     f"operand — nonlinear in the linearized inputs")
        elif name in _PREFIX_LINEAR:
            if any(ts[_PREFIX_LINEAR[name]:]):
                find(f"{path}: {name} is tainted in a nonlinear operand "
                     f"position (index/denominator)")
        elif name in _SCATTER:
            if ts[1]:
                find(f"{path}: {name} indices are tainted — "
                     f"data-dependent placement")
            out_t = ts[0] or any(ts[2:])
        elif name == "select_n":
            if ts[0]:
                find(f"{path}: select_n predicate is tainted — "
                     f"data-dependent selection")
            out_t = any(ts[1:])
        else:
            find(f"{path}: primitive {name} is not linear (or unknown to "
                 f"the linearity checker) but consumes tainted data")
        for v in eqn.outvars:
            env[v] = out_t
    outs = [rd(v) for v in jaxpr.outvars]
    fin = [env.get(v, False) for v in jaxpr.invars]
    return outs, fin


def check_linearity(f, args, *, scenario: str = "", location: str = "",
                    label: str = "") -> list:
    """Prove ``f`` is linear in every array argument by jaxpr analysis."""
    findings = []

    def find(msg):
        findings.append(LintFinding("transpose", scenario, location, msg))

    jx = jax.make_jaxpr(f)(*args)
    _linear_walk(jx.jaxpr, [True] * len(jx.jaxpr.invars), find,
                 label or getattr(f, "__name__", "fn"))
    return findings


# ----------------------------------------------------------------- hygiene

def _child_jaxprs(eqn):
    for v in eqn.params.values():
        items = v if isinstance(v, (tuple, list)) else (v,)
        for u in items:
            u = getattr(u, "jaxpr", u)
            if isinstance(u, Jaxpr):
                yield u


def _hygiene_walk(jaxpr, find, path, *, accum_width):
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if name in ("while", "cond"):
            find(f"{path}: data-dependent control flow primitive "
                 f"`{name}` in the traced computation")
        if name == "dot_general":
            pet = eqn.params.get("preferred_element_type")
            if pet is None:
                find(f"{path}: dot_general without preferred_element_type "
                     f"— accumulation dtype left to the backend")
            elif jnp.dtype(pet).itemsize < accum_width:
                find(f"{path}: dot_general preferred_element_type "
                     f"{jnp.dtype(pet).name} is narrower than the "
                     f"accumulation dtype")
        for sub in _child_jaxprs(eqn):
            _hygiene_walk(sub, find, f"{path}/{name}",
                          accum_width=accum_width)


def check_hygiene(f, args, *, accum_dtype="float32",
                  scenario: str = "", location: str = "",
                  label: str = "") -> list:
    """dot_general accumulation and control-flow hygiene of ``f``."""
    findings = []

    def find(msg):
        findings.append(LintFinding("hygiene", scenario, location, msg))

    jx = jax.make_jaxpr(f)(*args)
    _hygiene_walk(jx.jaxpr, find, label or getattr(f, "__name__", "fn"),
                  accum_width=jnp.dtype(accum_dtype).itemsize)
    return findings


# --------------------------------------------------------------- transpose

def transpose_dot_check(f, args, *, rtol: float, seed: int = 0,
                        scenario: str = "", location: str = "",
                        label: str = "") -> list:
    """Exact ``⟨Ax, y⟩ == ⟨x, Aᵀy⟩`` test of ``f`` and its VJP."""
    findings = []

    def find(msg):
        findings.append(LintFinding("transpose", scenario, location, msg))

    rng = np.random.default_rng(seed)
    out, vjpf = jax.vjp(f, *args)
    y = jnp.asarray(rng.normal(size=out.shape), out.dtype)
    cots = vjpf(y)

    def dot(a, b):
        return float(jnp.vdot(jnp.asarray(a, jnp.float32).ravel(),
                              jnp.asarray(b, jnp.float32).ravel()))

    lhs = dot(out, y)
    rhs = sum(dot(x, g) for x, g in zip(args, cots))
    denom = max(abs(lhs), abs(rhs), 1e-30)
    rel = abs(lhs - rhs) / denom
    if not math.isfinite(rel) or rel > rtol:
        find(f"{label or 'fn'}: adjoint is not the transpose of the "
             f"forward: <Ax, y> = {lhs:.6g} but <x, A^T y> = {rhs:.6g} "
             f"(relative error {rel:.3g} > {rtol:g})")
    return findings


# ----------------------------------------------------------------- drivers

def verify_plan(plan, *, geom=None, route=None, samples: int = 1,
                dtype=None, vmem_budget=None,
                scenario: str = "", location: str = "") -> list:
    """All static passes (coverage, bounds, halo, bytes) of one plan."""
    kw = dict(scenario=scenario, location=location)
    return (check_coverage(plan, **kw)
            + check_bounds(plan, **kw)
            + check_halo(plan, **kw)
            + check_bytes(plan, geom=geom, route=route, samples=samples,
                          dtype=dtype, vmem_budget=vmem_budget, **kw))


def _group_runner(grp, chart, kernel, *, storage, samples: int):
    """Build the route's differentiable runner at the group's verified
    tile config (interpret mode) plus random storage-dtype operands."""
    from repro.kernels.nd import refine_axes
    from repro.kernels.nd_fused import refine_nd_fused
    from repro.kernels.pyramid import refine_pyramid

    route = grp["route"]
    rng = np.random.default_rng(20260808)
    if route == dsp.ROUTE_PYRAMID:
        geoms = grp["geom"]
        mats, xis = [], []
        for lvl, g in enumerate(geoms):
            rs, ds = axis_refinement_matrices_level(chart, kernel, lvl)
            mats.append(([jnp.asarray(r, storage) for r in rs],
                         [jnp.asarray(d, storage) for d in ds]))
            nd = len(g.coarse_shape)
            xis.append(jnp.asarray(
                rng.normal(size=(samples, int(np.prod(g.T)),
                                 g.n_fsz ** nd)), storage))
        field = jnp.asarray(
            rng.normal(size=(samples,) + tuple(geoms[0].coarse_shape)),
            storage)
        s_b = grp["plans"][0].params["s_b"]

        def f(field, *xis):
            return refine_pyramid(field, list(xis), mats, geoms,
                                  interpret=True, sample_block=s_b,
                                  sample_axis=True)

        return f, (field, *xis)

    geom, lvl = grp["geom"], grp["level"]
    rs, ds = axis_refinement_matrices_level(chart, kernel, lvl)
    rs = [jnp.asarray(r, storage) for r in rs]
    ds = [jnp.asarray(d, storage) for d in ds]
    nd = len(geom.coarse_shape)
    field = jnp.asarray(
        rng.normal(size=(samples,) + tuple(geom.coarse_shape)), storage)
    xi = jnp.asarray(
        rng.normal(size=(samples, int(np.prod(geom.T)), geom.n_fsz ** nd)),
        storage)
    fwd = grp["plans"][0].params

    if route in (dsp.ROUTE_STATIONARY_1D, dsp.ROUTE_CHARTED_1D):
        xi = xi.reshape(samples, geom.T[0], geom.n_fsz)
        r, d = rs[0], ds[0]
        b_f, b_b = fwd["b_f"], fwd["b_b"]

        def f(field, xi):
            return dsp.refine(field, xi, r, d, geom, backend="interpret",
                              block_families=b_f, sample_block=b_b,
                              sample_axis=True)

        return f, (field, xi)
    if route == dsp.ROUTE_ND_FUSED:
        b_f, s_b = fwd["b_f"], fwd["s_b"]

        def f(field, xi):
            return refine_nd_fused(field, xi, rs, ds, geom,
                                   interpret=True, block_families=b_f,
                                   sample_block=s_b, sample_axis=True)

        return f, (field, xi)
    if route == dsp.ROUTE_AXES_ND:

        def f(field, xi):
            return refine_axes(field, xi, rs, ds, geom, interpret=True,
                               sample_axis=True)

        return f, (field, xi)
    raise ValueError(f"no runner for route {route!r}")


def verify_group(grp, chart, kernel, *, samples: int, storage,
                 vmem_budget=None, semantic: bool = True,
                 scenario: str = "") -> list:
    """Verify one launch group: static passes per plan + semantic
    (linearity, hygiene, transpose) checks of the route's custom VJP."""
    route, lvl = grp["route"], grp["level"]
    loc = (f"level={lvl}" if isinstance(lvl, int)
           else f"levels={lvl[0]}..{lvl[1]}")
    geom = grp["geom"] if isinstance(grp["geom"], LevelGeom) else None
    storage = jnp.dtype(storage)
    findings = []
    for plan in grp["plans"]:
        findings += verify_plan(plan, geom=geom, route=route,
                                samples=samples, dtype=storage,
                                vmem_budget=vmem_budget,
                                scenario=scenario, location=loc)
    if not semantic or not grp["plans"] or route == dsp.ROUTE_REFERENCE:
        return findings
    f, args = _group_runner(grp, chart, kernel, storage=storage,
                            samples=samples)
    kw = dict(scenario=scenario, location=loc)
    findings += check_linearity(f, args, label=f"{route}/fwd", **kw)
    findings += check_hygiene(f, args,
                              label=f"{route}/fwd", **kw)
    out, vjpf = jax.vjp(f, *args)
    y = jnp.zeros(out.shape, out.dtype)
    findings += check_hygiene(vjpf, (y,),
                              label=f"{route}/vjp", **kw)
    rtol = 2e-3 if storage.itemsize >= 4 else 0.2
    findings += transpose_dot_check(f, args, rtol=rtol,
                                    label=route, **kw)
    return findings


def verify_scenario(scn, *, vmem_budget=None, semantic: bool = True) -> list:
    """Run every verifier pass over one scenario cell.

    Both pyramid overlays are exported (``pyramid=True`` collapses the
    covered prefix into the single multi-level launch; ``pyramid=False``
    is the per-level execution ``ICR(use_pyramid=False)`` runs, whose
    1-D adjoints are also the pyramid's backward building blocks);
    identical groups between the two overlays are checked once.
    """
    from repro.kernels.policy import resolve as resolve_policy

    chart = scn.chart()
    pol = resolve_policy(scn.policy) if scn.policy else None
    storage = jnp.dtype(pol.storage_dtype) if pol else jnp.dtype(jnp.float32)
    kernel = matern32.with_defaults(rho=scn.rho)()
    findings, seen = [], set()
    for pyramid in (True, False):
        groups = dsp.chart_launch_plans(
            chart, samples=scn.samples, dtype=storage, pyramid=pyramid,
            vmem_budget=(vmem_budget or dsp.VMEM_BUDGET_BYTES))
        for grp in groups:
            key = json.dumps(
                [grp["route"], str(grp["level"]),
                 [p.describe() for p in grp["plans"]]],
                sort_keys=True, default=str)
            if key in seen:
                continue
            seen.add(key)
            findings += verify_group(grp, chart, kernel,
                                     samples=scn.samples, storage=storage,
                                     vmem_budget=vmem_budget,
                                     semantic=semantic, scenario=scn.label)
    return findings


def verify_all(*, scenarios=None, vmem_budget=None,
               semantic: bool = True) -> list:
    """Verify every scenario cell; the ``verify`` CLI entry point."""
    findings = []
    for scn in (scenarios if scenarios is not None else SCENARIOS()):
        findings += verify_scenario(scn, vmem_budget=vmem_budget,
                                    semantic=semantic)
    return findings
