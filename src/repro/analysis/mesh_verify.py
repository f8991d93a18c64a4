"""Mesh-safety analyzer: static verification of the sharded layers (§17).

The §13 fingerprints and §14 launch verifier prove the single-device
kernel layer from its compile artifacts; this module applies the same
"prove it from the jaxpr, don't just sample it" discipline to the
distributed layer. Every ``shard_map``'d entry point — the
``DistributedICR`` sqrt apply, the ``GPFieldServer`` slab step in both
``shard="samples"`` and ``shard="chart"`` modes, and the PCG conditioning
matvec from ``solvers/gp_system.py`` — runs under ``check_vma=False``
(the 0.4.x shard_map shim disables jax's own replication checking), so
nothing at runtime verifies that the bodies reduce what their out_specs
claim to replicate. Four passes close that gap:

  **collective** (:func:`check_collectives`): a per-mesh-axis
  device-variance dataflow over the body jaxpr. Each body input starts
  varying along exactly the mesh axes its ``in_specs`` shard it over;
  ``axis_index`` introduces variance, ``psum``/``pmax``/``pmin``/
  ``all_gather`` clear it on the reduced axes, ``ppermute`` and ordinary
  compute propagate it. An output whose ``out_specs`` omit a mesh axis
  *claims replication* along it — if the value still carries variance
  there, the claim is unsound (under ``check_vma=False`` jax will happily
  emit one device's arbitrary answer). Axis names outside the mesh and
  redundant psums of already-replicated operands are flagged too.

  **determinism** (:func:`check_determinism`): the PR8 guarantee is that
  a replayed sample-sharded slab is *bit-identical* to the unfaulted run.
  The pass walks the entry jaxpr for unkeyed PRNG draws (a random-bits
  chain rooted in a constant instead of a traced seed), and — on
  replay-sensitive entries only — data-dependent control flow
  (``while``/``cond``), PRNG keys tainted by ``axis_index`` (draws that
  change when the mesh does), and any cross-device collective (reduction
  order and ring structure change across re-meshes). Chart-sharded
  serving promises fp-tolerance equality, not bit-identity, so its halo
  ``ppermute`` traffic is exempt.

  **remesh** (:func:`check_remesh` over :func:`local_dot_signatures`):
  abstract-eval the entry at ≥3 mesh sizes and prove the *local*
  ``dot_general``/conv shapes inside the shard_map bodies are invariant.
  Sample-sharded serving pins per-device rows at construction
  (``GPFieldServer._local_rows``) precisely so replayed slabs run the
  same local gemms on a shrunk mesh — full shape-multiset equality is
  required. Chart-sharded bodies and the RHS-sharded PCG matvec scale
  their spatial/batch extents with the ring by design; there the
  contraction extents (the refinement-matrix dimensions) must be
  invariant instead.

  **cachekey** (:func:`cachekey_audit`, :func:`plan_key_audit`): build
  the server under single-dimension config perturbations (θ, dtype
  policy, slab height, backend override, mesh, q-params), fingerprint
  everything that reaches the compiled executable (stored matrices,
  argument avals, traced jaxpr, routing plan), and require that two
  configs colliding on ``GPFieldServer._cache_key`` have identical
  artifacts — a collision with differing artifacts is a stale-cache
  hazard naming the uncovered input. ``dispatch.plan_cached`` gets a
  functional probe per keyword: perturbing any argument must never
  return the cached plan object.

Findings are structured :class:`MeshFinding` records; an empty list is a
pass. ``python -m repro.analysis shardcheck`` runs everything over the
serving matrix (samples/chart × tod/image/dust) and exits non-zero on
any finding; ``tools/update_fingerprints.py`` refuses to re-baseline
goldens while findings exist.
"""
from __future__ import annotations

import dataclasses
import hashlib
import re

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from .kernel_verify import _CALL_PRIMS, _callee, _child_jaxprs

__all__ = [
    "MeshFinding", "iter_shard_maps", "analyze_entry", "analyze_jaxpr",
    "check_collectives", "check_determinism", "check_remesh",
    "local_dot_signatures", "cachekey_audit", "plan_key_audit",
    "shardcheck_scenario", "shardcheck_all", "SERVING_SCENARIOS",
]

SERVING_SCENARIOS = ("tod", "image", "dust")

# collectives that *reduce* device variance on their named axes vs ones
# that merely move data around the ring (variance-preserving)
_REDUCING_COLLECTIVES = frozenset({"psum", "pmax", "pmin", "all_gather",
                                   "reduce_scatter", "all_to_all"})
_PERMUTING_COLLECTIVES = frozenset({"ppermute", "pshuffle"})
_COLLECTIVES = _REDUCING_COLLECTIVES | _PERMUTING_COLLECTIVES
# primitives that materialize random bits from a key/seed chain
_DRAW_PRIMS = frozenset({"random_bits", "threefry2x32"})


@dataclasses.dataclass(frozen=True)
class MeshFinding:
    """One mesh-safety violation: which pass, which entry point, where."""

    pass_name: str   # collective | determinism | remesh | cachekey
    entry: str       # e.g. "serve[samples]:tod"
    location: str    # jaxpr path (top/eqn3:pjit/eqn0:shard_map/...)
    severity: str    # error | warning
    message: str

    def __str__(self):
        return (f"[{self.pass_name}/{self.severity}] {self.entry} "
                f"{self.location}: {self.message}")

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)


# -- jaxpr plumbing --------------------------------------------------------------
def _inner_jaxpr(obj):
    """Unwrap ClosedJaxpr → Jaxpr (shard_map/pjit params carry either)."""
    return getattr(obj, "jaxpr", obj)


def iter_shard_maps(jaxpr, path: str = "top"):
    """Yield ``(eqn, path)`` for every shard_map equation, recursively."""
    jaxpr = _inner_jaxpr(jaxpr)
    for i, eqn in enumerate(jaxpr.eqns):
        here = f"{path}/eqn{i}:{eqn.primitive.name}"
        if eqn.primitive.name == "shard_map":
            yield eqn, here
        for sub in _child_jaxprs(eqn):
            yield from iter_shard_maps(sub, here)


def _axes_param(eqn) -> tuple:
    """The mesh-axis names a collective/axis_index equation names."""
    for key in ("axes", "axis_name", "axis_index_groups_axis"):
        if key in eqn.params and eqn.params[key] is not None:
            v = eqn.params[key]
            if isinstance(v, (tuple, list)):
                # psum axes may mix positional ints (vmap axes) with names
                return tuple(a for a in v if isinstance(a, str))
            return (v,) if isinstance(v, str) else ()
    return ()


def _names_axes(spec) -> frozenset:
    """Mesh axes a shard_map in_specs/out_specs entry (a PartitionSpec)
    shards over."""
    out = set()
    for axs in spec:
        if axs is None:
            continue
        axs = (axs,) if isinstance(axs, str) else axs
        out.update(axs)
    return frozenset(out)


# -- pass (a): collective soundness ----------------------------------------------
def _variance_walk(jaxpr, in_var, mesh_axes, entry, path, out):
    """Propagate per-mesh-axis device variance through a shard_map body.

    ``in_var`` is one frozenset of mesh-axis names per invar. Returns the
    variance sets of the body outputs. Control flow and ``pallas_call``
    are handled conservatively (variance in → variance out, never
    cleared), so a replication claim this walk accepts is genuinely
    reduction-backed.
    """
    from repro.compat import Literal

    jaxpr = _inner_jaxpr(jaxpr)
    env = {}
    for v, t in zip(jaxpr.invars, in_var):
        env[v] = frozenset(t)
    for v in jaxpr.constvars:
        env[v] = frozenset()

    def rd(a):
        return frozenset() if isinstance(a, Literal) else env.get(a, frozenset())

    for i, eqn in enumerate(jaxpr.eqns):
        name = eqn.primitive.name
        here = f"{path}/eqn{i}:{name}"
        ts = [rd(a) for a in eqn.invars]
        joined = frozenset().union(*ts) if ts else frozenset()

        if name == "axis_index":
            env[eqn.outvars[0]] = joined | frozenset(_axes_param(eqn))
            continue
        if name in _COLLECTIVES:
            named = frozenset(_axes_param(eqn))
            unknown = named - mesh_axes
            if unknown:
                out(MeshFinding(
                    "collective", entry, here, "error",
                    f"`{name}` names mesh axis/axes {sorted(unknown)} not "
                    f"in this shard_map's mesh {sorted(mesh_axes)}"))
            if name in _REDUCING_COLLECTIVES:
                if name == "psum" and not (joined & named):
                    out(MeshFinding(
                        "collective", entry, here, "warning",
                        f"redundant psum over {sorted(named)}: the operand "
                        "is already replicated on those axes (it multiplies "
                        "replicated values by the axis size)"))
                res = joined - named
            else:
                # a partial ppermute leaves ring-edge devices with zeros:
                # the result varies along the permuted axes even from a
                # replicated operand
                res = joined | (named & mesh_axes)
            for v in eqn.outvars:
                env[v] = res
            continue
        if name == "shard_map":
            # nested shard_map: treat as opaque compute over its operands
            for v in eqn.outvars:
                env[v] = joined
            continue
        if name in _CALL_PRIMS:
            sub = _callee(eqn.params)
            if sub is not None and len(sub.invars) == len(ts):
                outs = _variance_walk(sub, ts, mesh_axes, entry, here, out)
            else:
                outs = [joined] * len(eqn.outvars)
            for v, t in zip(eqn.outvars, outs):
                env[v] = t
            continue
        # pallas_call, scan, while, cond, and every ordinary primitive:
        # any input variance reaches every output
        for v in eqn.outvars:
            env[v] = joined
    return [rd(v) for v in jaxpr.outvars]


def check_collectives(eqn, *, entry: str, path: str) -> list:
    """Pass (a) over one shard_map equation: replication claims must be
    backed by a reducing collective on every claimed axis."""
    findings = []
    mesh = eqn.params["mesh"]
    mesh_axes = frozenset(mesh.axis_names)
    in_specs = eqn.params["in_specs"]
    out_specs = eqn.params["out_specs"]
    body = _inner_jaxpr(eqn.params["jaxpr"])
    in_var = [_names_axes(n) for n in in_specs]
    out_var = _variance_walk(body, in_var, mesh_axes, entry, path,
                             findings.append)
    for i, (names, var) in enumerate(zip(out_specs, out_var)):
        resid = (var & mesh_axes) - _names_axes(names)
        if resid:
            findings.append(MeshFinding(
                "collective", entry, f"{path}/out{i}", "error",
                f"out_specs claim replication over mesh axis/axes "
                f"{sorted(resid)} but the output is device-varying there "
                "(no psum/all_gather on its path; under check_vma=False "
                "this silently serves one device's arbitrary shard)"))
    return findings


# -- pass (b): determinism -------------------------------------------------------
def _det_walk(jaxpr, in_taints, entry, path, out, *, replay: bool):
    """Taint walk for the determinism pass.

    Taint per var is ``(derived, meshy)``: *derived* = reachable from a
    body/entry input (a traced seed is derived; a baked-in PRNGKey(0) is
    not), *meshy* = influenced by ``axis_index`` or collective traffic.
    """
    from repro.compat import Literal

    jaxpr = _inner_jaxpr(jaxpr)
    env = {}
    for v, t in zip(jaxpr.invars, in_taints):
        env[v] = t
    for v in jaxpr.constvars:
        env[v] = (False, False)

    def rd(a):
        return (False, False) if isinstance(a, Literal) \
            else env.get(a, (False, False))

    for i, eqn in enumerate(jaxpr.eqns):
        name = eqn.primitive.name
        here = f"{path}/eqn{i}:{name}"
        ts = [rd(a) for a in eqn.invars]
        derived = any(d for d, _ in ts)
        meshy = any(m for _, m in ts)

        if name == "axis_index":
            env[eqn.outvars[0]] = (True, True)
            continue
        if name in ("while", "cond") and replay:
            out(MeshFinding(
                "determinism", entry, here, "error",
                f"data-dependent control flow `{name}` on the "
                "bit-identical-replay path: iteration counts/branches may "
                "differ across re-meshed replays"))
        if name in _COLLECTIVES and replay:
            out(MeshFinding(
                "determinism", entry, here, "error",
                f"cross-device collective `{name}` feeds a replay-"
                "sensitive entry: reduction order and ring structure "
                "change when the mesh does, breaking bit-identical replay"))
            meshy = True
        if name in _DRAW_PRIMS:
            if not derived:
                out(MeshFinding(
                    "determinism", entry, here, "error",
                    f"unkeyed PRNG draw (`{name}` rooted in a constant "
                    "key, not a traced seed): every slab redraws the same "
                    "noise and replay cannot re-key it per request"))
            if meshy and replay:
                out(MeshFinding(
                    "determinism", entry, here, "error",
                    f"mesh-dependent PRNG draw (`{name}` keyed through "
                    "axis_index/collectives): replayed draws change when "
                    "the mesh shrinks"))
        if name == "shard_map":
            sub = _inner_jaxpr(eqn.params["jaxpr"])
            if len(sub.invars) == len(ts):
                outs = _det_walk(sub, ts, entry, here, out, replay=replay)
            else:
                outs = [(derived, meshy)] * len(eqn.outvars)
            for v, t in zip(eqn.outvars, outs):
                env[v] = t
            continue
        if name in _CALL_PRIMS:
            sub = _callee(eqn.params)
            if sub is not None and len(sub.invars) == len(ts):
                outs = _det_walk(sub, ts, entry, here, out, replay=replay)
            else:
                outs = [(derived, meshy)] * len(eqn.outvars)
            for v, t in zip(eqn.outvars, outs):
                env[v] = t
            continue
        for v in eqn.outvars:
            env[v] = (derived, meshy)
    return [rd(v) for v in jaxpr.outvars]


def check_determinism(closed_jaxpr, *, entry: str,
                      replay_sensitive: bool) -> list:
    """Pass (b) over one entry jaxpr (recurses into shard_map bodies)."""
    findings = []
    jx = _inner_jaxpr(closed_jaxpr)
    _det_walk(jx, [(True, False)] * len(jx.invars), entry, "top",
              findings.append, replay=replay_sensitive)
    return findings


# -- pass (c): remesh invariance -------------------------------------------------
def _collect_dots(jaxpr, sigs, *, contract_only: bool):
    jaxpr = _inner_jaxpr(jaxpr)
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if name == "dot_general":
            lhs = tuple(eqn.invars[0].aval.shape)
            rhs = tuple(eqn.invars[1].aval.shape)
            (lc, rc), _ = eqn.params["dimension_numbers"]
            if contract_only:
                sig = ("dot",
                       tuple(lhs[i] for i in lc), tuple(rhs[j] for j in rc))
            else:
                sig = ("dot", lhs, rhs, eqn.params["dimension_numbers"])
            sigs[sig] = sigs.get(sig, 0) + 1
        elif name == "conv_general_dilated":
            lhs = tuple(eqn.invars[0].aval.shape)
            rhs = tuple(eqn.invars[1].aval.shape)
            sig = ("conv", rhs) if contract_only else ("conv", lhs, rhs)
            sigs[sig] = sigs.get(sig, 0) + 1
        for sub in _child_jaxprs(eqn):
            _collect_dots(sub, sigs, contract_only=contract_only)


def local_dot_signatures(closed_jaxpr, *, contract_only: bool = False) -> dict:
    """Multiset of dot_general/conv shape signatures *inside* the
    shard_map bodies of an entry jaxpr — the per-device local gemms.

    ``contract_only`` reduces each signature to its contraction extents
    (the refinement-matrix dimensions), the invariant for bodies whose
    spatial/batch extents legitimately scale with the ring size.
    """
    sigs: dict = {}
    for eqn, _ in iter_shard_maps(closed_jaxpr):
        _collect_dots(eqn.params["jaxpr"], sigs, contract_only=contract_only)
    return sigs


def check_remesh(entry: str, sigs_by_size: dict, *,
                 what: str = "local dot_general/conv shapes") -> list:
    """Pass (c): the signature multisets must agree across mesh sizes."""
    findings = []
    sizes = sorted(sigs_by_size)
    if len(sizes) < 2:
        return findings
    base_n = sizes[0]
    base = sigs_by_size[base_n]
    for n in sizes[1:]:
        cur = sigs_by_size[n]
        if cur == base:
            continue
        gone = {s: c for s, c in base.items() if cur.get(s) != c}
        new = {s: c for s, c in cur.items() if base.get(s) != c}
        sample = list(gone.items())[:2] + list(new.items())[:2]
        findings.append(MeshFinding(
            "remesh", entry, f"mesh[{base_n}]-vs-mesh[{n}]", "error",
            f"{what} depend on the mesh size: {len(gone)} signature(s) "
            f"changed between {base_n} and {n} device(s) (e.g. {sample}); "
            "per-device work must be pinned (the local_rows invariant) so "
            "replayed slabs run identical gemms after an elastic shrink"))
    return findings


# -- passes (a)+(b) driver over one entry ----------------------------------------
def analyze_jaxpr(closed_jaxpr, *, entry: str,
                  replay_sensitive: bool = False) -> list:
    """Collective + determinism passes over one traced entry point."""
    findings = []
    shard_maps = list(iter_shard_maps(closed_jaxpr))
    for eqn, path in shard_maps:
        findings += check_collectives(eqn, entry=entry, path=path)
    findings += check_determinism(closed_jaxpr, entry=entry,
                                  replay_sensitive=replay_sensitive)
    return findings


def analyze_entry(fn, args, *, entry: str,
                  replay_sensitive: bool = False) -> list:
    """Trace ``fn(*args)`` and run the collective + determinism passes."""
    return analyze_jaxpr(jax.make_jaxpr(fn)(*args), entry=entry,
                         replay_sensitive=replay_sensitive)


# -- pass (d): cache-key soundness -----------------------------------------------
def _digest(text: str) -> str:
    return hashlib.sha256(text.encode()).hexdigest()[:16]


def _mats_digest(mats) -> str:
    parts = []
    for leaf in jax.tree.leaves(mats):
        a = np.asarray(leaf)
        parts.append(f"{a.shape}:{a.dtype}:"
                     f"{hashlib.sha256(a.tobytes()).hexdigest()[:12]}")
    return _digest("|".join(parts))


def _artifact_fingerprint(srv) -> dict:
    """Per-component digests of everything reaching the compiled slab
    executable *except* the q-parameters (mean/std ride as jit arguments
    by design — swapping them is exactly what the cache is for)."""
    from repro.launch.serve_gp import _canonical_key

    e = srv._entry
    args = srv._slab_args(e, [])
    avals = jax.tree.map(lambda x: f"{jnp.shape(x)}:{jnp.asarray(x).dtype}",
                         list(args[3:]))  # seeds/rows/flags/xi overrides
    jx = jax.make_jaxpr(e["fn"])(*args)
    # custom_vjp residual-thunk params print with memory addresses;
    # two traces of the same program must digest identically
    jx_text = re.sub(r"0x[0-9a-f]+", "0x", str(jx))
    return {
        "mats": _mats_digest(e["mats"]),
        "arg_avals": _digest(repr(avals) + repr(e["capacity"])),
        "jaxpr": _digest(jx_text),
        "plan": _digest(_canonical_key(tuple(map(tuple, (
            sorted(p.items()) for p in _plan_rows(e["plan"])))))),
    }


def _plan_rows(plan) -> list:
    rows = []
    for level in plan:
        rows.append({str(k): repr(v) for k, v in sorted(level.items())})
    return rows


def _mk_server(name: str, *, mesh=None, shard: str = "samples",
               quick: bool = True, slab: int = 4, rho=None, policy=None,
               seed: int = 0, server_cls=None):
    from repro.launch.serve_gp import (GPFieldServer, SCENARIOS as RHO,
                                       demo_posterior, scenario_chart)

    chart = scenario_chart(name, quick=quick)
    post = demo_posterior(chart, RHO[name] if rho is None else rho,
                          dtype_policy=policy, seed=seed)
    cls = GPFieldServer if server_cls is None else server_cls
    return cls(post, slab=slab, mesh=mesh, shard=shard)


def cachekey_audit(name: str, *, quick: bool = True, slab: int = 4,
                   mesh=None, devices=None, server_cls=None,
                   backend: str = "reference") -> list:
    """Pass (d): single-dimension config perturbations must never collide
    on ``_cache_key`` while producing different compiled artifacts.

    The ``seed`` variant is the deliberate control: same config, new
    q-parameters — it *must* collide with the base key AND carry an
    identical artifact (q-params are jit arguments, not baked in).
    """
    from repro.analysis.scenarios import pinned_backend
    from repro.launch.serve_gp import SCENARIOS as RHO, _canonical_key

    rho = RHO[name]
    base = dict(quick=quick, slab=slab, mesh=mesh, rho=rho, policy=None,
                seed=0, server_cls=server_cls)
    variants = {
        "base": dict(base),
        "seed": {**base, "seed": 1},
        "theta": {**base, "rho": 2.0 * rho},
        "policy": {**base, "policy": "bf16"},
        "slab": {**base, "slab": slab + 4},
    }
    backends = {label: backend for label in variants}
    variants["backend"] = dict(base)
    backends["backend"] = "interpret" if backend != "interpret" \
        else "reference"
    if mesh is not None and int(np.asarray(mesh.devices).size) > 1:
        devs = list(np.asarray(mesh.devices).flat)
        variants["mesh"] = {**base, "mesh": Mesh(
            np.asarray(devs[:len(devs) // 2]), mesh.axis_names)}
        backends["mesh"] = backend

    entry = f"serve[samples]:{name}"
    findings = []
    groups: dict = {}
    for label, cfg in variants.items():
        with pinned_backend(backends[label]):
            srv = _mk_server(name, shard="samples", **cfg)
            key = _canonical_key(srv._cache_key(srv.posterior))
            fp = _artifact_fingerprint(srv)
        groups.setdefault(key, []).append((label, fp))

    for key, members in groups.items():
        if len(members) < 2:
            continue
        base_label, base_fp = members[0]
        for label, fp in members[1:]:
            diff = sorted(k for k in base_fp if fp[k] != base_fp[k])
            if diff:
                findings.append(MeshFinding(
                    "cachekey", entry, f"variant[{label}]", "error",
                    f"config variants {base_label!r} and {label!r} collide "
                    f"on _cache_key but their compiled artifacts differ in "
                    f"{diff}: that input reaches the executable without "
                    "being keyed (stale-cache hazard on re-fit/re-mesh)"))
    return findings


def plan_key_audit(name: str, *, quick: bool = True,
                   entry: str | None = None) -> list:
    """Functional probe of ``dispatch.plan_cached`` key coverage: for each
    keyword, a perturbed call must never return the *cached object* of the
    base call — identity here means the key dropped that input."""
    from repro.analysis.scenarios import pinned_backend
    from repro.kernels import dispatch
    from repro.launch.serve_gp import scenario_chart

    chart = scenario_chart(name, quick=quick)
    entry = entry or f"plan_cached:{name}"
    base = dict(have_axis_mats=False, samples=4, dtype=None, pyramid=True,
                vmem_budget=dispatch.VMEM_BUDGET_BYTES,
                mesh_key=("shardcheck", 0))
    perturbed = dict(have_axis_mats=True, samples=8, dtype=jnp.bfloat16,
                     pyramid=False,
                     vmem_budget=dispatch.VMEM_BUDGET_BYTES // 2,
                     mesh_key=("shardcheck", 1))
    findings = []
    with pinned_backend("reference"):
        p0 = dispatch.plan_cached(chart, **base)
        for kw, val in perturbed.items():
            p1 = dispatch.plan_cached(chart, **{**base, kw: val})
            if p1 is p0:
                findings.append(MeshFinding(
                    "cachekey", entry, f"kwarg[{kw}]", "error",
                    f"plan_cached returned the cached plan object for a "
                    f"different {kw}={val!r}: the plan-cache key does not "
                    "cover that input"))
        with pinned_backend("interpret"):
            p1 = dispatch.plan_cached(chart, **base)
        if p1 is p0:
            findings.append(MeshFinding(
                "cachekey", entry, "kwarg[backend]", "error",
                "plan_cached returned the cached plan object under a "
                "different REPRO_BACKEND: the key does not cover the "
                "effective backend"))
    return findings


# -- entry-point drivers ---------------------------------------------------------
def _mesh_sizes(n_dev: int) -> list:
    """Mesh sizes to sweep: the full device set plus halvings (≥3 sizes
    when the devices allow — 8 → [8, 4, 2])."""
    sizes = []
    n = n_dev
    while n >= 1 and len(sizes) < 3:
        sizes.append(n)
        n //= 2
    return sizes


def _mesh(devices, k: int, axis: str = "data") -> Mesh:
    return Mesh(np.asarray(devices[:k]), (axis,))


def _chart_ring_sizes(icr, devices, sizes) -> list:
    """Ring sizes over which this chart's family counts are shardable."""
    from repro.core.distributed import DistributedICR

    out = []
    for k in sizes:
        try:
            DistributedICR(icr=icr, mesh=_mesh(devices, k, "ring"),
                           axis_names=("ring",)).first_sharded_level()
        except ValueError:
            continue
        out.append(k)
    return out


def shardcheck_scenario(name: str, *, quick: bool = True, slab: int = 4,
                        devices=None, backend: str = "reference",
                        checked: list | None = None) -> list:
    """All four passes over every shard_map'd entry point of one serving
    scenario. ``checked`` (optional accumulator) collects the entry
    labels actually analyzed, for the CLI report."""
    from repro.analysis.scenarios import pinned_backend
    from repro.core.distributed import DistributedICR
    from repro.solvers.gp_system import build_condition_system, obs_operator

    devices = list(devices if devices is not None else jax.devices())
    sizes = _mesh_sizes(len(devices))
    checked = checked if checked is not None else []
    findings = []

    with pinned_backend(backend):
        # ---- serve[samples]: one server, re-meshed across sizes (the
        # pinned-local_rows path an elastic shrink actually takes)
        label = f"serve[samples]:{name}"
        srv = _mk_server(name, mesh=_mesh(devices, sizes[0]),
                         shard="samples", quick=quick, slab=slab)
        sigs = {}
        for k in sizes:
            srv.mesh = _mesh(devices, k)
            srv.set_posterior(srv.posterior)
            jx = jax.make_jaxpr(srv._entry["fn"])(
                *srv._slab_args(srv._entry, []))
            if k == sizes[0]:
                findings += analyze_jaxpr(jx, entry=label,
                                          replay_sensitive=True)
            sigs[k] = local_dot_signatures(jx)
        findings += check_remesh(label, sigs)
        checked.append(label)

        icr = srv.posterior.icr

        # ---- serve[chart]: fresh server per feasible ring size; the
        # local block scales with the ring, so the invariant is the
        # contraction extents, not the full local shapes
        ring_sizes = _chart_ring_sizes(icr, devices, sizes)
        if ring_sizes:
            label = f"serve[chart]:{name}"
            sigs = {}
            for k in ring_sizes:
                csrv = _mk_server(name, mesh=_mesh(devices, k, "ring"),
                                  shard="chart", quick=quick, slab=slab)
                jx = jax.make_jaxpr(csrv._entry["fn"])(
                    *csrv._slab_args(csrv._entry, []))
                if k == ring_sizes[0]:
                    findings += analyze_jaxpr(jx, entry=label,
                                              replay_sensitive=False)
                sigs[k] = local_dot_signatures(jx, contract_only=True)
            findings += check_remesh(
                label, sigs, what="local contraction extents")
            checked.append(label)

            # ---- DistributedICR.apply_sqrt (abstract-eval only)
            label = f"dist_icr:{name}"
            axes = icr.use_pallas and icr.chart.ndim > 1
            mats_s = jax.eval_shape(
                lambda: icr.matrices(None, joint=not axes, axes=axes))
            sigs = {}
            for k in ring_sizes:
                dist = DistributedICR(icr=icr,
                                      mesh=_mesh(devices, k, "ring"),
                                      axis_names=("ring",))
                xi_s = [jax.ShapeDtypeStruct(s, jnp.float32)
                        for s in dist.xi_structure()]
                jx = jax.make_jaxpr(dist.apply_sqrt)(mats_s, xi_s)
                if k == ring_sizes[0]:
                    findings += analyze_jaxpr(jx, entry=label,
                                              replay_sensitive=False)
                sigs[k] = local_dot_signatures(jx, contract_only=True)
            findings += check_remesh(
                label, sigs, what="local contraction extents")
            checked.append(label)

        # ---- PCG conditioning matvec: RHS-sharded over the mesh
        label = f"pcg_matvec:{name}"
        n_pix = int(np.prod(icr.chart.final_shape))
        op = obs_operator(icr, obs_idx=np.arange(0, n_pix, 2))
        mats = icr.matrices_cached(None)
        rows = max(4, sizes[0])
        v_s = jax.ShapeDtypeStruct((rows, op.n_obs), jnp.float32)
        sigs = {}
        for k in sizes:
            sys_k = build_condition_system(
                icr, op, 0.05 ** 2, mats=mats, mesh=_mesh(devices, k),
                use_precond=False)
            jx = jax.make_jaxpr(sys_k.matvec)(v_s)
            if k == sizes[0]:
                findings += analyze_jaxpr(jx, entry=label,
                                          replay_sensitive=False)
            sigs[k] = local_dot_signatures(jx, contract_only=True)
        findings += check_remesh(label, sigs,
                                 what="local contraction extents")
        checked.append(label)

    # ---- cache-key soundness (pins its own backend per variant)
    findings += cachekey_audit(name, quick=quick, slab=slab,
                               mesh=_mesh(devices, sizes[0]),
                               backend=backend)
    findings += plan_key_audit(name, quick=quick)
    checked.append(f"cachekey:{name}")
    return findings


def shardcheck_all(names=SERVING_SCENARIOS, *, quick: bool = True,
                   slab: int = 4, devices=None,
                   checked: list | None = None) -> list:
    """The full shardcheck sweep (the CI ``static-analysis`` step)."""
    findings = []
    for name in names:
        findings += shardcheck_scenario(name, quick=quick, slab=slab,
                                        devices=devices, checked=checked)
    return findings
