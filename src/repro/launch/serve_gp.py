"""Batched GP posterior field server (DESIGN.md §12, §15).

The `launch.serve` BatchedServer pattern applied to the GP side of the
repo: clients submit posterior-sample and predictive-moment requests
against a fitted ICR posterior (`core.vi.Posterior` — a MAP ξ̂ or ADVI
`(mean, log_std)` export), and the server

  * packs heterogeneous requests into fixed-size **sample slabs** executed
    through `ICR.apply_sqrt_batch` — the native §10 sample-block path, so
    the refinement matrices are fetched once per VMEM tile for the whole
    slab and the work is bandwidth-bound on the field, not the matrices;
  * computes predictive mean/std by **streaming Welford accumulation**
    over slabs (Chan parallel merge per slab — no request ever needs its
    full MC budget resident at once);
  * never recompiles or rebuilds structure for repeat traffic: the
    executable cache is keyed on (chart geometry, θ, dtype policy, mesh)
    and holds the matrices (`ICR.matrices_cached`), the routing decision
    (`dispatch.plan_cached`) and the jitted slab executable.

Per-row excitation noise is keyed by (request seed, row index) only —
`fold_in(PRNGKey(seed), row)` — so a request's draws are independent of
how they were packed **and of the mesh they ran on**: a packed
heterogeneous batch reproduces the per-request loop exactly (the
slab-parity test pins this at 1e-5), and a slab replayed after a device
loss reproduces the unfaulted run bit-for-bit (tests/test_chaos.py).

Mesh serving (DESIGN.md §15): pass ``mesh=`` to shard slabs over devices.
``shard="samples"`` runs data-parallel over the sample axis through
`shard_map` (the axis the PR3 kernels tile innermost); ``shard="chart"``
routes each row through the `DistributedICR` halo-exchange body for
fields that exceed one device. On a `DeviceLossError` the server runs
detect → remesh (``elastic.shrink_mesh`` + ``remesh_report`` with
structured degradation records) → rewarm (background compile on the
surviving mesh) → replay (the in-flight slab re-executes; same
(seed, row) keys ⇒ identical results). When the mesh collapses to one
device it degrades to the single-device path (pallas on TPU, jnp
reference elsewhere) and keeps serving.

Run:  PYTHONPATH=src python -m repro.launch.serve_gp [--scenario dust]
"""
from __future__ import annotations

import argparse
import dataclasses
import hashlib
import re
import threading
import time
from typing import Callable, List, Optional

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.compat import shard_map
from repro.core.vi import Posterior
from repro.distributed import elastic
from repro.distributed.fault import DeviceLossError, ServingFaultSupervisor
from repro.kernels import dispatch
from repro.launch.spans import span

_PAD_ROW = 2**30  # padding rows index past every request's eps stream
# an HLO instruction line's name and the op_name of its metadata
_HLO_OP_NAME = re.compile(
    r'^\s*(?:ROOT\s+)?%?([\w.\-]+) = .*?metadata=\{[^}]*?op_name="([^"]*)"',
    re.M)
# a named scope of ours in an op_name path, also inside a transformation's
# wrapper (``vmap(serve.draw)``)
_SCOPE = re.compile(r"(?<![\w.])((?:icr|serve)\.\w+)")


@dataclasses.dataclass(frozen=True)
class RequestError:
    """Structured per-request admission/serving error.

    Truthy (so existing ``if req.error`` call sites keep working), with a
    stable machine-readable ``code`` — clients branch on the code, humans
    read the message.
    """

    code: str
    message: str

    def __str__(self) -> str:
        return f"[{self.code}] {self.message}"

    def __bool__(self) -> bool:
        return True


@dataclasses.dataclass
class GPRequest:
    """One client request against the served posterior.

    kind="sample": return ``n`` posterior field draws (in ``fields``).
    kind="moments": MC predictive mean/std over an ``n``-draw budget
    (in ``mean``/``std``; the draws themselves are never retained).

    ``xi`` optionally replaces the posterior mean for this request's rows
    (a client-supplied excitation, e.g. a conditioning point estimate):
    leaf shapes must match the served chart's ``xi_shapes()`` and all
    values must be finite — both are checked at admission, so one bad
    request is rejected with a structured error instead of NaN-poisoning
    the slab it would have been packed into. ``theta`` optionally pins the
    hyperparameters the client expects to be served; a mismatch with the
    active posterior is an admission error, not silent wrong answers.
    """

    kind: str
    n: int
    seed: int = 0
    xi: Optional[list] = None
    theta: Optional[dict] = None
    # kind="condition" inputs: observed values plus exactly one of
    # on-grid flat indices (obs_idx) or off-grid 1-D locations (x_obs);
    # noise_std is the observation noise σ. ``n`` is the Matheron
    # pathwise-sample budget for the predictive std (n >= 2 for a
    # non-trivial std; the mean is exact either way).
    y: Optional[np.ndarray] = None
    obs_idx: Optional[np.ndarray] = None
    x_obs: Optional[np.ndarray] = None
    noise_std: float = 0.05
    done: bool = False
    error: Optional[object] = None  # RequestError (or legacy str)
    fields: list = dataclasses.field(default_factory=list)
    mean: Optional[np.ndarray] = None
    std: Optional[np.ndarray] = None
    report: Optional[object] = None  # solvers.SolveReport (condition)
    # stamped by the server: an id shared by the request's spans, and the
    # perf_counter times it was admitted, had its first row packed and
    # was done (admitted_s and started_s are their step's start)
    rid: Optional[int] = None
    admitted_s: Optional[float] = None
    started_s: Optional[float] = None
    finished_s: Optional[float] = None
    # internal: rows drawn so far (the per-request eps stream index),
    # the streaming Welford state (count, running mean, running M2),
    # and whether admission validation already ran
    _next_row: int = 0
    _wcount: int = 0
    _wmean: Optional[np.ndarray] = None
    _wm2: Optional[np.ndarray] = None
    _admitted: bool = False


def _canonical_key(x) -> str:
    """Deterministic printable form of an executable-cache key component.

    ``repr`` alone is not reproducible across processes: charts carry
    ``phi_inv`` function objects (repr embeds a memory address) and θ
    fingerprints carry raw bytes. Functions canonicalize to their
    qualified name, bytes to a content hash, dataclasses (Chart,
    DtypePolicy) recurse over their fields — so two servers built from
    equal configs print (and digest) identically in any process.
    """
    if isinstance(x, tuple):
        return "(" + ",".join(_canonical_key(v) for v in x) + ")"
    if isinstance(x, bytes):
        return "bytes<sha256:" + hashlib.sha256(x).hexdigest()[:12] + ">"
    if dataclasses.is_dataclass(x) and not isinstance(x, type):
        fields = ",".join(f"{f.name}={_canonical_key(getattr(x, f.name))}"
                          for f in dataclasses.fields(x))
        return f"{type(x).__name__}({fields})"
    if callable(x) and hasattr(x, "__qualname__"):
        return f"fn:{getattr(x, '__module__', '?')}.{x.__qualname__}"
    return repr(x)


def _slab_callable(make):
    """The slab step as one callable over its two jitted variants — with
    and without per-row ξ overrides (``xi_rows`` None) — each built by
    ``make(with_xi)`` on first use."""
    variants = {}

    def variant(with_xi: bool):
        if with_xi not in variants:
            variants[with_xi] = make(with_xi)
        return variants[with_xi]

    def fn(*args):
        return variant(args[-1] is not None)(*args)

    fn.variant = variant
    return fn


def _welford_merge(count, m, m2, batch: np.ndarray):
    """Chan et al. parallel merge of a k-sample batch into (count, m, m2)."""
    k = batch.shape[0]
    bm = batch.mean(axis=0)
    dev = batch - bm  # one batch-sized temporary: fields can be 10^8 points
    np.square(dev, out=dev)
    bm2 = dev.sum(axis=0)
    del dev
    if count == 0:
        return k, bm, bm2
    tot = count + k
    delta = bm - m
    m = m + delta * (k / tot)
    m2 = m2 + bm2 + delta**2 * (count * k / tot)
    return tot, m, m2


def _all_finite(x) -> bool:
    return bool(np.isfinite(np.asarray(x, np.float64)).all())


@jax.jit
def _device_leaves_finite(leaves) -> jax.Array:
    """Every device leaf finite, checked where the leaves live (no host
    copy of a 10^8-point posterior) in one program per tree structure."""
    return jnp.all(jnp.stack([jnp.isfinite(x).all() for x in leaves]))


class GPFieldServer:
    """Continuous-batching server over one (swappable) fitted Posterior.

    ``slab`` is the fixed sample-slab height: every step draws one
    fixed-shape batch of posterior fields through one jitted executable —
    static shapes, so repeat traffic never retraces. Rows are assigned to
    queued requests greedily in queue order; short steps pad with
    throwaway rows (their keys index past every request's stream).

    ``mesh`` (optional) shards execution. ``shard="samples"``: the slab is
    rounded up to a multiple of the mesh size (the *capacity*) and split
    over all mesh axes via `shard_map` — each device draws and refines its
    own rows. ``shard="chart"``: rows stay whole but each field is
    spatially decomposed through the `DistributedICR` halo-exchange body.
    The executable cache key includes the mesh fingerprint, so an elastic
    re-mesh is always a deliberate miss, never a stale executable.
    """

    def __init__(self, posterior: Posterior, slab: int = 8,
                 max_cached: int = 8, mesh=None, shard: str = "samples",
                 supervisor: Optional[ServingFaultSupervisor] = None,
                 fault_injector: Optional[Callable] = None,
                 ckpt_root: Optional[str] = None,
                 solver_checkpoint_every: int = 8,
                 solver_config=None):
        if shard not in ("samples", "chart"):
            raise ValueError(f"shard={shard!r}: expected 'samples' or "
                             "'chart'")
        self.slab = int(slab)
        self.mesh = mesh
        self.shard = shard
        # per-device rows are pinned at construction and survive re-meshes:
        # a replayed slab must run the *same local gemm shapes* on the
        # shrunk mesh, else batch-size-dependent rounding breaks the
        # bit-identical replay guarantee (capacity shrinks with the mesh,
        # local work per device stays constant)
        n0 = (int(np.asarray(mesh.devices).size)
              if mesh is not None and shard == "samples" else 1)
        self._local_rows = -(-self.slab // n0)
        self.supervisor = supervisor or ServingFaultSupervisor()
        # test/chaos hook: called once per slab attempt with the server;
        # may raise DeviceLossError (kill), sleep (straggler), or no-op
        self.fault_injector = fault_injector
        # (key -> entry) executable cache, LRU-bounded: a long-running
        # server periodically re-fit at new θ must not pin one matrices
        # set + compiled executable per historical θ forever
        self.max_cached = int(max_cached)
        self._exec: dict = {}
        self.cache_hits = 0
        self.cache_misses = 0
        self.slabs_run = 0
        self.slabs_attempted = 0  # execution attempts incl. faulted/retried
        self.rows_served = 0      # non-padding rows (posterior draws)
        self.fields_delivered = 0  # arrays handed back to clients
        self.replans = 0           # device-loss re-mesh events
        self.replayed_slabs = 0    # in-flight slabs re-executed after loss
        self.dead_devices: set = set()
        self.degradations: list = []  # elastic.Degradation records
        self.last_recovery_s: Optional[float] = None  # fault -> first slab
        self._phases: dict = {}  # span name -> (seconds, count): spans.span
        self._rids = 0             # ids handed out at admission
        self.requests_started = 0  # requests with a packed row
        self.queue_wait_s = 0.0    # sum of started_s - admitted_s
        # data-conditioned solves (kind="condition", DESIGN.md §16)
        self.ckpt_root = ckpt_root
        self.solver_checkpoint_every = int(solver_checkpoint_every)
        self.solver_config = solver_config
        self.condition_requests = 0
        self.condition_rhs = 0       # real (unpadded) RHS columns solved
        self.solve_segments = 0      # CG segment attempts (chaos hook)
        self.solve_reports: list = []  # last few SolveReports
        self._cond_cache: dict = {}
        self._cond_seq = 0
        self.posterior = None
        self.set_posterior(posterior)

    # -- mesh geometry ---------------------------------------------------------
    def _n_shards(self) -> int:
        """Sample-axis parallelism: mesh size in "samples" mode, else 1."""
        if self.mesh is None or self.shard != "samples":
            return 1
        return int(np.asarray(self.mesh.devices).size)

    @property
    def capacity(self) -> int:
        """Rows per executed slab. Unsharded / chart-sharded: the slab
        height. Sample-sharded: ``local_rows * n_dev`` with the per-device
        ``local_rows`` pinned at construction — on the full mesh this is
        ``slab`` rounded up to divide evenly; after an elastic shrink the
        capacity contracts with the mesh (local shapes never change)."""
        n = self._n_shards()
        return self.slab if n == 1 else self._local_rows * n

    def _mesh_key(self):
        """Hashable mesh fingerprint for the executable-cache key: shard
        mode, axis names, mesh shape, and the exact device set — so a
        re-mesh (even to an equal-size mesh on different devices) is a
        deliberate miss, never a stale executable."""
        if self.mesh is None:
            return None
        devs = np.asarray(self.mesh.devices)
        return (self.shard, tuple(self.mesh.axis_names),
                tuple(int(s) for s in devs.shape),
                tuple((int(d.id), str(d.platform)) for d in devs.flat))

    def _mesh_desc(self) -> str:
        """Printable mesh dimension for fingerprints/metrics."""
        if self.mesh is None:
            return "unsharded"
        devs = np.asarray(self.mesh.devices)
        shape = "x".join(str(int(s)) for s in devs.shape)
        return f"{self.shard}:{shape}:{','.join(self.mesh.axis_names)}"

    @property
    def serving_mode(self) -> str:
        """Where on the degradation ladder this server executes: sharded
        pallas → single-device pallas → jnp reference (DESIGN.md §15)."""
        return self._mode_for(self.posterior.icr)

    def _mode_for(self, icr) -> str:
        tier = "single" if self.mesh is None else f"sharded-{self.shard}"
        backend = (dispatch.select_backend() if icr.use_pallas
                   else dispatch.require_reference_allowed())
        return f"{tier}:{backend}"

    # -- executable cache ------------------------------------------------------
    def _cache_key(self, post: Posterior):
        icr = post.icr
        tkey = icr._theta_key(post.theta)
        if tkey is None:
            raise ValueError("serving requires concrete (untraced) theta")
        # the kernel must be fingerprinted too: θ is often baked into the
        # kernel's defaults (with_defaults) with theta=None, and two such
        # posteriors must not collide on an equal chart. Kernel.default_theta
        # is a dict (unhashable), so flatten it.
        kern = icr.kernel
        kkey = (kern.fn, kern.name,
                tuple(sorted((k, float(v))
                             for k, v in kern.default_theta.items())))
        # routing flags, the effective backend and the mesh belong in the
        # key: an equal-chart/θ/policy ICR with a different executor config
        # (a REPRO_BACKEND flip, or a resized/re-homed mesh after an
        # elastic re-plan) must not be served the cached executable
        return (icr.chart, kkey, icr.jitter, tkey, icr.policy,
                icr.use_pallas, icr.use_pyramid,
                dispatch.select_backend(), self.slab, self._mesh_key())

    def _validate_posterior(self, post: Posterior):
        """A poisoned fit can never be installed: non-finite θ or
        q-parameters would NaN every slab for every client."""
        # std() rather than log_std: log_std = -inf is a legitimate delta
        # posterior (sigma = 0), but NaN or +inf sigma poisons every slab
        for name, leaves in (("theta", list((post.theta or {}).values())),
                             ("mean", list(post.mean)),
                             ("std", list(post.std()))):
            dev = [x for x in leaves if isinstance(x, jax.Array)]
            host = [x for x in leaves if not isinstance(x, jax.Array)]
            if (dev and not bool(_device_leaves_finite(dev))) \
                    or not all(_all_finite(x) for x in host):
                raise ValueError(
                    f"posterior rejected: non-finite values in {name}")

    def set_posterior(self, post: Posterior, *, rewarm: bool = False):
        """Point the server at a (new) fit. Same (chart geometry, θ, dtype
        policy, mesh) ⇒ cache hit: the matrices, plan and compiled
        executable are reused even across re-fits (only the q-parameters
        swap); anything else is a miss and builds a fresh entry.

        ``rewarm=True`` (the fault-recovery path) compiles a fresh entry's
        executable in a background thread; the first slab on that entry
        joins it before executing."""
        self._validate_posterior(post)
        key = self._cache_key(post)
        entry = self._exec.pop(key, None)  # re-insert below: LRU order
        if entry is not None:
            self.cache_hits += 1
        else:
            self.cache_misses += 1
            entry = self._build(post)
        self._exec[key] = entry
        while len(self._exec) > self.max_cached:
            self._exec.pop(next(iter(self._exec)))  # evict least recent
        # q-parameters ride as jit arguments (same shapes ⇒ no retrace)
        entry["mean"] = list(post.mean)
        entry["std"] = post.std()
        self.posterior = post
        self._entry = entry
        if rewarm and "warm" not in entry:
            args = self._slab_args(entry, [])
            t = threading.Thread(
                target=lambda: jax.block_until_ready(entry["fn"](*args)),
                daemon=True)
            t.start()
            entry["warm"] = t
        return entry

    def _build(self, post: Posterior) -> dict:
        icr = post.icr
        shapes = icr.xi_shapes()
        n_shards = self._n_shards()
        capacity = self.capacity
        local_rows = capacity // n_shards
        # model what this ICR actually executes on *one device*: no pyramid
        # overlay when disabled, no axis factors without the fused path;
        # the mesh fingerprint keys the plan so a re-mesh re-plans
        plan = dispatch.plan_cached(
            icr.chart, samples=local_rows, dtype=icr.policy.storage_dtype,
            pyramid=icr.use_pallas and icr.use_pyramid,
            have_axis_mats=icr.use_pallas and icr.chart.ndim > 1,
            mesh_key=self._mesh_key())

        def draw(mean, std, seed, row, use_xi, xi_row):
            """One row's excitation: (seed, row)-keyed noise around the
            posterior mean — or the request's own ξ when it supplied one
            (``xi_row`` is None when no row of the slab did)."""
            with jax.named_scope("serve.draw"):
                k = jax.random.fold_in(jax.random.PRNGKey(seed), row)
                ks = jax.random.split(k, len(shapes))
                xs = [None] * len(shapes) if xi_row is None else xi_row
                out = []
                for kk, m, s, x in zip(ks, mean, std, xs):
                    base = m if x is None else jnp.where(
                        use_xi, x.astype(m.dtype), m)
                    out.append(base
                               + s * jax.random.normal(kk, m.shape, m.dtype))
                return out

        if self.mesh is not None and self.shard == "chart":
            entry = self._build_chart_sharded(post, draw, shapes)
        elif self.mesh is not None:
            entry = self._build_sample_sharded(post, draw)
        else:
            def slab_fn(mats, mean, std, seeds, rows, use_xi, xi_rows):
                one = lambda se, ro, fl, xp: draw(mean, std, se, ro, fl, xp)
                xi = jax.vmap(one)(seeds, rows, use_xi, xi_rows)
                # clients get f32 fields whatever the internal storage dtype
                return icr.apply_sqrt_batch(mats, xi).astype(jnp.float32)

            mats = icr.matrices_cached(post.theta)
            entry = {"mats": mats,
                     "fn": _slab_callable(lambda with_xi: jax.jit(slab_fn))}
        entry.update(plan=plan, capacity=capacity, shapes=shapes,
                     mode=self._mode_for(icr))
        return entry

    def _build_sample_sharded(self, post: Posterior, draw) -> dict:
        """Data-parallel over the sample axis: each device draws and
        refines ``capacity / n_dev`` rows; matrices and q-params are
        replicated, seeds/rows/ξ-overrides are split. Row keying is
        (seed, row) — device-independent — so the global result is
        identical to the unsharded slab."""
        icr = post.icr
        mesh = self.mesh
        axes = tuple(mesh.axis_names)

        def slab_fn(mats, mean, std, seeds, rows, use_xi, xi_rows):
            one = lambda se, ro, fl, xp: draw(mean, std, se, ro, fl, xp)
            xi = jax.vmap(one)(seeds, rows, use_xi, xi_rows)
            return icr.apply_sqrt_batch(mats, xi).astype(jnp.float32)

        mats = icr.matrices_cached(post.theta)
        repl = lambda tree: jax.tree.map(lambda _: P(), tree)
        # exercise the elastic placement path (replicated specs never
        # degrade, but a future param-sharded layout reports through the
        # same channel)
        mats, report = elastic.remesh_report(mats, mesh, repl(mats))
        self.degradations.extend(report)
        n_levels = len(post.mean)
        def make(with_xi):
            in_specs = (repl(mats), [P()] * n_levels, [P()] * n_levels,
                        P(axes), P(axes), P(axes),
                        [P(axes)] * n_levels if with_xi else None)
            return jax.jit(shard_map(slab_fn, mesh=mesh, in_specs=in_specs,
                                     out_specs=P(axes), check_vma=False))

        return {"mats": mats, "fn": _slab_callable(make)}

    def _build_chart_sharded(self, post: Posterior, draw, shapes) -> dict:
        """Spatial decomposition via the DistributedICR halo-exchange body:
        every device owns a block of the field along ``shard_axis`` and
        each row's refinement exchanges halos with ring neighbors. The ξ
        draw itself is replicated per row (same (seed, row) keys on every
        device) and each device keeps its local block — numerics match the
        single-device path to fp tolerance (interior math identical; see
        tests/test_distributed_icr.py)."""
        from repro.core.distributed import DistributedICR

        icr = post.icr
        mesh = self.mesh
        dist = DistributedICR(icr=icr, mesh=mesh,
                              axis_names=tuple(mesh.axis_names))
        k = dist.first_sharded_level()
        struct = dist.xi_structure()
        n_dev = dist.n_dev
        ax = dist.shard_axis

        def slab_fn(mats, mean, std, seeds, rows, use_xi, xi_rows):
            idx = jax.lax.axis_index(dist.axis_names)

            def one(seed, row, flag, xi_row):
                xi = draw(mean, std, seed, row, flag, xi_row)
                loc = [xi[0]]
                for lvl in range(icr.chart.n_levels):
                    leaf = xi[lvl + 1].reshape(struct[lvl + 1])
                    if lvl >= k:
                        blk = struct[lvl + 1][ax] // n_dev
                        leaf = jax.lax.dynamic_slice_in_dim(
                            leaf, idx * blk, blk, axis=ax)
                    loc.append(leaf)
                return dist._sharded_body(mats, loc)

            out = jax.vmap(one)(seeds, rows, use_xi, xi_rows)
            return out.astype(jnp.float32)

        # the matrices the sharded body refines with (per-axis factors on
        # the kernel path of an N-D chart, else joint), placed with the
        # distributed specs; any degradation (e.g. a ring the family counts
        # don't divide would replicate) is reported, not hidden
        axes = dist.uses_axis_factors()
        mats = icr.matrices_cached(post.theta, joint=not axes, axes=axes)
        mat_specs = dist.mat_specs()
        mats, report = elastic.remesh_report(mats, mesh, mat_specs)
        self.degradations.extend(report)
        n_levels = len(post.mean)
        def make(with_xi):
            in_specs = (mat_specs, [P()] * n_levels, [P()] * n_levels,
                        P(), P(), P(), [P()] * n_levels if with_xi else None)
            return jax.jit(shard_map(slab_fn, mesh=mesh, in_specs=in_specs,
                                     out_specs=P(None, *dist.out_spec()),
                                     check_vma=False))

        return {"mats": mats, "fn": _slab_callable(make)}

    # -- admission -------------------------------------------------------------
    @staticmethod
    def _finish(req: GPRequest):
        req.done = True
        req.finished_s = time.perf_counter()

    def _reject(self, req: GPRequest, code: str, message: str):
        req.error = RequestError(code=code, message=message)
        self._finish(req)

    def _admit(self, queue: List[GPRequest], now: float):
        """Validate each request once, before any of its rows are packed:
        a rejected request never enters a slab, so it cannot poison the
        streaming moments of the healthy requests packed beside it. Each
        request admitted here gets its ``rid`` and ``admitted_s = now``."""
        shapes = self.posterior.icr.xi_shapes()
        served_theta = dict(self.posterior.icr.kernel.default_theta)
        served_theta.update(self.posterior.theta or {})
        for req in queue:
            if req.done or req.error or req._admitted:
                continue
            req._admitted = True
            self._rids += 1
            req.rid, req.admitted_s = self._rids, now
            if req.kind not in ("sample", "moments", "condition") \
                    or not isinstance(req.n, (int, np.integer)) \
                    or req.n <= 0 or not 0 <= int(req.seed) < 2**31:
                self._reject(req, "bad-request",
                             f"kind={req.kind!r} n={req.n} seed={req.seed} "
                             "(seed must fit int32)")
                continue
            if req.theta is not None:
                bad = [k for k, v in req.theta.items()
                       if not _all_finite(v)]
                if bad:
                    self._reject(req, "theta-nonfinite",
                                 f"non-finite theta entries {bad}")
                    continue
                stale = [k for k, v in req.theta.items()
                         if k not in served_theta
                         or not np.allclose(served_theta[k], v)]
                if stale:
                    self._reject(
                        req, "theta-mismatch",
                        f"request pinned theta {sorted(req.theta)} but the "
                        f"server is fitted at {sorted(served_theta)} with "
                        f"different values for {stale}")
                    continue
            if req.xi is not None:
                got = [tuple(np.shape(leaf)) for leaf in req.xi]
                want = [tuple(s) for s in shapes]
                if got != want:
                    self._reject(req, "xi-geometry",
                                 f"xi leaves {got} do not match the served "
                                 f"chart's xi_shapes() {want}")
                    continue
                if not all(_all_finite(leaf) for leaf in req.xi):
                    self._reject(req, "xi-nonfinite",
                                 "xi contains NaN/Inf values")
                    continue
            if req.kind == "condition":
                self._admit_condition(req)

    def _admit_condition(self, req: GPRequest):
        """Conditioning inputs are validated before any solve work runs:
        a non-finite y or a malformed observation spec is a structured
        rejection at the queue, while runtime divergence/NaN *inside* the
        solve is the solver quarantine's job (per-RHS isolation) — either
        way no other request's answer is perturbed."""
        y = None if req.y is None else np.asarray(req.y, np.float64).ravel()
        if y is None or y.size == 0:
            return self._reject(req, "y-missing",
                                "kind='condition' requires observed "
                                "values y")
        if not np.isfinite(y).all():
            return self._reject(req, "y-nonfinite",
                                "y contains NaN/Inf values")
        if (req.obs_idx is None) == (req.x_obs is None):
            return self._reject(req, "obs-spec",
                                "pass exactly one of obs_idx (on-grid) "
                                "or x_obs (off-grid 1-D)")
        chart = self.posterior.icr.chart
        n_grid = int(np.prod(chart.final_shape))
        if req.obs_idx is not None:
            idx = np.asarray(req.obs_idx)
            if idx.size and not np.issubdtype(idx.dtype, np.integer):
                return self._reject(req, "obs-dtype",
                                    "obs_idx must be integer flat indices")
            if idx.size == 0 or idx.min() < 0 or idx.max() >= n_grid:
                return self._reject(req, "obs-range",
                                    "obs_idx empty or out of range for a "
                                    f"{n_grid}-pixel chart")
            n_obs = idx.size
        else:
            x = np.asarray(req.x_obs, np.float64).ravel()
            if chart.ndim != 1:
                return self._reject(req, "obs-ndim",
                                    "off-grid x_obs interpolation is 1-D "
                                    "only; use obs_idx for N-D charts")
            if x.size == 0 or not np.isfinite(x).all():
                return self._reject(req, "obs-nonfinite",
                                    "x_obs is empty or non-finite")
            n_obs = x.size
        if y.size != n_obs:
            return self._reject(req, "obs-length",
                                f"y has {y.size} entries but the "
                                f"observation spec has {n_obs}")
        if not (np.isfinite(req.noise_std) and float(req.noise_std) > 0):
            return self._reject(req, "noise-invalid",
                                f"noise_std={req.noise_std!r} must be a "
                                "finite positive float")

    # -- slab execution --------------------------------------------------------
    def _slab_args(self, entry: dict, rows: list) -> tuple:
        """Device arguments for one slab: fixed ``capacity`` height, rows
        beyond the packed prefix are padding (keys past every stream)."""
        cap = entry["capacity"]
        seeds = np.zeros(cap, np.int32)
        idxs = np.full(cap, _PAD_ROW, np.int32)
        flags = np.zeros(cap, bool)
        # a slab in which no request supplies ξ ships none: at deployment
        # sizes the ξ override is as large as the field itself
        xi_rows = None
        if any(req.xi is not None for req, _ in rows):
            xi_rows = [np.zeros((cap,) + tuple(s), np.float32)
                       for s in entry["shapes"]]
        for i, (req, ridx) in enumerate(rows):
            seeds[i], idxs[i] = req.seed, ridx
            if req.xi is not None:
                flags[i] = True
                for lvl, leaf in enumerate(req.xi):
                    xi_rows[lvl][i] = np.asarray(leaf, np.float32)
        return (entry["mats"], entry["mean"], entry["std"],
                jnp.asarray(seeds), jnp.asarray(idxs), jnp.asarray(flags),
                None if xi_rows is None
                else [jnp.asarray(x) for x in xi_rows])

    def _execute_once(self, entry: dict, args: tuple) -> np.ndarray:
        """One slab attempt under the fault supervisor: transient errors
        retry with backoff, DeviceLossError propagates to the re-plan
        path, wall time feeds the straggler monitor. An attempt is two
        spans: ``serve.execute`` (dispatch and the device's work, as the
        host waits for it) and ``serve.fetch`` (the copy to the host)."""
        warm = entry.pop("warm", None)
        if warm is not None:
            warm.join()

        def attempt():
            self.slabs_attempted += 1
            slab = self.slabs_attempted
            if self.fault_injector is not None:
                self.fault_injector(self)
            with span("serve.execute", self._phases, slab=slab):
                out = jax.block_until_ready(entry["fn"](*args))
            with span("serve.fetch", self._phases, slab=slab):
                return np.asarray(out, dtype=np.float32)

        return self.supervisor.execute(attempt)

    def _on_device_loss(self, exc: DeviceLossError):
        """detect → remesh → rewarm: shrink the mesh to the surviving
        devices, re-key the executable cache (the mesh is in the key, so
        this is a deliberate miss), rebuild matrices/plan on the new mesh
        and start the compile in the background. The caller then replays
        the in-flight slab."""
        if self.mesh is None:
            raise exc  # single device lost: nothing left to shrink onto
        self.dead_devices.update(exc.device_ids)
        new_mesh = elastic.shrink_mesh(self.mesh, self.dead_devices)
        if new_mesh is not None and self.shard == "chart":
            new_mesh = self._feasible_chart_mesh(new_mesh)
        if new_mesh is None:
            self.degradations.append(elastic.Degradation(
                path="<mesh>", requested=self._mesh_desc(),
                applied="unsharded",
                reason=f"lost device(s) {sorted(self.dead_devices)}; "
                       "degrading to the single-device path"))
        self.mesh = new_mesh
        self.replans += 1
        self.set_posterior(self.posterior, rewarm=True)

    def _feasible_chart_mesh(self, mesh):
        """Chart sharding needs the family counts divisible by the ring:
        shrink to the largest feasible ring ≤ the survivor count (recorded
        as a degradation when devices must idle), or None when no ring ≥ 2
        is feasible."""
        from repro.core.distributed import DistributedICR

        devs = list(np.asarray(mesh.devices).flat)
        for n in range(len(devs), 1, -1):
            cand = type(mesh)(np.asarray(devs[:n]), mesh.axis_names)
            try:
                DistributedICR(icr=self.posterior.icr, mesh=cand,
                               axis_names=tuple(cand.axis_names)
                               ).first_sharded_level()
            except ValueError:
                continue
            if n < len(devs):
                self.degradations.append(elastic.Degradation(
                    path="<mesh>", requested=f"{self.shard}:{len(devs)}",
                    applied=f"{self.shard}:{n}",
                    reason=f"no refinement level shardable over {len(devs)} "
                           f"survivors; largest feasible ring is {n}"))
            return cand
        self.degradations.append(elastic.Degradation(
            path="<mesh>", requested=f"{self.shard}:{len(devs)}",
            applied="unsharded",
            reason="no feasible chart ring over the survivors"))
        return None

    def _run_rows(self, rows: list, args: tuple) -> np.ndarray:
        """Execute packed rows, chunked to the active entry's capacity;
        ``args`` are the first chunk's, packed by the caller for the
        active entry. A DeviceLossError mid-chunk re-plans onto the
        surviving mesh and replays that chunk — the (seed, row) noise keys
        make the replay reproduce the unfaulted results exactly."""
        outs = []
        i = 0
        recovery_t0 = None
        while i < len(rows):
            entry = self._entry
            chunk = rows[i:i + entry["capacity"]]
            if args is None:  # a later chunk, or a replay on a new entry
                with span("serve.pack", self._phases,
                          slab=self.slabs_attempted + 1):
                    args = self._slab_args(entry, chunk)
            try:
                out = self._execute_once(entry, args)
            except DeviceLossError as exc:
                if recovery_t0 is None:
                    recovery_t0 = time.perf_counter()
                self._on_device_loss(exc)
                self.replayed_slabs += 1
                args = None
                continue  # replay the same chunk on the new entry
            args = None
            if recovery_t0 is not None:
                self.last_recovery_s = time.perf_counter() - recovery_t0
                recovery_t0 = None
            outs.append(out[:len(chunk)])
            self.slabs_run += 1
            i += len(chunk)
        return outs[0] if len(outs) == 1 else np.concatenate(outs, axis=0)

    # -- data-conditioned solves (kind="condition", DESIGN.md §16) -------------
    def _cond_mesh(self):
        """RHS-axis sharding mesh for the conditioning matvec: the serving
        mesh in "samples" mode (the RHS batch *is* a sample batch, split
        the same way); chart-sharded serving solves unsharded — the
        conditioning batch is small and the halo-exchange body has no RHS
        axis to split."""
        return self.mesh if self.shard == "samples" else None

    def _condition_system(self, op, noise_var: float):
        """LRU-cached ConditionSystem keyed like the executable cache plus
        the observation fingerprint and σ² — a re-fit, re-mesh or new
        observation pattern is a deliberate miss."""
        from repro.solvers import build_condition_system

        post = self.posterior
        key = (self._cache_key(post), op.fingerprint(), float(noise_var))
        sys_ = self._cond_cache.pop(key, None)
        if sys_ is None:
            sys_ = build_condition_system(post.icr, op, noise_var,
                                          theta=post.theta,
                                          mesh=self._cond_mesh())
        self._cond_cache[key] = sys_
        while len(self._cond_cache) > self.max_cached:
            self._cond_cache.pop(next(iter(self._cond_cache)))
        return sys_

    def _solver_manager(self):
        """Per-solve CheckpointManager rooted under ``ckpt_root`` (lazily
        a tempdir): every solve gets its own directory so a resumed carry
        can never alias another request's checkpoints."""
        if self.solver_checkpoint_every <= 0:
            return None
        import os
        import tempfile

        from repro.checkpoint.checkpointer import CheckpointManager

        if self.ckpt_root is None:
            self.ckpt_root = tempfile.mkdtemp(prefix="gp-serve-solve-")
        self._cond_seq += 1
        return CheckpointManager(
            os.path.join(self.ckpt_root, f"solve_{self._cond_seq}"))

    def _run_condition(self, req: GPRequest):
        """Serve one kind="condition" request end to end (§16).

        RHS layout: column 0 solves the posterior-mean system
        ``(W K Wᵀ + σ²I) α = y``; columns 1..n are Matheron pathwise
        targets ``y − W f_j − σ ε_j`` for prior draws ``f_j = S ξ_j``
        keyed ``fold_in(seed, row)`` exactly like the sampling slab — a
        re-meshed replay reproduces the same draws. The batch is padded
        to a multiple of the mesh size (zero-RHS columns converge at
        iteration 0) and re-padded after an elastic shrink. The solve
        runs the guarded fallback ladder under the fault supervisor with
        checkpoint/resume; the structured SolveReport rides back on the
        request and in ``metrics()``."""
        from repro.solvers import CGConfig, solve_guarded
        from repro.solvers.gp_system import obs_operator

        self.condition_requests += 1
        post = self.posterior
        icr = post.icr
        try:
            op = obs_operator(icr, obs_idx=req.obs_idx, x_obs=req.x_obs)
        except ValueError as e:  # race-proofing: _admit already checks
            return self._reject(req, "obs-invalid", str(e))
        noise_std = float(req.noise_std)
        noise_var = noise_std ** 2
        state = {"system": self._condition_system(op, noise_var)}
        shapes = icr.xi_shapes()
        shape = tuple(icr.chart.final_shape)
        n = int(req.n)
        k_real = 1 + n

        def draw(row):
            k = jax.random.fold_in(jax.random.PRNGKey(req.seed), row)
            ks = jax.random.split(k, len(shapes) + 1)
            xi = [jax.random.normal(kk, tuple(s), jnp.float32)
                  for kk, s in zip(ks[:-1], shapes)]
            eps = jax.random.normal(ks[-1], (op.n_obs,), jnp.float32)
            return xi, eps

        xi, eps = jax.vmap(draw)(jnp.arange(n))
        fields = np.asarray(state["system"].sqrt_batch(xi)
                            ).astype(np.float32).reshape(n, -1)
        y = jnp.asarray(np.asarray(req.y, np.float32).ravel())[None, :]
        b = jnp.concatenate(
            [y, y - op.apply(jnp.asarray(fields)) - noise_std * eps],
            axis=0)

        def shards_of(sys_):
            return (1 if sys_.mesh is None
                    else int(np.asarray(sys_.mesh.devices).size))

        k_pad = -(-k_real // shards_of(state["system"])) \
            * shards_of(state["system"])
        if k_pad > k_real:
            b = jnp.concatenate(
                [b, jnp.zeros((k_pad - k_real, op.n_obs), b.dtype)],
                axis=0)

        def fault_hook(it):
            self.solve_segments += 1
            if self.fault_injector is not None:
                self.fault_injector(self)

        def on_device_loss(exc):
            # shrink the mesh + rewarm the sampling entry, then rebuild
            # the conditioning system on the survivors; the new width
            # pads *up* to the survivors' multiple so the already-running
            # solve_guarded ladder never narrows below its batch
            self._on_device_loss(exc)
            sys_ = self._condition_system(op, noise_var)
            state["system"] = sys_
            n_sh = shards_of(sys_)
            k_new = -(-max(k_real, k_pad) // n_sh) * n_sh
            return sys_.matvec, {"icr": sys_.precond, "none": None}, k_new

        cfg = self.solver_config or CGConfig(
            rtol=1e-7, max_iters=max(4 * op.n_obs, 200))
        ladder = ([("icr", state["system"].precond)]
                  if state["system"].precond is not None else []) \
            + [("none", None)]
        alpha, report = solve_guarded(
            state["system"].matvec, b, preconds=ladder, cfg=cfg,
            dense_solve=lambda bb: state["system"].dense_solve(bb),
            manager=self._solver_manager(),
            checkpoint_every=self.solver_checkpoint_every or None,
            fault_hook=fault_hook, on_device_loss=on_device_loss,
            executor=self.supervisor.execute,
            n_report=k_real, tag=f"condition:{op.n_obs}obs")

        req.report = report
        self.solve_reports.append(report)
        del self.solve_reports[:-16]
        self.condition_rhs += k_real
        if report.status[0] not in ("converged", "dense"):
            self._finish(req)
            req.error = RequestError(
                "solve-failed",
                f"posterior-mean solve ended '{report.status[0]}' "
                f"(relres {report.relres[0]:.2e}) after rungs "
                f"{list(report.rungs)}")
            return
        corr = np.asarray(state["system"].correct(
            jnp.asarray(alpha[:k_real], jnp.float32))).reshape(k_real, -1)
        req.mean = corr[0].reshape(shape)
        # predictive std over the *non-quarantined* Matheron samples: a
        # diverged/NaN sample column is excluded, never averaged in
        good = [j for j in range(1, k_real)
                if report.status[j] in ("converged", "dense")]
        if len(good) >= 2:
            samples = np.stack([fields[j - 1] + corr[j] for j in good])
            req.std = samples.std(axis=0).reshape(shape)
        else:
            req.std = np.zeros(shape, np.float32)
        self.fields_delivered += 2
        self._finish(req)

    # -- serving loop ----------------------------------------------------------
    def step(self, queue: List[GPRequest]) -> bool:
        """Pack one slab from the queue, execute it, scatter the results.
        Condition requests are served one per step (a whole batched solve
        is one unit of work); sample/moments rows pack into slabs.
        Returns False when no request had demand (queue drained).

        The call is the span ``serve.step``; a sample/moments slab runs
        in its children ``serve.admit``, ``serve.pack`` (row assignment
        and the slab's arguments; its ``rids`` are the packed requests'),
        ``serve.execute``, ``serve.fetch`` and ``serve.scatter`` (row
        copies and the Welford merge). Each carries ``slab``, the number
        of the slab's first attempt (``slabs_attempted`` counts them)."""
        now = time.perf_counter()
        slab = self.slabs_attempted + 1
        with span("serve.step", self._phases, slab=slab):
            with span("serve.admit", self._phases, slab=slab):
                self._admit(queue, now)
            for req in queue:
                if not req.done and req.kind == "condition":
                    self._run_condition(req)
                    return True
            with span("serve.pack", self._phases, slab=slab) as ann:
                rows = self._pack(queue, now)
                if not rows:
                    return False
                ann.set_metadata(rids=" ".join(
                    str(rid) for rid in sorted({r.rid for r, _ in rows})))
                args = self._slab_args(self._entry, rows)
            out = self._run_rows(rows, args)
            self.rows_served += len(rows)
            with span("serve.scatter", self._phases, slab=slab):
                self._scatter(rows, out)
        return True

    def _pack(self, queue: List[GPRequest], now: float) -> list:
        """Assign up to one slab of rows greedily in queue order: (request,
        row index in its eps stream) pairs. A request's first packed row
        stamps its ``started_s = now`` and its wait since admission."""
        cap = self._entry["capacity"]
        rows = []
        for req in queue:
            if req.done:
                continue
            take = min(req.n - req._next_row, cap - len(rows))
            if take > 0 and req.started_s is None:
                req.started_s = now
                self.requests_started += 1
                self.queue_wait_s += now - req.admitted_s
            rows.extend((req, req._next_row + j) for j in range(take))
            req._next_row += take
            if len(rows) == cap:
                break
        return rows

    def _scatter(self, rows: list, out: np.ndarray):
        """Hand a slab's rows to their requests: contiguous runs per
        request (greedy packing keeps order); a request whose last row
        came back is done."""
        i = 0
        while i < len(rows):
            req = rows[i][0]
            j = i
            while j < len(rows) and rows[j][0] is req:
                j += 1
            chunk = out[i:j]
            if req.kind == "sample":
                # copies, not views: a retained row must not pin the slab
                req.fields.extend(np.array(row) for row in chunk)
            else:
                req._wcount, req._wmean, req._wm2 = _welford_merge(
                    req._wcount, req._wmean, req._wm2, chunk)
            if req._next_row >= req.n:
                if req.kind == "moments":
                    req.mean = req._wmean
                    req.std = np.sqrt(np.maximum(req._wm2 / req._wcount, 0.0))
                    self.fields_delivered += 2
                else:
                    self.fields_delivered += len(req.fields)
                self._finish(req)
            i = j

    def run(self, requests: List[GPRequest], max_iters: int = 1_000_000):
        queue = list(requests)
        # re-resolve the executable for this batch: warm traffic against the
        # same (chart, θ, policy, mesh) counts a hit and reuses everything
        self.set_posterior(self.posterior)
        it = 0
        while any(not r.done for r in queue) and it < max_iters:
            if not self.step(queue):
                break
            it += 1
        for r in queue:
            if not r.done:  # max_iters exhausted: signal, never silently
                self._reject(r, "max-iters",
                             f"server stopped after max_iters={max_iters} "
                             f"slabs with {r.n - r._next_row} rows pending")
        return requests

    # -- introspection ---------------------------------------------------------
    def modeled_slab_bytes(self) -> int:
        """Roofline HBM bytes one *per-device* slab application moves
        (plan estimate for the local rows)."""
        return sum(e["hbm_bytes"]["selected"] for e in self._entry["plan"])

    @property
    def route(self) -> str:
        """Dispatch route of the finest (dominant) refinement level."""
        return self._entry["plan"][-1]["route"]

    def metrics(self) -> dict:
        """Serving + fault counters for dashboards and the chaos suite.

        Counters since construction; a reader takes differences over its
        own window. ``slabs_run``/``rows_served``/``capacity`` give slab
        fill. ``phase_s`` and ``phase_n`` map each span of ``step``
        (``serve.step`` and its children ``serve.admit``, ``serve.pack``,
        ``serve.execute``, ``serve.fetch``, ``serve.scatter``) to its
        cumulative host seconds and count: where a slab's time goes,
        without a profiler. ``queue_wait_s`` sums each request's wait from
        admission to its first packed row (``started_s - admitted_s``)
        over ``requests_started`` requests.
        """
        return {
            "phase_s": {k: v[0] for k, v in self._phases.items()},
            "phase_n": {k: v[1] for k, v in self._phases.items()},
            "queue_wait_s": self.queue_wait_s,
            "requests_started": self.requests_started,
            "slabs_run": self.slabs_run,
            "slabs_attempted": self.slabs_attempted,
            "rows_served": self.rows_served,
            "cache_hits": self.cache_hits,
            "cache_misses": self.cache_misses,
            "replans": self.replans,
            "replayed_slabs": self.replayed_slabs,
            "dead_devices": sorted(self.dead_devices),
            "mesh": self._mesh_desc(),
            "mode": self.serving_mode,
            "capacity": self.capacity,
            "last_recovery_s": self.last_recovery_s,
            "degradations": [str(d) for d in self.degradations],
            "condition_requests": self.condition_requests,
            "condition_rhs": self.condition_rhs,
            "solve_segments": self.solve_segments,
            "solve_fallbacks": sum(len(r.fallbacks)
                                   for r in self.solve_reports),
            "solve_resumes": sum(len(r.resumes)
                                 for r in self.solve_reports),
            "solve_reports": [r.summary() for r in self.solve_reports[-4:]],
            **{f"fault_{k}": v
               for k, v in self.supervisor.metrics().items()},
        }

    def cache_key_fingerprint(self) -> dict:
        """Deterministic printable fingerprint of the active
        executable-cache key (DESIGN.md §13) — the serving column of the
        compile fingerprints (repro.analysis). Equal server configs
        produce byte-identical fingerprints in any process; anything that
        would be a cache miss (chart geometry, θ, dtype policy, routing
        flags, effective backend, slab height, mesh) changes the digest."""
        canon = _canonical_key(self._cache_key(self.posterior))
        icr = self.posterior.icr
        return {
            "digest": hashlib.sha256(canon.encode()).hexdigest()[:16],
            "key": canon,
            "slab": self.slab,
            "backend": dispatch.select_backend(),
            "storage_dtype": icr.policy.storage_name,
            "mesh": self._mesh_desc(),
        }

    def lowered_slab(self):
        """``jax.stages.Lowered`` of the active entry's slab executable —
        the §12 hot step as one lowering, handed to the compile-fingerprint
        subsystem (repro.analysis) so a serving-path route or dtype
        regression is caught by the golden diff, not by wall-time noise."""
        e = self._entry
        return e["fn"].variant(False).lower(*self._slab_args(e, []))

    def op_scopes(self) -> dict:
        """``{instruction name: scope path}`` of the compiled slab
        executable: each instruction's ``op_name`` metadata cut to its
        ``icr.*``/``serve.*`` named scopes (``"fusion.35":
        "icr.level11/icr.interleave"``); instructions in no such scope are
        left out. A profiler trace names device ops by instruction, so this
        ties each op to its refinement level and phase. Compiling hits the
        executable the slab ran, in memory or in the compile cache. The
        persistent cache's key leaves metadata out, so an executable that
        an older version of this code compiled into a shared cache keeps
        that version's scopes (none, before they existed)."""
        text = self.lowered_slab().compile().as_text()
        out = {}
        for name, op_name in _HLO_OP_NAME.findall(text):
            found = _SCOPE.findall(op_name)
            # a transformation can repeat the scope it wraps
            path = [p for i, p in enumerate(found)
                    if not i or p != found[i - 1]]
            if path:
                out[name] = "/".join(path)
        return out


# -- demo / smoke entry point ---------------------------------------------------
def demo_posterior(chart, rho: float, dtype_policy=None,
                   seed: int = 0) -> Posterior:
    """A synthetic ADVI-shaped posterior (prior-sample mean, constant
    log-std) for benchmarks and smoke runs — no fit required. Real fits
    export through `core.vi.map_posterior` / `advi_posterior`."""
    from repro.core import ICR, matern32

    icr = ICR(chart=chart, kernel=matern32.with_defaults(rho=rho),
              use_pallas=True, dtype_policy=dtype_policy)
    mean = icr.init_xi(jax.random.PRNGKey(seed), dtype=jnp.float32)
    log_std = [jnp.full_like(m, -1.5) for m in mean]
    return Posterior(icr=icr, mean=mean, log_std=log_std)


def scenario_chart(name: str, quick: bool = False):
    """The three serving scenarios: 1-D time-ordered data, 2-D image,
    3-D dust map (the paper's flagship chart, reduced)."""
    from repro.core import regular_chart
    from repro.core.charts import galactic_dust_chart

    if name == "tod":
        return regular_chart(64, 3 if quick else 5, boundary="reflect")
    if name == "image":
        return regular_chart((16, 16) if quick else (32, 32), 2,
                             boundary="reflect")
    if name == "dust":
        return galactic_dust_chart((6, 8, 8), n_levels=2)
    raise ValueError(f"unknown scenario {name!r}")


SCENARIOS = {"tod": 8.0, "image": 4.0, "dust": 0.5}  # name -> kernel rho


def mixed_requests(n_fields: int = 3, mc: int = 8) -> List[GPRequest]:
    """A heterogeneous batch: sample + moments requests of varying size."""
    return [
        GPRequest(kind="sample", n=n_fields, seed=1),
        GPRequest(kind="moments", n=mc, seed=2),
        GPRequest(kind="sample", n=1, seed=3),
        GPRequest(kind="moments", n=mc // 2, seed=4),
    ]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--scenario", default="dust", choices=[*SCENARIOS, "all"])
    ap.add_argument("--slab", type=int, default=8)
    ap.add_argument("--fields", type=int, default=3)
    ap.add_argument("--mc", type=int, default=16)
    ap.add_argument("--dtype", default="fp32", choices=["fp32", "bf16"])
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--mesh", type=int, default=0,
                    help="shard over the first N local devices (0: off)")
    args = ap.parse_args()
    from repro.launch.cache import enable_compile_cache

    enable_compile_cache()

    mesh = None
    if args.mesh:
        from jax.sharding import Mesh
        mesh = Mesh(np.asarray(jax.devices()[:args.mesh]), ("data",))

    names = list(SCENARIOS) if args.scenario == "all" else [args.scenario]
    for name in names:
        chart = scenario_chart(name, quick=args.quick)
        pol = None if args.dtype == "fp32" else "bf16"
        post = demo_posterior(chart, SCENARIOS[name], dtype_policy=pol)
        srv = GPFieldServer(post, slab=args.slab, mesh=mesh)
        shape = chart.final_shape
        print(f"[{name}] chart {shape} = {int(np.prod(shape)):,} px, "
              f"slab={args.slab}, dtype={post.icr.policy.storage_name}, "
              f"mesh={srv._mesh_desc()}")

        t0 = time.time()
        srv.run(mixed_requests(args.fields, args.mc))
        cold = time.time() - t0
        t0 = time.time()
        reqs = srv.run(mixed_requests(args.fields, args.mc))
        warm = time.time() - t0

        assert all(r.done and r.error is None for r in reqs)
        mom = next(r for r in reqs if r.kind == "moments")
        print(f"  cold {cold*1e3:.0f} ms, warm {warm*1e3:.0f} ms "
              f"({cold/max(warm, 1e-9):.1f}x), "
              f"{srv.rows_served} rows in {srv.slabs_run} slabs, "
              f"{srv.rows_served/ (cold+warm):.1f} samples/s")
        print(f"  exec cache: {srv.cache_hits} hits / "
              f"{srv.cache_misses} misses; est {srv.modeled_slab_bytes():,} "
              f"HBM bytes/slab (route={srv.route})")
        print(f"  moments({mom.n}): mean std over field = "
              f"{float(np.mean(mom.std)):.3f}")


if __name__ == "__main__":
    main()
