"""Fault-injection harness for the sharded GP serving subsystem (§15).

Chaos faults are injected at the slab-execution boundary through the
server's ``fault_injector`` hook — the same place a real runtime raises
(device halt, collective timeout) — so the recovery path exercised here
(detect → remesh → rewarm → replay) is exactly the production path:

  * :class:`KillDevice` — raise a :class:`DeviceLossError` for one (or
    more) mesh devices at a chosen slab attempt; the server must shrink
    the mesh, re-plan, and replay the in-flight slab bit-identically.
  * :class:`Straggler` — a delayed-collective straggler: sleep inside the
    attempt so the slab wall time spikes; the serving-side
    :class:`~repro.distributed.fault.StragglerMonitor` must flag it.
  * :func:`poison_request` — a NaN-poisoned ξ request; admission must
    reject it with a structured error before it can touch a slab.

The acceptance suite (``--check``) runs on 8 virtual CPU devices:

  XLA_FLAGS=--xla_force_host_platform_device_count=8 \
      PYTHONPATH=src python -m repro.distributed.chaos --check

``--check-solvers`` runs the §16 *solver* chaos suite on the same mesh:
kill a device mid-CG-solve (checkpoint/resume on the shrunk mesh, zero
dropped RHS) and NaN-poison one RHS column of a sharded batched solve
(quarantine isolation — siblings bit-identical to the clean run).

``--bench`` emits JSON benchmark rows (mesh 1 vs 8 throughput and the
fault → first-completed-slab recovery time) consumed by
``benchmarks.speed.run_serving_mesh``.
"""
from __future__ import annotations

import os

if __name__ == "__main__":  # before jax initializes (conftest rule: only
    # standalone drivers may set XLA_FLAGS, never the test process). The
    # flag only multiplies *host* devices: the harness runs on whatever
    # platform JAX finds (the caller pins JAX_PLATFORMS=cpu for a virtual
    # 8-device mesh)
    os.environ.setdefault(
        "XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import dataclasses
import json
import time
from typing import List

import numpy as np

from .fault import DeviceLossError, ServingFaultSupervisor, StragglerMonitor


@dataclasses.dataclass
class KillDevice:
    """Lose device(s) at slab attempt ``at_slab`` (0-based attempt index)."""

    at_slab: int
    device_indices: tuple = (0,)


@dataclasses.dataclass
class Straggler:
    """Delay slab attempt ``at_slab`` by ``delay_s`` (slow collective)."""

    at_slab: int
    delay_s: float = 0.25


class ChaosInjector:
    """``GPFieldServer.fault_injector`` hook: fires each fault once, at its
    configured slab-attempt index, then lets execution proceed normally."""

    def __init__(self, faults: List):
        self.pending = list(faults)
        self.fired: list = []
        self.attempts = 0
        self.fault_times: list = []  # perf_counter at each fired fault

    def __call__(self, server):
        idx = self.attempts
        self.attempts += 1
        due = [f for f in self.pending if f.at_slab <= idx]
        kill_ids: list = []
        for f in due:
            self.pending.remove(f)
            self.fired.append((idx, f))
            if isinstance(f, Straggler):
                time.sleep(f.delay_s)
            elif isinstance(f, KillDevice):
                devs = (list(np.asarray(server.mesh.devices).flat)
                        if server.mesh is not None else [])
                if devs:
                    kill_ids.extend(
                        int(devs[i % len(devs)].id) for i in f.device_indices)
                else:
                    kill_ids.append(0)
        if kill_ids:
            self.fault_times.append(time.perf_counter())
            raise DeviceLossError(sorted(set(kill_ids)))


def poison_request(icr, kind: str = "moments", n: int = 3, seed: int = 0):
    """A request whose ξ override carries a NaN — admission must reject it
    (code ``xi-nonfinite``) before it shares a slab with healthy traffic."""
    from repro.launch.serve_gp import GPRequest

    xi = [np.zeros(s, np.float32) for s in icr.xi_shapes()]
    xi[-1].flat[0] = np.nan
    return GPRequest(kind=kind, n=n, seed=seed, xi=xi)


# -- acceptance checks (run under 8 virtual devices) ----------------------------
def _mk_server(mesh, *, slab: int = 8, shard: str = "samples",
               injector=None, supervisor=None, scenario: str = "tod"):
    from repro.launch.serve_gp import (GPFieldServer, SCENARIOS,
                                       demo_posterior, scenario_chart)

    chart = scenario_chart(scenario, quick=True)
    post = demo_posterior(chart, SCENARIOS[scenario])
    return GPFieldServer(post, slab=slab, mesh=mesh, shard=shard,
                         supervisor=supervisor, fault_injector=injector)


def _requests():
    from repro.launch.serve_gp import GPRequest

    return [GPRequest(kind="sample", n=5, seed=11),
            GPRequest(kind="moments", n=9, seed=12),
            GPRequest(kind="sample", n=3, seed=13)]


def _assert_equal_results(base, got, *, exact: bool = True, tol: float = 0.0):
    for a, b in zip(base, got):
        assert a.done and b.done and b.error is None, (a, b.error)
        pairs = (list(zip(a.fields, b.fields)) if a.kind == "sample"
                 else [(a.mean, b.mean), (a.std, b.std)])
        for xa, xb in pairs:
            if exact:
                assert np.array_equal(np.asarray(xa), np.asarray(xb)), \
                    "results differ from the unfaulted run"
            else:
                np.testing.assert_allclose(xa, xb, rtol=tol, atol=tol)


def _full_mesh(axis: str = "data"):
    import jax
    from jax.sharding import Mesh

    return Mesh(np.asarray(jax.devices()), (axis,))


def check_kill_midstream() -> str:
    """ISSUE 8 acceptance: kill one device mid-stream on an 8-mesh.
    Zero dropped requests, a re-planned mesh of 7, bit-identical results
    vs the unfaulted run, and a provable executable-cache invalidation."""
    import jax

    n_dev = len(jax.devices())
    base = _requests()
    _mk_server(_full_mesh()).run(base)

    inj = ChaosInjector([KillDevice(at_slab=1, device_indices=(3,))])
    srv = _mk_server(_full_mesh(), injector=inj)
    fp_before = srv.cache_key_fingerprint()["digest"]
    misses_before = srv.cache_misses
    got = _requests()
    srv.run(got)

    assert inj.fired, "fault never fired"
    assert all(r.done and r.error is None for r in got), "dropped requests"
    assert srv.mesh is not None, "mesh collapsed instead of shrinking"
    live = int(np.asarray(srv.mesh.devices).size)
    assert live == n_dev - 1, f"expected mesh of {n_dev - 1}, got {live}"
    assert srv.replans == 1 and srv.replayed_slabs >= 1, srv.metrics()
    # the re-mesh is a *deliberate* executable-cache miss, never a stale hit
    assert srv.cache_misses == misses_before + 1, srv.metrics()
    assert srv.cache_key_fingerprint()["digest"] != fp_before
    _assert_equal_results(base, got, exact=True)
    return (f"kill-midstream: mesh {n_dev}->{live}, "
            f"{srv.replayed_slabs} slab(s) replayed bit-identically, "
            f"cache miss on re-mesh")


def check_collapse_to_single_device() -> str:
    """Degradation ladder: losing all but one device drops to the
    single-device path and keeps serving, with the collapse recorded as a
    structured degradation."""
    import jax

    n_dev = len(jax.devices())
    base = _requests()
    _mk_server(None).run(base)

    inj = ChaosInjector([KillDevice(at_slab=0,
                                    device_indices=tuple(range(n_dev - 1)))])
    srv = _mk_server(_full_mesh(), injector=inj)
    got = _requests()
    srv.run(got)

    assert all(r.done and r.error is None for r in got)
    assert srv.mesh is None and srv.serving_mode.startswith("single")
    assert any(d.applied == "unsharded" for d in srv.degradations), \
        srv.metrics()
    _assert_equal_results(base, got, exact=True)
    return (f"collapse: {n_dev}->1 device, degraded to "
            f"{srv.serving_mode!r}, results bit-identical to unsharded")


def check_straggler_detection() -> str:
    """A delayed-collective straggler must be flagged by the serving-side
    StragglerMonitor fed from slab step times."""
    sup = ServingFaultSupervisor(monitor=StragglerMonitor(min_samples=6))
    inj = ChaosInjector([Straggler(at_slab=10, delay_s=0.5)])
    srv = _mk_server(_full_mesh(), injector=inj, supervisor=sup)
    from repro.launch.serve_gp import GPRequest

    srv.run([GPRequest(kind="sample", n=96, seed=5)])  # 12 slabs of 8
    assert inj.fired, "straggler never fired"
    assert sup.monitor.stragglers >= 1, sup.metrics()
    return (f"straggler: flagged {sup.monitor.stragglers} of "
            f"{srv.slabs_run} slabs (median {sup.monitor.median*1e3:.1f} ms)")


def check_chart_sharded_kill() -> str:
    """Chart-sharded serving (DistributedICR halo body) survives a device
    loss: the ring shrinks to the largest feasible size and results match
    the unsharded server to fp tolerance (halo math reorders reductions)."""
    base = _requests()
    _mk_server(None).run(base)

    inj = ChaosInjector([KillDevice(at_slab=1, device_indices=(2,))])
    srv = _mk_server(_full_mesh("space"), shard="chart", injector=inj)
    got = _requests()
    srv.run(got)

    assert all(r.done and r.error is None for r in got)
    assert srv.replans == 1, srv.metrics()
    _assert_equal_results(base, got, exact=False, tol=1e-5)
    ring = (int(np.asarray(srv.mesh.devices).size)
            if srv.mesh is not None else 1)
    return f"chart-kill: ring shrank to {ring}, results within 1e-5"


def check_poison_isolation() -> str:
    """A NaN-ξ request packed beside healthy traffic is rejected at
    admission and the healthy results are untouched."""
    from repro.launch.serve_gp import GPRequest

    srv = _mk_server(_full_mesh())
    clean = GPRequest(kind="moments", n=6, seed=2)
    _mk_server(_full_mesh()).run([clean])

    bad = poison_request(srv.posterior.icr)
    good = GPRequest(kind="moments", n=6, seed=2)
    srv.run([bad, good])
    assert bad.error is not None and bad.error.code == "xi-nonfinite"
    assert good.error is None
    assert np.array_equal(good.mean, clean.mean)
    assert np.isfinite(good.mean).all() and np.isfinite(good.std).all()
    return "poison: rejected at admission, healthy neighbor bit-identical"


CHECKS = [check_kill_midstream, check_collapse_to_single_device,
          check_straggler_detection, check_chart_sharded_kill,
          check_poison_isolation]


# -- §16 solver chaos (kind="condition" / guarded batched CG) -------------------
def _condition_inputs(srv):
    icr = srv.posterior.icr
    n = int(np.prod(icr.chart.final_shape))
    obs_idx = np.arange(0, n, 4)
    rng = np.random.default_rng(3)
    y = (np.sin(np.linspace(0.0, 6.0, obs_idx.size))
         + 0.05 * rng.standard_normal(obs_idx.size))
    return y, obs_idx


def check_solver_kill_midsolve() -> str:
    """Kill one of 8 devices mid-CG-solve: the solve must checkpoint,
    re-plan onto the 7-survivor mesh, resume from the saved carry and
    finish with zero dropped RHS — the posterior mean matching the
    unfaulted run (fp tolerance: shard reductions reorder on 7 vs 8)."""
    import jax
    from repro.launch.serve_gp import GPRequest

    n_dev = len(jax.devices())
    base_srv = _mk_server(_full_mesh())
    base_srv.solver_checkpoint_every = 2
    y, obs_idx = _condition_inputs(base_srv)
    base = GPRequest(kind="condition", n=7, seed=21, y=y, obs_idx=obs_idx)
    base_srv.run([base])
    assert base.error is None and base.report.ok, base.report

    inj = ChaosInjector([KillDevice(at_slab=1, device_indices=(3,))])
    srv = _mk_server(_full_mesh(), injector=inj)
    srv.solver_checkpoint_every = 2
    req = GPRequest(kind="condition", n=7, seed=21, y=y, obs_idx=obs_idx)
    srv.run([req])

    assert inj.fired, "fault never fired"
    assert req.error is None, req.error
    assert req.report.ok, f"dropped RHS: {req.report.summary()}"
    assert req.report.resumes, "no checkpoint resume recorded"
    assert srv.mesh is not None, "mesh collapsed instead of shrinking"
    live = int(np.asarray(srv.mesh.devices).size)
    assert live == n_dev - 1, f"expected mesh of {n_dev - 1}, got {live}"
    rel = (np.linalg.norm(req.mean - base.mean)
           / np.linalg.norm(base.mean))
    assert rel < 1e-5, f"resumed mean off by rel {rel:.2e}"
    np.testing.assert_allclose(req.std, base.std, atol=1e-4)
    ev = req.report.resumes[0]
    return (f"solver-kill: mesh {n_dev}->{live} at iter {ev.at_iter}, "
            f"resumed from checkpoint step {ev.restored_step}, "
            f"{req.report.n_rhs} RHS all converged (mean rel {rel:.1e})")


def check_solver_divergence_isolation() -> str:
    """NaN-poison one RHS column of a mesh-sharded batched solve: the
    column is quarantined (iterate zeroed, status nonfinite) and every
    sibling column is bit-identical to the clean run."""
    import jax
    import jax.numpy as jnp
    from repro.launch.serve_gp import (SCENARIOS, demo_posterior,
                                      scenario_chart)
    from repro.solvers import (CGConfig, build_condition_system,
                               obs_operator, pcg_solve)

    mesh = _full_mesh()
    chart = scenario_chart("tod", quick=True)
    post = demo_posterior(chart, SCENARIOS["tod"])
    icr = post.icr
    n = int(np.prod(chart.final_shape))
    op = obs_operator(icr, obs_idx=np.arange(0, n, 4))
    system = build_condition_system(icr, op, 0.05 ** 2, mesh=mesh)
    k = len(jax.devices())
    rng = np.random.default_rng(5)
    b = rng.standard_normal((k, op.n_obs)).astype(np.float32)
    cfg = CGConfig(rtol=1e-7, max_iters=200)
    x_clean, _, _, _ = pcg_solve(system.matvec, jnp.asarray(b),
                                 precond=system.precond, cfg=cfg)
    bad = b.copy()
    bad[3, 0] = np.nan
    x_bad, st_bad, _, _ = pcg_solve(system.matvec, jnp.asarray(bad),
                                    precond=system.precond, cfg=cfg)
    keep = [i for i in range(k) if i != 3]
    assert np.array_equal(np.asarray(x_clean)[keep],
                          np.asarray(x_bad)[keep]), \
        "sibling columns perturbed by the poisoned RHS"
    assert int(np.asarray(st_bad["status"])[3]) == 2, st_bad  # NONFINITE
    assert np.all(np.asarray(x_bad)[3] == 0.0), "quarantine not zeroed"
    return (f"solver-isolation: NaN column quarantined on mesh {k}, "
            f"{len(keep)} siblings bit-identical to the clean run")


SOLVER_CHECKS = [check_solver_kill_midsolve,
                 check_solver_divergence_isolation]


def run_checks(checks=None, label: str = "chaos") -> int:
    import jax

    checks = CHECKS if checks is None else checks
    n_dev = len(jax.devices())
    print(f"{label} acceptance suite on {n_dev} {jax.default_backend()} "
          "devices")
    if n_dev < 2:
        print("FAIL need >= 2 devices (set XLA_FLAGS="
              "--xla_force_host_platform_device_count=8)")
        return 1
    failed = 0
    for check in checks:
        try:
            msg = check()
        except Exception as exc:  # noqa: BLE001 — report every check
            failed += 1
            print(f"FAIL {check.__name__}: {type(exc).__name__}: {exc}")
        else:
            print(f"PASS {msg}")
    return 1 if failed else 0


# -- benchmark mode (consumed by benchmarks.speed.run_serving_mesh) -------------
def run_bench(quick: bool = True) -> list:
    """Throughput at mesh sizes 1 and N plus fault-recovery time, as JSON
    rows on stdout (one object per line, prefixed ``BENCH ``)."""
    import jax
    from jax.sharding import Mesh
    from repro.launch.serve_gp import GPRequest

    devs = jax.devices()
    rows = []
    for n_mesh in sorted({1, len(devs)}):
        mesh = (None if n_mesh == 1
                else Mesh(np.asarray(devs[:n_mesh]), ("data",)))
        srv = _mk_server(mesh, slab=8)
        work = lambda: [GPRequest(kind="sample", n=32, seed=9)]
        srv.run(work())  # cold: compile
        t0 = time.perf_counter()
        reps = 2 if quick else 8
        for _ in range(reps):
            srv.run(work())
        dt = time.perf_counter() - t0
        rows.append({"mesh": n_mesh, "mode": srv.serving_mode,
                     "samples_per_s": 32 * reps / dt,
                     "warm_s": dt / reps})
    # recovery: kill one device mid-stream, measure fault -> first slab
    if len(devs) >= 2:
        inj = ChaosInjector([KillDevice(at_slab=1, device_indices=(1,))])
        srv = _mk_server(Mesh(np.asarray(devs), ("data",)), injector=inj)
        srv.run([GPRequest(kind="sample", n=32, seed=9)])
        rows.append({"mesh": len(devs), "mode": "recovery",
                     "recovery_s": srv.last_recovery_s,
                     "replayed_slabs": srv.replayed_slabs})
    for row in rows:
        print("BENCH " + json.dumps(row, sort_keys=True))
    return rows


def main():
    import argparse

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--check", action="store_true",
                    help="run the chaos acceptance suite")
    ap.add_argument("--check-solvers", action="store_true",
                    help="run the §16 solver chaos suite (mid-solve kill "
                         "+ sharded divergence isolation)")
    ap.add_argument("--bench", action="store_true",
                    help="emit mesh-throughput + recovery benchmark rows")
    ap.add_argument("--full", action="store_true")
    args = ap.parse_args()
    rc = 0
    if args.check:
        rc = run_checks()
    if args.check_solvers:
        rc = max(rc, run_checks(SOLVER_CHECKS, label="solver chaos"))
    if args.bench:
        run_bench(quick=not args.full)
    if not (args.check or args.check_solvers or args.bench):
        rc = run_checks()
    raise SystemExit(rc)


if __name__ == "__main__":
    main()
