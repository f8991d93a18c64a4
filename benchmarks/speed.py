"""Paper Fig. 4 reproduction: forward-pass wall time, ICR vs KISS-GP.

Timed units exactly as §5.2:
  * ICR: one application of sqrt(K_ICR) (the generative forward pass);
  * KISS-GP: apply K^{-1} with 40 CG iterations + stochastic log-det with
    10 probes x 15 Lanczos iterations.
Median over repeats, double precision, single host device (the paper used
CPU and GPU; this container is CPU). Paper result: ICR is ~1 order of
magnitude faster at every N on both backends.
"""
import math
import time

import numpy as np

import jax
import jax.numpy as jnp


def _bench(fn, *args, repeats=5):
    fn(*args)  # compile + warmup
    times = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        out = fn(*args)
        jax.block_until_ready(out)
        times.append(time.perf_counter() - t0)
    return float(np.median(times))


def run(report, sizes=(256, 1024, 4096, 16384, 65536)):
    from repro.core import ICR, KissGP, log_chart, matern32

    for n in sizes:
        # ICR: log chart grown to ~n points, (3,2) (the paper benches all
        # parametrizations; (3,2) and (5,4) bracket them — we report both)
        for (ncsz, nfsz) in [(3, 2), (5, 4)]:
            n0, lvl = 16, 1
            while True:
                c = log_chart(n0, lvl, n_csz=ncsz, n_fsz=nfsz,
                              delta0=math.log(50) / n, boundary="reflect")
                if c.final_shape[0] >= n:
                    break
                lvl += 1
            icr = ICR(chart=c, kernel=matern32.with_defaults(rho=1.0))
            mats = icr.matrices()
            xi = icr.init_xi(jax.random.PRNGKey(0))
            fwd = jax.jit(lambda m, x: icr.apply_sqrt(m, x))
            t = _bench(fwd, mats, xi)
            report(f"speed/icr_{ncsz}{nfsz}_n{n}", t * 1e6,
                   f"N={c.final_shape[0]} t={t*1e3:.2f}ms")

        xs = np.cumsum(np.random.default_rng(0).uniform(0.5, 2.0, n))
        kiss = KissGP(x=xs, kernel_fn=matern32.with_defaults(rho=10.0)())
        y = jnp.asarray(np.random.default_rng(1).normal(size=n), jnp.float32)
        fwd_k = jax.jit(kiss.forward_pass)
        t_k = _bench(fwd_k, y, jax.random.PRNGKey(0))
        report(f"speed/kissgp_n{n}", t_k * 1e6, f"N={n} t={t_k*1e3:.2f}ms")


def _bw_util(hbm_bytes: int, seconds: float):
    """Achieved bytes/s over the running device's HBM bandwidth from the
    device table, or None on a device the table does not list (a CPU):
    no bandwidth share is ever computed against another chip's peak."""
    from repro.launch.mesh import device_spec

    try:
        peak = device_spec().hbm_bw
    except KeyError:
        return None
    return hbm_bytes / max(seconds, 1e-12) / peak


def run_nd(report):
    """2-D and 3-D refinement through the N-D Pallas paths (DESIGN.md §4/§10).

    Benches the finest level three ways — the single-launch fused megakernel
    (``nd_fused``), the per-axis passes (``nd.refine_axes``) and the jnp
    reference oracle — in interpret mode on CPU (the kernel bodies execute
    as pure jnp, checking the exact tiling). Both kernel paths must agree
    with the oracle to <= 1e-5 (acceptance bar). Each row carries the
    roofline HBM-byte estimate of its route so the JSON tracks the traffic
    win next to the wall time (interpret-mode wall time measures emulation
    overhead, not kernel speed).
    """
    from repro.core import matern32, regular_chart
    from repro.core.charts import galactic_dust_chart
    from repro.core.refine import LevelGeom, axis_refinement_matrices_level
    from repro.kernels import nd as knd
    from repro.kernels import nd_fused as kfu
    from repro.kernels import ref as kref
    from repro.kernels.dispatch import ROUTE_ND_FUSED, plan, select_backend
    from repro.roofline import refine_level_traffic

    backend = select_backend()
    cases = [
        ("2d", regular_chart((64, 64), 2, boundary="reflect"), 4.0),
        ("3d", galactic_dust_chart((6, 16, 16), n_levels=2), 0.5),
    ]
    for name, c, rho in cases:
        k = matern32.with_defaults(rho=rho)()
        # pyramid=False: this table benches the per-level megakernel; the
        # pyramid overlay has its own table (run_dtype)
        routes = [e["route"] for e in plan(c, pyramid=False)]
        assert all(r == ROUTE_ND_FUSED for r in routes), routes
        lvl = c.n_levels - 1  # finest (dominant) level
        geom = LevelGeom.for_level(c, lvl)
        rs, ds = axis_refinement_matrices_level(c, k, lvl)
        rng = np.random.default_rng(0)
        field = jnp.asarray(rng.normal(size=geom.coarse_shape), jnp.float32)
        f = int(np.prod(geom.T))
        xi = jnp.asarray(
            rng.normal(size=(f, geom.n_fsz ** c.ndim)), jnp.float32)

        fused = jax.jit(lambda fl, x: kfu.refine_nd_fused(
            fl, x, rs, ds, geom, interpret=True))
        axes = jax.jit(lambda fl, x: knd.refine_axes(
            fl, x, rs, ds, geom, interpret=True))
        ref = jax.jit(lambda fl, x: kref.refine_axes_ref(
            fl, x, rs, ds, T=geom.T, n_fsz=geom.n_fsz,
            boundary=geom.boundary, b=geom.b))
        out_r = ref(field, xi)
        scale = float(jnp.abs(out_r).max() + 1e-30)
        for label, fn in [("fused", fused), ("axes", axes)]:
            rel = float(jnp.abs(fn(field, xi) - out_r).max() / scale)
            assert rel <= 1e-5, f"nd/{name}/{label} vs oracle rel {rel:.2e}"
        n = int(np.prod(geom.fine_shape))
        # the jnp oracle row carries no byte estimate: XLA fuses it
        # unpredictably and the roofline "reference" model describes the
        # joint-window path, not the per-axis oracle timed here
        rows = [
            ("fused", fused, "nd-fused"),
            ("axes", axes, "nd-axes"),
            ("ref", ref, None),
        ]
        for label, fn, route in rows:
            t = _bench(fn, field, xi)
            hbm = (refine_level_traffic(geom, route)["total"]
                   if route else None)
            report(f"nd/{label}_{name}", t * 1e6,
                   f"N={n} t={t*1e3:.2f}ms"
                   + (f" est_bytes={hbm:,}" if hbm else ""),
                   route=route or "jnp-oracle",
                   backend=backend if route else "jnp",
                   hbm_bytes=hbm,
                   bw_util=_bw_util(hbm, t) if hbm else None)
        report(f"nd/{name}_fused_vs_axes_bytes",
               refine_level_traffic(geom, "nd-axes")["total"]
               / refine_level_traffic(geom, "nd-fused")["total"],
               "modeled per-level HBM traffic ratio (axes/fused)")


def run_batch(report, *, quick: bool = False):
    """Batched-sample throughput (DESIGN.md §10): the native sample-batch
    kernel dimension vs a per-sample Python loop, on the 1-D charted chart
    and the 3-D dust chart. Off-TPU both run interpret mode — the ratio
    shows launch/emulation amortization, the JSON bytes column the traffic.
    """
    from repro.core import ICR, matern32
    from repro.core.charts import galactic_dust_chart, log_chart
    from repro.core.refine import LevelGeom
    from repro.kernels.dispatch import plan, select_backend
    from repro.roofline import refine_level_traffic

    backend = select_backend()
    n_s = 4 if quick else 8
    cases = [
        ("1d-charted", log_chart(64, 2 if quick else 4, n_csz=5, n_fsz=4,
                                 delta0=0.05), 1.0),
        ("3d-dust", galactic_dust_chart((6, 8, 8), n_levels=2), 0.5),
    ]
    for name, c, rho in cases:
        icr = ICR(chart=c, kernel=matern32.with_defaults(rho=rho),
                  use_pallas=True)
        mats = icr.matrices()
        xi = icr.init_xi(jax.random.PRNGKey(0), batch=n_s)
        batched = jax.jit(lambda m, xs: icr.apply_sqrt_batch(m, xs))
        looped = jax.jit(lambda m, xs: jnp.stack(
            [icr.apply_sqrt(m, [x[i] for x in xs]) for i in range(n_s)]))
        err = float(jnp.abs(batched(mats, xi) - looped(mats, xi)).max())
        assert err <= 1e-4, f"batch/{name} batched-vs-loop {err:.2e}"
        t_b = _bench(batched, mats, xi)
        t_l = _bench(looped, mats, xi)
        entries = plan(c, samples=n_s)
        # samples= keeps the matrix bytes counted once — the amortization
        # this table exists to track; "selected" is position-aware for
        # pyramid-covered levels (first/last carry the field read/write)
        hbm = sum(e["hbm_bytes"]["selected"] for e in entries)
        route = entries[-1]["route"]
        report(f"batch/{name}/native", t_b * 1e6,
               f"S={n_s} {n_s/t_b:.1f} samples/s", route=route,
               backend=backend, hbm_bytes=hbm, bw_util=_bw_util(hbm, t_b))
        report(f"batch/{name}/loop", t_l * 1e6,
               f"S={n_s} {n_s/t_l:.1f} samples/s", route=route,
               backend=backend)
        report(f"batch/{name}/speedup", t_l / t_b,
               f"loop/native wall-time ratio ({backend})")


def run_serving(report, *, quick: bool = False):
    """GP posterior serving table (DESIGN.md §12; BENCH_PR5.json): the
    three chart scenarios (1-D TOD, 2-D image, 3-D dust) x fp32/bf16
    storage, each serving a mixed sample+moments request batch through
    `launch.serve_gp.GPFieldServer`. Rows report warm-path samples/s and
    fields/s (the cold row carries compile+build and is reported once as
    the warm/cold ratio), the modeled HBM bytes of one warm request batch
    from the cached plan, and the would-be bandwidth utilization at the
    TPU roofline
    (off-TPU wall time measures the jnp oracle path — the bytes column is
    the trajectory metric).
    """
    from repro.kernels.dispatch import select_backend
    from repro.launch.serve_gp import (
        SCENARIOS, GPFieldServer, demo_posterior, mixed_requests,
        scenario_chart,
    )

    backend = select_backend()
    slab = 4 if quick else 8
    n_fields, mc = (2, 4) if quick else (3, 16)
    for name, rho in SCENARIOS.items():
        chart = scenario_chart(name, quick=quick)
        for dt_name, pol in (("float32", None), ("bfloat16", "bf16")):
            post = demo_posterior(chart, rho, dtype_policy=pol)
            srv = GPFieldServer(post, slab=slab)
            t0 = time.perf_counter()
            srv.run(mixed_requests(n_fields, mc))
            cold = time.perf_counter() - t0

            rows0, fields0 = srv.rows_served, srv.fields_delivered
            slabs0 = srv.slabs_run
            reps = 2 if quick else 3
            t0 = time.perf_counter()
            for _ in range(reps):
                reqs = srv.run(mixed_requests(n_fields, mc))
            warm = (time.perf_counter() - t0) / reps
            assert all(r.done and r.error is None for r in reqs)
            assert srv.cache_misses == 1  # warm traffic never rebuilt

            rows = (srv.rows_served - rows0) / reps
            fields = (srv.fields_delivered - fields0) / reps
            # modeled bytes of ONE warm batch (slab estimate x slabs the
            # batch actually ran) — the same unit `warm` measures
            slabs_per_batch = (srv.slabs_run - slabs0) // reps
            hbm = srv.modeled_slab_bytes() * slabs_per_batch
            route = srv.route
            report(f"serving/{name}/{dt_name}/samples_per_s", rows / warm,
                   f"slab={slab} {rows:.0f} rows/batch "
                   f"{fields / warm:.1f} fields/s",
                   route=route, backend=backend, dtype=dt_name,
                   hbm_bytes=hbm, bw_util=_bw_util(hbm, warm), mesh=1)
            report(f"serving/{name}/{dt_name}/warm_cold_ratio", cold / warm,
                   "first-batch (compile+build) over warm-batch wall time",
                   mesh=1)


def run_serving_mesh(report, *, quick: bool = False):
    """Mesh-serving dimension (DESIGN.md §15; BENCH_PR8.json): warm
    samples/s at mesh size 1 vs every device that exists, plus the
    fault-recovery time — a device killed mid-stream to the first
    completed slab after the detect → remesh → rewarm → replay cycle.

    Runs ``repro.distributed.chaos.run_bench`` in this process, on the
    devices this process already holds: a child process could not take a
    chip this process owns. With one device there is no mesh to measure
    and the rows are skipped.
    """
    import jax

    from repro.distributed.chaos import run_bench

    if len(jax.devices()) < 2:
        print("# serving_mesh: skipped (one device)")
        return
    for row in run_bench(quick=quick):
        if row.get("mode") == "recovery":
            report("serving_mesh/tod/recovery_s", row["recovery_s"],
                   f"device kill -> first completed slab "
                   f"({row['replayed_slabs']} slab(s) replayed)",
                   mesh=row["mesh"])
        else:
            report(f"serving_mesh/tod/mesh{row['mesh']}/samples_per_s",
                   row["samples_per_s"],
                   f"{row['mode']} warm {row['warm_s']*1e3:.1f} ms/batch",
                   mesh=row["mesh"])


def run_scaling(report, sizes=(1024, 4096, 16384, 65536, 262144)):
    """O(N) scaling check (paper Eq. 13): time per point should flatten."""
    from repro.core import ICR, matern32, regular_chart

    ts = []
    for n in sizes:
        lvl = int(math.log2(n / 64))
        c = regular_chart(64, lvl, boundary="reflect")
        icr = ICR(chart=c, kernel=matern32.with_defaults(rho=4.0))
        mats = icr.matrices()
        xi = icr.init_xi(jax.random.PRNGKey(0))
        fwd = jax.jit(lambda m, x: icr.apply_sqrt(m, x))
        t = _bench(fwd, mats, xi)
        npts = c.size
        ts.append((npts, t))
        report(f"scaling/icr_n{npts}", t / npts * 1e9,
               f"{t/npts*1e9:.2f} ns/point (t={t*1e3:.2f}ms)")
    # linear fit in log-log: slope ~1 means O(N)
    xs = np.log([a for a, _ in ts])
    ys = np.log([b for _, b in ts])
    slope = float(np.polyfit(xs, ys, 1)[0])
    report("scaling/loglog_slope", slope,
           f"log-log slope={slope:.2f} (O(N) => ~1.0)")


def run_dtype(report, *, quick: bool = False):
    """Mixed-precision policy table (DESIGN.md §11): fp32 vs bf16 storage
    x pyramid on/off on the dust chart. Each row: wall time, selected
    route, modeled HBM bytes at that dtype, would-be bandwidth utilization.
    Off-TPU the wall time measures interpret-mode emulation; the bytes
    column is the trajectory metric (bf16 must halve it, the pyramid must
    erase the covered levels' inter-level field traffic).
    """
    from repro.core import ICR, matern32
    from repro.core.charts import galactic_dust_chart
    from repro.kernels.dispatch import plan, select_backend

    backend = select_backend()
    c = galactic_dust_chart((6, 8, 8), n_levels=2) if quick \
        else galactic_dust_chart((8, 16, 16), n_levels=3)
    n = int(np.prod(c.final_shape))
    totals = {}
    for dt_name, pol in (("float32", None), ("bfloat16", "bf16")):
        for pyr in (True, False):
            icr = ICR(chart=c, kernel=matern32.with_defaults(rho=0.5),
                      use_pallas=True, dtype_policy=pol, use_pyramid=pyr)
            mats = icr.matrices()
            xi = icr.init_xi(jax.random.PRNGKey(0))
            fwd = jax.jit(lambda m, x: icr.apply_sqrt(m, x))
            t = _bench(fwd, mats, xi, repeats=3 if quick else 5)
            entries = plan(c, dtype=dt_name, pyramid=pyr)
            hbm = sum(e["hbm_bytes"]["selected"] for e in entries)
            totals[(dt_name, pyr)] = hbm
            label = f"dtype/{dt_name}/{'pyramid' if pyr else 'per-level'}"
            report(label, t * 1e6,
                   f"N={n} t={t*1e3:.2f}ms est_bytes={hbm:,}",
                   route=entries[0]["route"], backend=backend,
                   hbm_bytes=hbm, bw_util=_bw_util(hbm, t), dtype=dt_name)
    report("dtype/bf16_bytes_reduction",
           totals[("float32", True)] / totals[("bfloat16", True)],
           "modeled HBM bytes fp32/bf16 (acceptance: >= 1.9x)")
    report("dtype/pyramid_bytes_reduction",
           totals[("bfloat16", False)] / totals[("bfloat16", True)],
           "modeled HBM bytes per-level/pyramid at bf16")


def run_cg(report, *, quick: bool = False):
    """Data-conditioning solver table (§16; BENCH_PR9.json): batched CG on
    the observation system (W K Wᵀ + σ²I) for the 1-D TOD and 2-D image
    scenarios — iterations-to-rtol and warm solves/s for the ICR-whitened
    preconditioner vs unpreconditioned CG vs the dense direct solve. The
    acceptance bar is the iteration ratio row: icr must need <=0.5x the
    unpreconditioned iterations."""
    from repro.core import ICR, matern32, regular_chart
    from repro.solvers import CGConfig, build_condition_system, pcg_solve
    from repro.solvers.gp_system import obs_operator

    cases = [
        ("tod", regular_chart(64, 2 if quick else 3, boundary="reflect"),
         8.0),
        ("image", regular_chart((8, 8), 2, boundary="reflect"), 4.0),
    ]
    k_rhs = 4 if quick else 8
    for name, chart, rho in cases:
        icr = ICR(chart=chart, kernel=matern32.with_defaults(rho=rho),
                  use_pallas=True)
        n = int(np.prod(chart.final_shape))
        obs_idx = np.arange(0, n, 2)
        noise = 0.25
        system = build_condition_system(
            icr, obs_operator(icr, obs_idx=obs_idx), noise ** 2)
        rng = np.random.default_rng(0)
        b = jnp.asarray(rng.standard_normal((k_rhs, obs_idx.size)),
                        jnp.float32)
        cfg = CGConfig(rtol=1e-6, max_iters=4 * obs_idx.size)

        iters = {}
        for variant, pc in (("icr", system.precond), ("none", None)):
            x, stats, _, _ = pcg_solve(system.matvec, b, precond=pc,
                                       cfg=cfg)
            its = int(np.max(np.asarray(stats["iters"])))
            iters[variant] = its
            t = _bench(lambda: pcg_solve(system.matvec, b, precond=pc,
                                         cfg=cfg)[0],
                       repeats=2 if quick else 5)
            report(f"cg/{name}/{variant}/solve", t * 1e6,
                   f"N={n} n_obs={obs_idx.size} k={k_rhs} iters={its} "
                   f"{k_rhs / t:.1f} solves/s")
        t_d = _bench(lambda: system.dense_solve(b),
                     repeats=2 if quick else 5)
        report(f"cg/{name}/dense/solve", t_d * 1e6,
               f"N={n} n_obs={obs_idx.size} k={k_rhs} "
               f"{k_rhs / t_d:.1f} solves/s")
        ratio = iters["icr"] / iters["none"]
        report(f"cg/{name}/iter_ratio", ratio,
               f"icr {iters['icr']} vs unpreconditioned {iters['none']} "
               f"iterations to rtol=1e-6 (bar: <=0.5)")
        assert ratio <= 0.5, \
            f"ICR preconditioner ratio {ratio:.2f} misses the 0.5x bar"
