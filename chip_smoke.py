#!/usr/bin/env python3
"""Chip smoke test: ICR's served path on a TPU at deployment sizes.

Drives ``GPFieldServer`` (-> ``ICR.apply_sqrt_batch`` / ``apply_sqrt_T`` ->
``dispatch.refine`` -> the Pallas kernels) once per chart, with posteriors
made from a seed (``demo_posterior``):

  dust  ``galactic_dust_chart((8, 64, 8), 5)``: 134M voxels, fp32 and bf16
        (``icr-dust-pod``'s final grid; its (16, 128, 16) coarse grid would
        need a dense eigh of a 32,768^2 level-0 kernel matrix, 4.29 GB per
        copy, beside a 2 GB field slab on a 16 GB chip)
  tod   ``regular_chart(1024, 12, boundary="reflect")``: 4.2M points, plus
        one ``condition`` request (256 on-grid observations) and three
        ``core.vi.map_fit`` steps (the adjoint kernels under
        ``value_and_grad``)
  log   ``log_chart(1024, 11, n_csz=5, n_fsz=4, delta0=0.02,
        boundary="reflect")``: 2.1M points (``icr-log1d`` with 11 of its
        17 levels)

The 1-D charts keep their coarse grids and stencils and are cut in depth
so that the whole smoke ends well inside 20 minutes: at 17 levels (134M
points) the tod slab took ~22 s per 4-row batch on a v5e, and the
conditioning solve (two full applications per CG iteration) did not end
within the limit. Both 1-D charts still run their pyramid prefix and
their per-level kernel route (stationary-1d x5, charted-1d x6).

Each chart serves a slab of 4 through ``sample`` and ``moments`` requests.
The script asserts that every level runs on the ``pallas`` backend (no
``reference`` route), forward and backward (each level's backward runs its
adjoint kernels), that the compiled slab contains ``tpu_custom_call``,
and that one served sample agrees with the float32 jnp oracle of the same
levels (``kernels/ref.py``, ``core/refine.refine_level``) run at
``highest`` matmul precision:

  fp32  relative L2 <= 1e-4: the kernels add the same float32 products as
        the oracle in another order (rounding ~1e-7 per level), and the
        oracle's dense level-0 sqrt is applied at the same precision;
  bf16  relative L2 <= 5e-2: fields, excitations and matrices are stored
        with 8 mantissa bits (unit roundoff 2^-9 ~ 2e-3); rounding every
        stored field and matrix over the dust chart's level 0 and 5
        levels accumulates to ~sqrt(6) * 2 * 2e-3 ~ 1e-2, so 5e-2 keeps a
        5x margin.

``--chips 4`` runs only the sharded phase and the one-chip run it is
compared with: sample-sharded rows must be bit-identical to one chip, and
the chart-sharded dust ring must agree with one chip to 1e-5 relative L2
(the ring refines the same math on halo-padded blocks).

Run it on a machine with a TPU from the repository root:

    python chip_smoke.py            # one chip
    python chip_smoke.py --chips 4  # the 4-chip phase

The last line of standard output is one JSON object naming the device;
any failure exits non-zero before printing it. Without an accelerator (or
outside the repository) it exits non-zero and prints no result.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))

SLAB = 4
TOL = {"fp32": 1e-4, "bf16": 5e-2}
CHART_TOL = 1e-5


def fail(msg: str):
    print(f"chip_smoke: FAIL {msg}", file=sys.stderr, flush=True)
    sys.exit(1)


def log(msg: str):
    print(msg, flush=True)


def setup():
    src = os.path.join(HERE, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        fail(f"no repro package under {src}: run from the repository")
    sys.path.insert(0, src)
    backend = os.environ.get("REPRO_BACKEND")
    if backend not in (None, "", "pallas"):
        fail(f"REPRO_BACKEND={backend!r}: the chip runs the pallas kernels")
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        fail(f"no TPU: JAX found {devs[0].platform} devices")
    from repro.launch.cache import enable_compile_cache
    from repro.launch.mesh import device_spec

    device_spec(devs[0].device_kind)  # a chip the peaks table must know
    log(f"compile cache: {enable_compile_cache()}")
    return jax, devs


def charts():
    from repro.core import regular_chart
    from repro.core.charts import galactic_dust_chart, log_chart

    return {
        "dust": (galactic_dust_chart((8, 64, 8), 5), 0.5),
        "tod": (regular_chart(1024, 12, boundary="reflect"), 8.0),
        "log": (log_chart(1024, 11, n_csz=5, n_fsz=4, delta0=0.02,
                          boundary="reflect"), 1.0),
    }


def peak_gib(jax) -> float:
    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use", 0) / 2**30


def host_gib() -> float:
    """This process's resident host memory. The TPU runtime alone holds
    ~13 GiB of it once the chip is open, so every field the smoke keeps on
    the host (0.5 GB per 134M-point row) counts against what is left."""
    with open("/proc/self/status") as f:
        for line in f:
            if line.startswith("VmRSS:"):
                return int(line.split()[1]) / 2**20
    return float("nan")


def row_excitation(jax, post, seed: int, row: int):
    """The ξ the server draws for (seed, row): the same keying and noise
    as ``GPFieldServer``'s sampling slab (posterior mean + std·N(0, 1))."""
    import jax.numpy as jnp

    mean, std = list(post.mean), post.std()
    k = jax.random.fold_in(jax.random.PRNGKey(seed), row)
    ks = jax.random.split(k, len(mean))
    return [m + s * jax.random.normal(kk, m.shape, m.dtype)
            for kk, m, s in zip(ks, mean, std)]


def refine_1d_slices(coarse, xi, r, d, geom):
    """Paper Eq. 9 on one 1-D level with strided slices only: child ``f``
    of family ``t`` is ``sum_k R_t[f, k] c[t·s + k] + sum_j D_t[f, j]
    xi[t, j]`` on the boundary-padded coarse field ``c``. The same math as
    ``core.refine.refine_level``, which stacks the ``(T, n_csz)`` windows:
    at 10^8 points that stack, padded to 128 lanes on the chip, needs
    32 GiB. Here every array is a plain vector."""
    from jax import lax
    import jax.numpy as jnp

    t, s, csz, fsz = geom.T[0], geom.stride, geom.n_csz, geom.n_fsz
    if geom.boundary == "reflect":
        coarse = jnp.pad(coarse, (geom.b, geom.b), mode="reflect")
    win = [lax.slice_in_dim(coarse, k, k + s * (t - 1) + 1, stride=s)
           for k in range(csz)]
    noise = [xi[:, j] for j in range(fsz)]
    r = r.reshape(-1, fsz, csz)  # (1 or T, fsz, csz): stationary or charted
    d = d.reshape(-1, fsz, fsz)
    out = jnp.zeros(t * fsz, jnp.float32)
    for f in range(fsz):
        acc = sum(r[:, f, k] * win[k] for k in range(csz))
        acc = acc + sum(d[:, f, j] * noise[j] for j in range(fsz))
        out = out.at[f::fsz].set(acc)
    return out.reshape(geom.fine_shape)


def oracle(jax, icr, xi):
    """The float32 jnp oracle of the same levels: the dense level-0 sqrt,
    then the joint 1-D matrices applied by ``refine_1d_slices`` (checked
    against ``core.refine.refine_level`` on the first level), or the
    per-axis factor oracle ``ref.refine_axes_ref`` on an N-D chart — the
    math the kernels implement, at ``highest`` matmul precision."""
    import jax.numpy as jnp

    from repro.core import ICR
    from repro.core.refine import LevelGeom, refine_level
    from repro.kernels import ref

    f32 = ICR(chart=icr.chart, kernel=icr.kernel, jitter=icr.jitter,
              use_pallas=True)
    chart = icr.chart
    with jax.default_matmul_precision("highest"):
        mats = f32.matrices()
        field = jnp.matmul(mats["sqrt0"], xi[0].astype(jnp.float32))
        field = field.reshape(chart.shape0)
        for lvl in range(chart.n_levels):
            geom = LevelGeom.for_level(chart, lvl)
            x = xi[lvl + 1].astype(jnp.float32)
            if chart.ndim == 1:
                args = (field, x, mats["R"][lvl], mats["sqrtD"][lvl])
                step = jax.jit(lambda f, x, r, d, g=geom:
                               refine_1d_slices(f, x, r, d, g))
                if lvl == 0:
                    err = rel_l2(jax, step(*args), jax.jit(
                        lambda f, x, r, d, g=geom:
                        refine_level(f, x, r, d, g))(*args))
                    if not err <= 1e-6:
                        fail(f"1-D slice oracle vs refine_level: rel L2 "
                             f"{err:.3e}")
                field = step(*args)
            else:
                step = jax.jit(lambda f, x, rs, ds, g=geom:
                               ref.refine_axes_ref(
                                   f, x, rs, ds, T=g.T, n_fsz=g.n_fsz,
                                   boundary=g.boundary, b=g.b))
                field = step(field, x, mats["Rax"][lvl],
                             mats["sqrtDax"][lvl])
        return jax.block_until_ready(field)


def rel_l2(jax, a, b) -> float:
    import jax.numpy as jnp

    a, b = jnp.asarray(a, jnp.float32), jnp.asarray(b, jnp.float32)
    return float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))


def check_plan(srv, label: str):
    from collections import Counter

    plan = srv._entry["plan"]
    bad = [e for e in plan
           if e["backend"] != "pallas" or e["route"] == "reference"
           or e["vjp"]["backend"] != "pallas"
           or e["vjp"]["route"] != e["route"] + "-adjoint"]
    if bad:
        fail(f"{label}: levels off the kernel path (forward or "
             f"backward): {bad}")
    if srv.serving_mode != "single:pallas":
        fail(f"{label}: serving mode {srv.serving_mode!r}")
    routes = Counter(e["route"] for e in plan)
    vjps = Counter(e["vjp"]["route"] for e in plan)
    return "; ".join(", ".join(f"{r} x{n}" for r, n in c.items())
                     for c in (routes, vjps))


def serve_chart(jax, name: str, dtype: str):
    import numpy as np

    from repro.launch.serve_gp import GPFieldServer, GPRequest, \
        demo_posterior

    chart, rho = charts()[name]
    label = f"{name}/{dtype}"
    t0 = time.perf_counter()
    post = demo_posterior(chart, rho,
                          dtype_policy="bf16" if dtype == "bf16" else None)
    srv = GPFieldServer(post, slab=SLAB)
    jax.block_until_ready(srv._entry["mats"])
    build_s = time.perf_counter() - t0
    routes = check_plan(srv, label)
    log(f"[{label}] chart {chart.shape0} x {chart.n_levels} levels -> "
        f"{chart.final_shape} ({chart.size:,} points); routes (forward; "
        f"backward): {routes}")

    t0 = time.perf_counter()
    compiled = srv.lowered_slab().compile()
    compile_s = time.perf_counter() - t0
    if "tpu_custom_call" not in compiled.as_text():
        fail(f"{label}: the compiled slab holds no tpu_custom_call")

    def requests():
        return [GPRequest(kind="sample", n=SLAB, seed=11),
                GPRequest(kind="moments", n=2 * SLAB, seed=12)]

    def batch():
        """Serve one batch and check it; keep only the first sample (a
        batch is 6.4 GB on the host at 134M points)."""
        t0 = time.perf_counter()
        sample_req, moments_req = srv.run(requests())
        wall = time.perf_counter() - t0
        for req in (sample_req, moments_req):
            if req.error:
                fail(f"{label}: request failed: {req.error}")
        for f in sample_req.fields:
            if f.shape != tuple(chart.final_shape) or not np.isfinite(f).all():
                fail(f"{label}: served sample shape {f.shape} or non-finite")
        if not (np.isfinite(moments_req.mean).all()
                and np.isfinite(moments_req.std).all()
                and (moments_req.std > 0).all()):
            fail(f"{label}: moments not finite/positive")
        return sample_req.fields[0], wall

    _, cold_s = batch()
    sample, warm_s = batch()
    log(f"[{label}] matrices+posterior {build_s:.1f} s, slab compile "
        f"{compile_s:.1f} s, cold batch {cold_s:.1f} s, warm batch "
        f"{warm_s:.3f} s ({SLAB + 2 * SLAB} rows), peak HBM "
        f"{peak_gib(jax):.2f} GiB, host RSS {host_gib():.2f} GiB")

    extra = {}
    if name == "tod":
        extra = tod_extras(jax, srv, post, label)

    # one served sample against the f32 oracle: row 0 of the sample request
    xi = row_excitation(jax, post, seed=11, row=0)
    icr = post.icr
    del srv, compiled
    t0 = time.perf_counter()
    want = oracle(jax, icr, xi)
    err = rel_l2(jax, sample, want)
    log(f"[{label}] served sample vs f32 oracle: rel L2 {err:.3e} "
        f"(tolerance {TOL[dtype]:g}; oracle {time.perf_counter() - t0:.1f}"
        f" s), host RSS {host_gib():.2f} GiB")
    if not err <= TOL[dtype]:
        fail(f"{label}: rel L2 {err:.3e} > {TOL[dtype]:g}")
    return dict(extra, err=err, compile_s=compile_s, warm_s=warm_s)


def tod_extras(jax, srv, post, label: str) -> dict:
    """One conditioned request and three MAP steps on the tod chart."""
    import jax.numpy as jnp
    import numpy as np

    from repro.core.vi import gaussian_log_likelihood, map_fit
    from repro.launch.serve_gp import GPRequest

    icr = post.icr
    n = icr.chart.size
    obs_idx = np.linspace(0, n - 1, 256).astype(np.int64)
    y = np.random.default_rng(3).normal(size=256).astype(np.float32)
    t0 = time.perf_counter()
    (req,) = srv.run([GPRequest(kind="condition", n=2, seed=5, y=y,
                                obs_idx=obs_idx, noise_std=0.5)])
    cond_s = time.perf_counter() - t0
    if req.error or not np.isfinite(req.mean).all():
        fail(f"{label}: condition request failed: {req.error}")
    log(f"[{label}] condition (256 obs): {cond_s:.1f} s, "
        f"{req.report}")

    mats = srv._entry["mats"]
    ll = gaussian_log_likelihood(0.5, obs_idx=jnp.asarray(obs_idx))
    xi0 = icr.zero_xi(jnp.float32)
    t0 = time.perf_counter()
    _, losses = map_fit(ll, lambda xi: icr.apply_sqrt(mats, xi), xi0,
                        jnp.asarray(y), steps=3)
    losses = np.asarray(jax.block_until_ready(losses))
    fit_s = time.perf_counter() - t0
    if not np.isfinite(losses).all():
        fail(f"{label}: map_fit losses not finite: {losses}")
    log(f"[{label}] map_fit 3 steps (compile included): {fit_s:.1f} s, "
        f"losses {np.array2string(losses, precision=4)}")
    return {"condition_s": cond_s, "fit_s": fit_s}


def one_chip(jax):
    for name, dtype in (("dust", "fp32"), ("dust", "bf16"), ("tod", "fp32"),
                        ("log", "fp32")):
        serve_chart(jax, name, dtype)


def four_chips(jax, devs):
    """Sample-sharded rows bit-identical to one chip; the chart-sharded
    dust ring within CHART_TOL of one chip."""
    import numpy as np
    from jax.sharding import Mesh

    from repro.launch.serve_gp import GPFieldServer, GPRequest, \
        demo_posterior

    if len(devs) < 4:
        fail(f"--chips 4 needs 4 devices, found {len(devs)}")
    mesh = Mesh(np.asarray(devs[:4]), ("data",))
    chart, rho = charts()["dust"]
    post = demo_posterior(chart, rho)

    def fields(srv, n):
        t0 = time.perf_counter()
        (req,) = srv.run([GPRequest(kind="sample", n=n, seed=21)])
        if req.error:
            fail(f"request failed: {req.error}")
        return np.stack(req.fields), time.perf_counter() - t0

    one = GPFieldServer(post, slab=SLAB)
    check_plan(one, "dust/1-chip")
    ref, t1 = fields(one, 2 * SLAB)
    log(f"[4-chip] dust one-chip reference: {2 * SLAB} rows in {t1:.1f} s")
    del one

    srv = GPFieldServer(post, slab=4 * SLAB, mesh=mesh, shard="samples")
    if srv.serving_mode != "sharded-samples:pallas":
        fail(f"samples mode runs as {srv.serving_mode!r}")
    got, t4 = fields(srv, 2 * SLAB)
    same = bool(np.array_equal(got, ref))
    log(f"[4-chip] shard=samples on {len(mesh.devices)} chips: "
        f"{2 * SLAB} rows in {t4:.1f} s; bit-identical to one chip: {same}")
    if not same:
        fail("sample-sharded rows differ from the one-chip rows")
    del srv

    # the ring body holds ~2.5 GiB of temporaries per row on each chip
    # (compile rehearsal): half a slab keeps the chart phase well inside
    # 16 GB; rows are keyed (seed, row), so the slab size changes nothing
    srv = GPFieldServer(post, slab=SLAB // 2, mesh=mesh, shard="chart")
    if srv.serving_mode != "sharded-chart:pallas":
        fail(f"chart mode runs as {srv.serving_mode!r}")
    if srv.degradations:
        fail(f"chart mode degraded: {srv.degradations}")
    text = srv.lowered_slab().compile().as_text()
    if "tpu_custom_call" not in text:
        fail("the chart-sharded slab holds no tpu_custom_call")
    got, tc = fields(srv, 2 * SLAB)
    err = rel_l2(jax, got, ref)
    log(f"[4-chip] shard=chart (ring over axis 0, halo exchange) on "
        f"{len(mesh.devices)} chips: {2 * SLAB} rows in {tc:.1f} s; rel L2 "
        f"vs one chip {err:.3e} (tolerance {CHART_TOL:g}); peak HBM chip 0 "
        f"{peak_gib(jax):.2f} GiB")
    if not err <= CHART_TOL:
        fail(f"chart-sharded rel L2 {err:.3e} > {CHART_TOL:g}")


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the sharded phase (needs 4 chips)")
    args = ap.parse_args()
    jax, devs = setup()
    log(f"device: {devs[0].device_kind} x {len(devs)} "
        f"(jax {jax.__version__})")
    t0 = time.perf_counter()
    if args.chips == 4:
        four_chips(jax, devs)
    else:
        one_chip(jax)
    log(f"total {time.perf_counter() - t0:.1f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}), flush=True)


if __name__ == "__main__":
    main()
