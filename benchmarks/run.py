"""Benchmark harness — one module per paper table/figure.

  accuracy  — paper Fig. 3 / §5.1 (covariance errors, KL parameter sweep)
  speed     — paper Fig. 4 / §5.2 (forward pass: ICR vs KISS-GP)
  nd        — N-D Pallas paths: fused level megakernel vs per-axis passes
              vs jnp reference — 2-D/3-D parity (<=1e-5) + wall time
  batch     — batched-sample throughput: native sample-batch kernel dim
              vs a per-sample loop (DESIGN.md §10)
  dtype     — mixed-precision policy (DESIGN.md §11): fp32 vs bf16 storage
              x pyramid on/off — walltime, modeled bytes, bandwidth util
  serving   — GP posterior serving (DESIGN.md §12): the three chart
              scenarios x fp32/bf16 through launch.serve_gp's slab-packed
              server — warm samples/s + fields/s, modeled bytes, bw util
  serving_mesh — mesh serving (DESIGN.md §15): samples/s at mesh 1 vs 8
              virtual CPU devices + fault-recovery time (device kill ->
              first completed slab), via repro.distributed.chaos --bench
  cg        — data-conditioning solvers (DESIGN.md §16): batched CG on
              (W K Wᵀ + σ²I) — iterations-to-rtol + solves/s, ICR-whitened
              preconditioner vs unpreconditioned vs dense (BENCH_PR9.json)
  scaling   — paper Eq. 13 (O(N) check, log-log slope)
  vi        — §3.2 end-to-end: standardized GP regression (MAP)
  grad      — one value_and_grad step of the §3.2 loss: fused adjoint
              kernels vs the jnp reference path (training-time cost)

Prints ``name,us_per_call,derived`` CSV rows. ``--quick`` trims sizes for
CI; ``--only <name>`` runs one table; ``--json <path>`` additionally emits
machine-readable rows (name, us_per_call, route, backend, dtype,
estimated HBM bytes, bandwidth utilization against the TPU-v5e roofline
constant — on CPU/interpret backends the utilization is the *would-be*
number at TPU bandwidth, a traffic metric, not a measurement) so the perf
trajectory is tracked across PRs (CI uploads ``BENCH_PR4.json``).
"""
import argparse
import json
import os
import platform
import re
import sys
import time

_ROWS = []


def _report(name: str, value: float, derived: str = "", **extra):
    print(f"{name},{value:.6g},{derived}", flush=True)
    row = {"name": name, "us_per_call": float(value), "derived": derived}
    for key in ("route", "backend", "hbm_bytes", "bw_util", "dtype", "mesh"):
        if key in extra and extra[key] is not None:
            row[key] = extra[key]
    _ROWS.append(row)


def run_vi(report):
    """End-to-end §3.2: MAP GP regression with the ICR prior (no kernel
    inversion anywhere); reports wall time per optimization step + recon
    quality."""
    import numpy as np
    import jax
    import jax.numpy as jnp
    from repro.core import (
        ICR, gaussian_log_likelihood, map_fit, matern32, regular_chart,
    )
    from repro.data import charted_gp_dataset

    c = regular_chart(64, 4, boundary="reflect")  # 1024 points
    icr = ICR(chart=c, kernel=matern32.with_defaults(rho=40.0))
    truth, obs_idx, y = charted_gp_dataset(icr, jax.random.PRNGKey(0))
    mats = icr.matrices()
    ll = gaussian_log_likelihood(0.05, obs_idx)
    fwd = lambda xi: icr.apply_sqrt(mats, xi)
    t0 = time.perf_counter()
    steps = 200
    xi, losses = map_fit(ll, fwd, icr.zero_xi(), y, steps=steps)
    jax.block_until_ready(xi)
    dt = time.perf_counter() - t0
    rec = np.asarray(fwd(xi).reshape(-1))
    rmse = float(np.sqrt(np.mean((rec - np.asarray(truth)) ** 2)))
    report("vi/map_step", dt / steps * 1e6,
           f"N={c.size} rmse={rmse:.3f} loss {float(losses[0]):.0f}->"
           f"{float(losses[-1]):.0f}")


def run_grad(report, *, quick: bool = False):
    """Backward-pass table (paper §1: inference = two sqrt applications +
    the VJP): wall time of one jitted value_and_grad of the standardized
    loss, fused custom-VJP path vs the jnp reference, per chart.

    Off-TPU the fused path runs in Pallas interpret mode (BlockSpec
    machinery emulated in jnp), so CPU rows measure correctness plumbing,
    not the kernel — the derived column records the backend for that reason.
    """
    import numpy as np
    import jax
    import jax.numpy as jnp
    from repro.core import ICR, matern32, regular_chart
    from repro.core.charts import galactic_dust_chart, log_chart
    from repro.kernels import dispatch

    backend = dispatch.select_backend()

    cases = [
        ("1d-stationary", lambda: regular_chart(64, 3 if quick else 5,
                                                boundary="reflect")),
        ("1d-charted", lambda: log_chart(64, 3 if quick else 5,
                                         n_csz=5, n_fsz=4, delta0=0.05)),
        ("3d-dust", lambda: galactic_dust_chart(
            (6, 8, 8) if quick else (8, 16, 16), n_levels=2)),
    ]
    for name, chartf in cases:
        chart = chartf()
        timings = {}
        for fused in (False, True):
            icr = ICR(chart=chart, kernel=matern32.with_defaults(rho=4.0),
                      use_pallas=fused)
            mats = icr.matrices()
            xi = icr.init_xi(jax.random.PRNGKey(0))

            def loss(xs):
                s = icr.apply_sqrt(mats, xs)
                return 0.5 * jnp.sum(jnp.square(s))

            step = jax.jit(jax.value_and_grad(loss))
            jax.block_until_ready(step(xi))  # compile
            reps = 3 if quick else 10
            t0 = time.perf_counter()
            for _ in range(reps):
                jax.block_until_ready(step(xi))
            us = (time.perf_counter() - t0) / reps * 1e6
            timings[fused] = us
            bk = backend if fused else "jnp"
            route = (dispatch.plan(chart)[-1]["route"] if fused
                     else "reference")
            report(f"grad/{name}/{'fused' if fused else 'reference'}", us,
                   f"N={int(np.prod(chart.final_shape))} backend={bk}",
                   route=route, backend=bk)
        report(f"grad/{name}/speedup", timings[False] / timings[True],
               f"reference/fused wall-time ratio ({backend})")


def _pr_tag(path: str):
    """PR tag encoded in a trajectory filename (BENCH_PR3.json -> PR3)."""
    m = re.search(r"BENCH_(PR\d+)", os.path.basename(path))
    return m.group(1) if m else None


def _write_json(path: str, *, quick: bool, force: bool = False) -> None:
    import jax

    tag = _pr_tag(path)
    if os.path.exists(path) and not force:
        # The BENCH_PR*.json files are a per-PR perf trajectory: each is
        # seeded once by its PR and then only regenerated knowingly.
        # Refuse to clobber a file whose recorded PR differs from the tag
        # in the target filename (or one we can't read) — rewriting the
        # *same* PR's file is fine, which is what CI does on every run.
        try:
            with open(path) as fh:
                prev = json.load(fh).get("meta", {}).get("pr")
        except (OSError, ValueError):
            prev = "<unreadable>"
        if tag is None or prev != tag:
            sys.exit(f"refusing to overwrite {path}: it records pr={prev!r} "
                     f"but the target name implies {tag!r} — pass --force "
                     f"to re-baseline a prior PR's trajectory file")
    doc = {
        "meta": {
            "pr": tag or "PR?",
            "backend": jax.default_backend(),
            "python": platform.python_version(),
            "jax": jax.__version__,
            "quick": bool(quick),
        },
        "rows": _ROWS,
    }
    with open(path, "w") as fh:
        json.dump(doc, fh, indent=2, sort_keys=True)
    print(f"wrote {len(_ROWS)} rows to {path}", file=sys.stderr)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="run only these tables (comma-separated)")
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="also write machine-readable rows (BENCH_PR5.json)")
    ap.add_argument("--force", action="store_true",
                    help="allow --json to overwrite a prior PR's "
                         "BENCH_PR*.json trajectory file")
    args = ap.parse_args()
    from repro.launch.cache import enable_compile_cache

    enable_compile_cache()

    from . import accuracy, speed

    tables = {
        "accuracy": lambda: accuracy.run(_report),
        "speed": lambda: speed.run(
            _report, sizes=(256, 1024, 4096) if args.quick
            else (256, 1024, 4096, 16384, 65536)),
        "nd": lambda: (speed.run_nd(_report),
                       accuracy.run_nd_cov(_report)),
        "batch": lambda: speed.run_batch(_report, quick=args.quick),
        "dtype": lambda: speed.run_dtype(_report, quick=args.quick),
        "serving": lambda: speed.run_serving(_report, quick=args.quick),
        "serving_mesh": lambda: speed.run_serving_mesh(_report,
                                                       quick=args.quick),
        "cg": lambda: speed.run_cg(_report, quick=args.quick),
        "scaling": lambda: speed.run_scaling(
            _report, sizes=(1024, 4096, 16384) if args.quick
            else (1024, 4096, 16384, 65536, 262144)),
        "vi": lambda: run_vi(_report),
        "grad": lambda: run_grad(_report, quick=args.quick),
    }
    only = set(args.only.split(",")) if args.only else None
    if only:
        unknown = only - set(tables)
        if unknown:
            ap.error(f"unknown table(s) {sorted(unknown)}; "
                     f"have {sorted(tables)}")
    print("name,us_per_call,derived")
    for name, fn in tables.items():
        if only and name not in only:
            continue
        t0 = time.time()
        fn()
        _report(f"{name}/_table_wall_s", (time.time() - t0) * 1e6, "")
    if args.json:
        _write_json(args.json, quick=args.quick, force=args.force)


if __name__ == "__main__":
    main()
