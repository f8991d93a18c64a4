import os
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("XLA_FLAGS", "")
)
# The two lines above MUST run before any jax import: jax locks the device
# count at first initialization (assignment: MULTI-POD DRY-RUN §0).

"""Multi-pod dry-run driver (assignment deliverable e).

For every (architecture x input-shape) cell and both production meshes
(16x16 single pod, 2x16x16 multi-pod) this driver:

  1. builds the jitted, sharded step (train / prefill / serve),
  2. ``.lower(**ShapeDtypeStructs).compile()`` — no buffers are allocated,
  3. prints ``compiled.memory_analysis()`` (proves the cell fits 16 GB HBM)
     and ``compiled.cost_analysis()`` (FLOPs/bytes for §Roofline),
  4. parses collective bytes out of the post-SPMD HLO,
  5. emits a JSON row consumed by EXPERIMENTS.md §Dry-run / §Roofline.

Run one cell:   python -m repro.launch.dryrun --cell gemma3-27b:train_4k:pod
Run everything: python -m repro.launch.dryrun --all --out experiments/dryrun
ICR cells:      python -m repro.launch.dryrun --cell icr-dust122b:sample:pod
"""
import argparse
import json
import subprocess
import sys
import time
import traceback
from concurrent.futures import ThreadPoolExecutor

import numpy as np


def _mesh_for(kind: str):
    import jax
    from repro.launch.mesh import make_production_mesh
    return make_production_mesh(multi_pod=(kind == "multipod"))


def run_lm_cell(arch: str, shape: str, mesh_kind: str) -> dict:
    import jax
    from repro.configs import SHAPES, get_arch, input_specs
    from repro.launch.steps import (
    active_param_count,
    choose_accum,
    make_prefill_step,
    make_serve_step,
    make_train_step,
)
    from repro.models import build_model
    from repro.roofline.analysis import (
        analyze_compiled,
        model_flops_decode,
        model_flops_train,
    )

    cfg = get_arch(arch)
    cell = SHAPES[shape]
    mesh = _mesh_for(mesh_kind)
    n_chips = int(np.prod(list(mesh.shape.values())))
    row = {"arch": arch, "shape": shape, "mesh": mesh_kind,
           "chips": n_chips, "status": "?"}

    if shape not in cfg.shape_cells:
        row.update(status="SKIP", reason=cfg.notes)
        return row

    model = build_model(cfg)
    n_active = active_param_count(model)
    row["params_total"] = model.param_count()
    row["params_active"] = n_active
    t0 = time.time()

    if cell.kind == "train":
        accum = choose_accum(model, cell, mesh)
        ts = make_train_step(cfg, mesh, accum=accum)
        row["accum"] = accum
        row["optimizer"] = ts.opt_name
        specs = input_specs(cfg, cell)
        jit_fn, batch_sh = ts.fn(specs)
        p_spec = ts.model.params_spec()
        o_spec = jax.eval_shape(ts.optimizer.init, p_spec)
        lowered = jit_fn.lower(p_spec, o_spec, specs)
        tokens = cell.global_batch * (
            min(cell.seq_len, cfg.encoder.max_target)
            if cfg.encoder else cell.seq_len)
        mf = model_flops_train(n_active, tokens) / n_chips
    elif cell.kind == "prefill":
        model, params_sh, jit_for = make_prefill_step(cfg, mesh)
        specs = input_specs(cfg, cell)
        specs.pop("labels", None)
        fn, _ = jit_for(specs)
        lowered = fn.lower(model.params_spec(), specs)
        tokens = cell.global_batch * (
            min(cell.seq_len, cfg.encoder.max_target)
            if cfg.encoder else cell.seq_len)
        mf = model_flops_decode(n_active, tokens) / n_chips
    else:  # decode
        s_max = min(cell.seq_len,
                    cfg.encoder.max_target) if cfg.encoder else cell.seq_len
        model, step, params_sh, cache_sh, c_spec = make_serve_step(
            cfg, mesh, cell.global_batch, s_max)
        specs = input_specs(cfg, cell)
        lowered = step.lower(model.params_spec(), c_spec, specs["tokens"],
                             specs["positions"])
        mf = model_flops_decode(n_active, cell.global_batch) / n_chips

    row["lower_s"] = round(time.time() - t0, 1)
    t1 = time.time()
    compiled = lowered.compile()
    row["compile_s"] = round(time.time() - t1, 1)

    terms = analyze_compiled(compiled, model_flops_per_device=mf)
    row.update(status="OK", **terms.summary())
    return row


def run_icr_cell(arch: str, mesh_kind: str) -> dict:
    import jax
    from repro.compat import use_mesh
    from repro.configs.registry import ICR_ARCHS
    from repro.core.distributed import DistributedICR
    from repro.roofline.analysis import analyze_compiled

    spec = ICR_ARCHS[arch]
    mesh = _mesh_for(mesh_kind)
    n_chips = int(np.prod(list(mesh.shape.values())))
    axes = ("pod", "data", "model") if mesh_kind == "multipod" else \
        ("data", "model")
    row = {"arch": arch, "shape": "sample", "mesh": mesh_kind,
           "chips": n_chips, "status": "?"}
    icr = spec.build()
    dist = DistributedICR(icr=icr, mesh=mesh, axis_names=axes,
                          shard_axis=0 if spec.kind == "log1d" else 1)
    row["points"] = int(np.prod(icr.chart.final_shape))
    t0 = time.time()
    mats_spec = jax.eval_shape(icr.matrices)
    xi_spec = [jax.ShapeDtypeStruct(s, np.float32)
               for s in dist.xi_structure()]
    mat_sh, xi_sh, out_sh = dist.shardings()
    with use_mesh(mesh):
        fn = jax.jit(dist.apply_sqrt, in_shardings=(mat_sh, tuple(xi_sh)),
                     out_shardings=out_sh)
        lowered = fn.lower(mats_spec, tuple(xi_spec))
        row["lower_s"] = round(time.time() - t0, 1)
        t1 = time.time()
        compiled = lowered.compile()
    row["compile_s"] = round(time.time() - t1, 1)
    # useful flops: refinement einsums, Sum_l F_l * (2*fsz*csz + 2*fsz^2)
    c = icr.chart
    nd, fsz, csz = c.ndim, c.n_fsz**c.ndim, c.n_csz**c.ndim
    mf = 0.0
    for lvl in range(c.n_levels):
        f_l = np.prod([c.family_count(lvl, a) for a in range(nd)])
        mf += f_l * (2 * fsz * csz + 2 * fsz * fsz)
    terms = analyze_compiled(compiled, model_flops_per_device=mf / n_chips)
    row.update(status="OK", **terms.summary())
    return row


def run_cell(cell_id: str) -> dict:
    arch, shape, mesh_kind = cell_id.split(":")
    try:
        if arch.startswith("icr-"):
            return run_icr_cell(arch, mesh_kind)
        return run_lm_cell(arch, shape, mesh_kind)
    except Exception as exc:  # noqa: BLE001
        return {"arch": arch, "shape": shape, "mesh": mesh_kind,
                "status": "FAIL", "error": f"{type(exc).__name__}: {exc}",
                "traceback": traceback.format_exc()[-2000:]}


def all_cells():
    from repro.configs import SHAPES, ARCHS
    from repro.configs.registry import ICR_ARCHS
    cells = []
    for arch in sorted(ARCHS):
        for shape in SHAPES:
            for mesh_kind in ("pod", "multipod"):
                cells.append(f"{arch}:{shape}:{mesh_kind}")
    for arch in sorted(ICR_ARCHS):
        for mesh_kind in ("pod", "multipod"):
            cells.append(f"{arch}:sample:{mesh_kind}")
    return cells


def _run_in_subprocess(cell_id: str, timeout: int = 3600) -> dict:
    """Each cell gets a fresh process: jax device state is per-process and a
    pathological compile can't take down the sweep."""
    cmd = [sys.executable, "-m", "repro.launch.dryrun", "--cell", cell_id,
           "--json-only"]
    env = dict(os.environ)
    env["PYTHONPATH"] = env.get("PYTHONPATH", "src")
    # a CPU-only tool: the cells compile on virtual host devices, and a
    # child must never reach for an accelerator the parent may hold
    env["JAX_PLATFORMS"] = "cpu"
    try:
        out = subprocess.run(cmd, capture_output=True, text=True,
                             timeout=timeout, env=env)
        for line in reversed(out.stdout.strip().splitlines()):
            if line.startswith("{"):
                return json.loads(line)
        arch, shape, mesh_kind = cell_id.split(":")
        return {"arch": arch, "shape": shape, "mesh": mesh_kind,
                "status": "FAIL",
                "error": (out.stderr or out.stdout)[-1500:]}
    except subprocess.TimeoutExpired:
        arch, shape, mesh_kind = cell_id.split(":")
        return {"arch": arch, "shape": shape, "mesh": mesh_kind,
                "status": "TIMEOUT"}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--cell", help="arch:shape:mesh — run one cell in-proc")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--arch", help="run all shapes/meshes for one arch")
    ap.add_argument("--workers", type=int, default=3)
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--json-only", action="store_true")
    args = ap.parse_args()

    if args.cell:
        row = run_cell(args.cell)
        if args.json_only:
            print(json.dumps(row))
        else:
            print(json.dumps(row, indent=2))
        return 0 if row["status"] in ("OK", "SKIP") else 1

    cells = all_cells()
    if args.arch:
        cells = [c for c in cells if c.startswith(args.arch + ":")]
    os.makedirs(args.out, exist_ok=True)
    results = []
    with ThreadPoolExecutor(max_workers=args.workers) as pool:
        for row in pool.map(_run_in_subprocess, cells):
            results.append(row)
            tag = f"{row['arch']}:{row.get('shape')}:{row['mesh']}"
            print(f"[{len(results)}/{len(cells)}] {tag}: {row['status']} "
                  f"dom={row.get('dominant', '-')} "
                  f"frac={row.get('roofline_fraction', 0):.3f}",
                  flush=True)
            with open(os.path.join(args.out, "dryrun.json"), "w") as f:
                json.dump(results, f, indent=1)
    n_ok = sum(r["status"] == "OK" for r in results)
    n_skip = sum(r["status"] == "SKIP" for r in results)
    print(f"done: {n_ok} OK, {n_skip} SKIP, "
          f"{len(results) - n_ok - n_skip} FAIL")
    return 0 if n_ok + n_skip == len(results) else 1


if __name__ == "__main__":
    sys.exit(main())
