#!/usr/bin/env python3
"""Readings that set a cell's limits, on the chip.

    python3 bench/control.py --workload <cell> --seeds 1,2,3 [--program S]

For each seed, the control: the reference itself put in the program's
place at ``high`` precision (three bfloat16 passes), at the cell's own
sizes and on the answers a run would check, compared with the reference
at ``highest`` -- the upper readings. With ``--program S`` it first runs
the cell itself for each seed (an ``S``-second window, as ``run.py`` does)
in this one process -- the lower readings. Prints one JSON line per
reading; the benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

import harness  # noqa: E402


def serve_control(cell, seed: int) -> dict:
    import oracle
    import serve
    import traffic as gen

    cfg = cell.config
    geom = oracle.geometry(cfg)
    mode = cell.traffic["mode"]
    if mode == "open":
        sched = gen.open_schedule(cell.traffic, seed,
                                  float(cell.traffic.get("control_seconds",
                                                         10.0)))
        keep = serve._kept_indices(sched, int(cell.traffic["checked"]), seed)
        reqs = [sched[i] for i in sorted(keep)]
    else:  # each client's first request: one of each kind of the mix
        reqs = [seq[0] for seq in gen.closed_sequences(cell.traffic, seed)]
    icr_mean = _posterior_xi(cfg, geom, seed)
    mean, std = icr_mean
    low = oracle.matrices(cfg, oracle.HIGH)
    answers = []
    for a, rows in serve.reference_answers(
            [{"kind": r.kind, "seed": r.seed, "n": r.n} for r in reqs],
            low, mean, std, geom, oracle.HIGH):
        if a["kind"] == "sample":
            answers.append(dict(a, fields=list(rows)))
        else:
            m, s = serve.welford(rows)
            answers.append(dict(a, mean=m, std=s))
    del low
    ref = oracle.matrices(cfg)
    return serve.gaps(answers, ref, mean, std, geom)


def _posterior_xi(cfg, geom, seed):
    """The served posterior's mean and std as the benchmark makes them."""
    import jax
    import jax.numpy as jnp

    import program
    import traffic as gen

    k = jax.random.PRNGKey(int(gen.rng(seed, "posterior").integers(
        0, 2 ** 31 - 1)))
    mean = program.normals(k, geom.xi_shapes())
    std = [jnp.exp(jnp.full_like(m, float(cfg["posterior_log_std"])))
           for m in mean]
    return mean, std


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--program", type=float, default=0.0)
    ap.add_argument("--control", type=int, default=1)
    args = ap.parse_args(argv)
    root = HERE.parent
    try:
        cell = harness.load_cell(root, args.workload)
        harness.import_program(root)
        devs = harness.require_chip(int(cell.workload["chips"]))
    except harness.Refused as e:
        harness.log(f"control: refused: {e}")
        return 2
    harness.enable_cache()
    seeds = [int(s) for s in args.seeds.split(",")]
    if args.program > 0:
        import run

        for seed in seeds:
            t0 = time.perf_counter()
            res, checks = run.run_cell(cell, seed, args.program, False, devs,
                                       t0)
            print(json.dumps({"reading": "program", "seed": seed,
                              "correct": res["correct"],
                              "metrics": res["metrics"],
                              "memory_peak_bytes":
                                  res["device"]["memory_peak_bytes"],
                              "checks": {k: v["value"]
                                         for k, v in checks.items()}}),
                  flush=True)
    if args.control:
        for seed in seeds:
            t0 = time.perf_counter()
            out = serve_control(cell, seed)
            print(json.dumps({"reading": "control", "seed": seed,
                              "seconds": time.perf_counter() - t0,
                              "checks": out}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
