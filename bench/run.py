#!/usr/bin/env python3
"""Run one benchmark cell once on the chip and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

From the root of a checkout that holds the program (``src/repro``) and
``BENCHMARK.json``. The cell's configuration, traffic and limits are found
by the names in ``BENCHMARK.json`` (see ``harness.py``). Set-up (runtime,
matrices, data, warm-up of the cell's own shapes) is reported as
``setup_s``; the window then runs for ``--seconds``; the reference checks
what the window produced once the window has closed. With ``--trace 1``
the window runs under the profiler and the line carries the per-layer
metrics. Without a TPU that ``bench/peaks.json`` knows, or without the
program, it exits with code 2 and prints no result.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

import harness  # noqa: E402

MODES = {"open": ("serve", "run_open"), "closed": ("serve", "run_closed")}


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def run_cell(cell, seed: int, seconds: float, trace: bool, devs,
             t0: float) -> tuple:
    """One run of ``cell``: (result line without checks, checks)."""
    import importlib

    import tracing
    import traffic as gen

    gen.check_traffic(cell.traffic)
    mod, fn = MODES[cell.traffic["mode"]]
    run_mode = getattr(importlib.import_module(mod), fn)
    tracer = tracing.Tracer(trace, harness.trace_dir(cell))
    out = run_mode(cell, seed, seconds, tracer, t0, devs)
    result = {"correct": harness.judge(out["checks"]),
              "attempted": int(out["attempted"]),
              "failed": int(out["failed"]), "metrics": None,
              "device": out["device"]}
    if trace:
        red = tracing.reduce(tracer.events())
        reading = dict(out["reading"], trace=red,
                       peaks=harness.peaks().get(devs[0].device_kind))
        result["metrics"] = harness.per_layer(cell, reading)
        result["device"].update(busy_s=red["busy_s"],
                                window_s=red["window_s"])
        result["breakdown"] = {"device_ops": red["device_ops"],
                               "idle_gaps": red["idle_gaps"]}
    else:
        result["metrics"] = harness.end_to_end(cell, out["e2e"])
    return result, out["checks"]


def main(argv=None) -> int:
    args = parse(argv)
    root = HERE.parent
    try:
        cell = harness.load_cell(root, args.workload)
        harness.import_program(root)
        devs = harness.require_chip(int(cell.workload["chips"]))
    except harness.Refused as e:
        harness.log(f"bench: refused: {e}")
        return 2
    harness.log(f"compile cache: {harness.enable_cache()}; device "
                f"{devs[0].device_kind} x {len(devs)}; "
                f"LIBTPU_INIT_ARGS={os.environ.get('LIBTPU_INIT_ARGS', '')!r}")
    result, checks = run_cell(cell, args.seed, args.seconds,
                              bool(args.trace), devs, T0)
    harness.emit(result, checks)
    return 0


if __name__ == "__main__":
    sys.exit(main())
