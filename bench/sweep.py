#!/usr/bin/env python3
"""Find the highest rate an open-loop cell sustains, once, on the chip.

    python3 bench/sweep.py --workload log1d.serve --rates 2,4,8 --seconds 30

One process, one server: for each rate, an open-loop window of the cell's
mix at that rate (``run.py``'s loop), then the p50 and p90 latency and how
far the queue ran behind: the time it took past the window to drain.
The benchmark's own runs never run this; the cell fixes its rate at 0.8
of the knee found here.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

import harness  # noqa: E402


def main(argv=None) -> int:
    import numpy as np

    import serve
    import tracing
    import traffic as gen

    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    root = HERE.parent
    try:
        cell = harness.load_cell(root, args.workload)
        harness.import_program(root)
        harness.require_chip(int(cell.workload["chips"]))
    except harness.Refused as e:
        harness.log(f"sweep: refused: {e}")
        return 2
    harness.enable_cache()
    _, _, srv = serve._setup(cell, args.seed)
    off = tracing.Tracer(False, "")
    for rate in (float(r) for r in args.rates.split(",")):
        t = dict(cell.traffic, rate=rate)
        sched = gen.open_schedule(t, args.seed, args.seconds)
        out = serve.open_loop(srv, sched, args.seconds, set(), off)
        lat = np.asarray(out["latency"])
        print(json.dumps({"rate": rate, "requests": len(sched),
                          "p50_s": serve.quantile(lat, 0.5),
                          "p90_s": serve.p90(lat),
                          "failed": int((~np.isfinite(lat)).sum()),
                          "drain_s": out["span"] - args.seconds,
                          "rows": out["rows"], "slabs": out["slabs"]}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
