"""Tiny copies of the benchmark's cells that a CPU test run can hold: the
same files, limits and code paths, on small charts."""
import time

import jax

import _paths
import harness
import run

SIZES = {"galactic_dust_chart": {"shape0": [6, 8, 6], "n_levels": 2},
         "log_chart": {"shape0": 32, "n_levels": 3}}


def cell(name: str):
    c = harness.load_cell(_paths.ROOT, name)
    c.config["args"].update(SIZES[c.config["chart"]])
    if c.traffic["mode"] == "open":  # eight requests a second-long run
        c.traffic["rate"] = 8.0
    c.traffic["control_seconds"] = 1.0
    return c


def run_once(name: str, seed: int = 2 ** 31 + 99, seconds: float = 1.0):
    """One run of the tiny cell, the chip check skipped: (correct, checks)."""
    res, checks = run.run_cell(cell(name), seed, seconds, False,
                               jax.devices(), time.perf_counter())
    return res["correct"], checks
