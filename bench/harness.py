"""What every cell shares: finding a cell's files by name, the chip check,
the compile cache, per-layer metric readers and the result line.

A cell is found from ``BENCHMARK.json`` alone: its configuration's file
(``configs[].file``), its traffic ``bench/traffic/<traffic>.json``, the
limits of its correctness check ``bench/limits/<workload>.json``, and each
per-layer metric's reader ``bench/metrics/<metric>.py`` (a module with
``read(reading) -> float | None``), or, where there is no such file, the
reader of the metric's stem before its first dot: ``idle_share.tail`` and
``idle_share.draw`` share ``bench/metrics/idle_share.py``. Adding a cell, a configuration, a mix
or a metric adds files and entries; no file here changes.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import math
import os
import sys
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parent


class Refused(Exception):
    """The run cannot be made here: no result is printed, exit code 2."""


@dataclasses.dataclass
class Cell:
    root: Path
    workload: dict
    config: dict
    traffic: dict
    limits: dict
    end_to_end: list
    per_layer: list

    @property
    def name(self) -> str:
        return self.workload["name"]


def _read_json(path: Path) -> dict:
    try:
        with open(path) as f:
            return json.load(f)
    except FileNotFoundError:
        raise Refused(f"missing {path}") from None


def _for(entries: list, workload: str) -> list:
    return [e for e in entries
            if "workloads" not in e or workload in e["workloads"]]


def load_cell(root: Path, workload: str) -> Cell:
    """The cell named ``workload`` with everything it names."""
    bench = _read_json(root / "BENCHMARK.json")
    wl = [w for w in bench["workloads"] if w["name"] == workload]
    if not wl:
        raise Refused(f"no workload {workload!r} in BENCHMARK.json")
    wl = wl[0]
    cfgs = [c for c in bench["configs"] if c["name"] == wl["config"]]
    if not cfgs:
        raise Refused(f"no config {wl['config']!r}")
    cfg = _read_json(root / cfgs[0]["file"])
    traffic = _read_json(root / "bench" / "traffic" / f"{wl['traffic']}.json")
    limits = _read_json(root / "bench" / "limits" / f"{workload}.json")
    return Cell(root, wl, cfg, traffic, limits,
                _for(bench["end_to_end"], workload),
                _for(bench["per_layer"], workload))


def peaks() -> dict:
    return _read_json(BENCH_DIR / "peaks.json")["devices"]


def require_chip(chips: int):
    """JAX's devices when they are TPUs that the peaks table knows, at
    least ``chips`` of them; otherwise ``Refused``. Never a fallback."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise Refused(f"no TPU: JAX found {devs[0].platform} devices")
    kind = devs[0].device_kind
    if kind not in peaks():
        raise Refused(f"device kind {kind!r} is not in bench/peaks.json")
    if len(devs) < chips:
        raise Refused(f"the cell needs {chips} chips, JAX found {len(devs)}")
    return devs[:chips]


def import_program(root: Path):
    """Put the program's package on the path; refuse a tree without it."""
    src = root / "src"
    if not (src / "repro" / "__init__.py").is_file():
        raise Refused(f"no program under {src}")
    if str(src) not in sys.path:
        sys.path.insert(0, str(src))


# An executable that embeds a chart's matrices as constants runs to hundreds
# of MB: under a smaller size cap the persistent cache refuses it and every
# run compiles afresh.
CACHE_MAX_BYTES = 8 << 30


def enable_cache() -> str:
    """The program's compile cache (``$JAX_COMPILATION_CACHE_DIR``, else
    ``<checkout>/.jax_cache``), with room for the largest executable."""
    import jax
    from repro.launch.cache import enable_compile_cache

    path = enable_compile_cache()
    jax.config.update("jax_compilation_cache_max_size", CACHE_MAX_BYTES)
    return path


def reader(root: Path, name: str):
    """The ``read`` function of per-layer metric ``name``: its own file,
    else its stem's."""
    metrics = root / "bench" / "metrics"
    path = metrics / f"{name}.py"
    if not path.is_file():
        path = metrics / f"{name.split('.', 1)[0]}.py"
    if not path.is_file():
        raise Refused(f"no reader {metrics / name}.py")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + path.stem.replace(".", "_").replace("-", "_"),
        path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def per_layer(cell: Cell, reading: dict) -> dict:
    """Each per-layer metric whose reader finds something to read."""
    out = {}
    for m in cell.per_layer:
        val = reader(cell.root, m["name"])(reading)
        if val is not None:
            out[m["name"]] = {"value": float(val), "unit": m["unit"]}
    return out


def end_to_end(cell: Cell, values: dict) -> dict:
    out = {}
    for m in cell.end_to_end:
        if m["name"] not in values:
            raise RuntimeError(f"the run reported no {m['name']}")
        out[m["name"]] = {"value": float(values[m["name"]]),
                          "unit": m["unit"]}
    return out


def device_info(devs) -> dict:
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devs)
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": int(peak)}


def judge(checks: dict) -> bool:
    """Every compared number is finite and within its limit."""
    return all(math.isfinite(c["value"]) and c["value"] <= c["limit"]
               for c in checks.values())


def emit(result: dict, checks: dict):
    """The numbers compared on standard error, then the result line last
    on standard output with ``checks`` as its last key."""
    for name, c in checks.items():
        print(f"check {name}: {float(c['value'])!r} (limit {float(c['limit'])!r})",
              file=sys.stderr, flush=True)
    line = dict(result)
    line["checks"] = checks
    print(json.dumps(line), flush=True)


def log(msg: str):
    print(msg, file=sys.stderr, flush=True)


def trace_dir(cell: Cell) -> str:
    return os.path.join(cell.root, "bench", ".trace", cell.name)
