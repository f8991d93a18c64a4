"""Production device meshes (DESIGN.md §8).

Defined as FUNCTIONS so importing this module never touches jax device
state — the dry-run driver must be able to set
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before jax
initializes.

Mesh layouts (TPU v5e pod = 16x16 = 256 chips):

* LM configs:   (data=16, model=16); multi-pod (pod=2, data=16, model=16).
  The ``pod`` axis extends data parallelism across the DCN; gradient
  all-reduce over ("pod", "data") is hierarchical (ICI first, DCN once).
* ICR configs:  the same meshes, re-labelled by the caller: the spatial ring
  is ("data", "model") flattened (single pod) or ("pod", "data", "model")
  (multi-pod) — halo ppermute traffic crosses the DCN on exactly two ring
  edges (core/distributed.py).
"""
from __future__ import annotations

import dataclasses

import jax
import numpy as np
from jax.sharding import Mesh

from repro import compat


def _make(shape, axes) -> Mesh:
    n = int(np.prod(shape))
    if len(jax.devices()) < n:
        raise RuntimeError(
            f"mesh {dict(zip(axes, shape))} needs {n} devices but only "
            f"{len(jax.devices())} are visible; the dry-run driver must set "
            "XLA_FLAGS=--xla_force_host_platform_device_count=512 before "
            "importing jax"
        )
    # axis_types is resolved by repro.compat: Auto on jax with AxisType,
    # omitted entirely on 0.4.x.
    return compat.make_mesh(shape, axes)


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    """16x16 single-pod (256 chips) or 2x16x16 multi-pod (512 chips)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make(shape, axes)


def make_mesh(shape, axes) -> Mesh:
    """Arbitrary mesh with Auto axis types (tests, small CPU runs)."""
    return _make(tuple(shape), tuple(axes))


def make_host_mesh(model: int | None = None) -> Mesh:
    """Best-effort mesh over whatever devices exist (CPU tests/examples)."""
    n = len(jax.devices())
    model = model or 1
    return _make((n // model, model), ("data", "model"))


# -- device table ----------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class DeviceSpec:
    """Per-chip peaks of one accelerator kind (keyed by ``device_kind``)."""

    peak_flops_bf16: float   # FLOP/s
    peak_ops_int8: float     # OP/s
    hbm_bw: float            # B/s
    hbm_bytes: int
    ici_bw: float            # B/s per ICI link (four links per chip)
    vmem_bytes: int          # vector memory of one TensorCore
    source: str


# Peaks: Google Cloud documentation, "TPU v5e" (197 TFLOP/s bf16, 394 TOP/s
# int8, 16 GB HBM at 819 GB/s, 1,600 Gbit/s ICI per chip). VMEM: the Pallas
# TPU documentation's per-core vector memory of a v5e TensorCore (128 MiB).
DEVICES = {
    "TPU v5 lite": DeviceSpec(
        peak_flops_bf16=197e12, peak_ops_int8=394e12, hbm_bw=819e9,
        hbm_bytes=16 * 10**9, ici_bw=1600e9 / 8 / 4, vmem_bytes=128 * 2**20,
        source="Google Cloud documentation, TPU v5e"),
}

# The chip the Pallas kernels are tiled for: autotuner budgets and the
# compiler's scoped-VMEM limit derive from this entry (kernels/dispatch.py,
# kernels/launch.py), whatever backend happens to trace them.
TARGET_KIND = "TPU v5 lite"

# Cross-pod data-centre network, per host (collective cost model only).
DCN_BW = 25e9


def device_spec(kind: str | None = None) -> DeviceSpec:
    """The table entry for ``kind`` (default: the first visible device).

    A device that is not in the table is an error: no metric may be
    computed against another chip's peaks.
    """
    if kind is None:
        kind = jax.devices()[0].device_kind
    try:
        return DEVICES[kind]
    except KeyError:
        raise KeyError(
            f"device kind {kind!r} is not in the peaks table "
            f"(known: {sorted(DEVICES)})") from None


def target_spec() -> DeviceSpec:
    return DEVICES[TARGET_KIND]
