"""Analytic HBM-traffic model for one ICR refinement level, per route.

This is the single source of truth for the per-level byte estimates that
``kernels.dispatch.plan()`` reports and that the benchmark JSON carries
(bandwidth-utilization column). The numbers are *model* estimates from the
level geometry alone — no arrays, no compiled HLO — mirroring how the
kernels actually move data:

  ``stationary-1d`` / ``charted-1d`` / ``nd-fused``
      read L (+ boundary/tile padding) + read ξ + write N + matrices —
      one launch (DESIGN.md §2/§10). The fused N-D halo re-read (q_max/b_f
      of the coarse tile) is below the model's resolution and ignored.

  ``pyramid``
      the VMEM-resident multi-level launch (DESIGN.md §11): a covered
      level reads its ξ and matrices only; the coarse field is read from
      HBM by the FIRST covered level alone (``first=True``) and the fine
      field written by the LAST alone (``last=True``) — inter-level field
      traffic inside the covered prefix is zero by construction.

  ``nd-axes``
      one launch per axis: each pass reads its input field and writes its
      output at mixed resolution, ξ is read by the final (axis-0) pass only
      (the noise=False mode killed the zero-ξ reads of the other passes),
      and every pass whose axis is not already minor pays a relayout —
      XLA materializes a contiguous transpose around the kernel call, a
      read+write of the field on each side.

Every kernel route also pays the phase-plane relayouts (``kernels/
planes.py``, DESIGN.md §3): XLA passes outside the launch that split the
coarse field and ξ into planes and interleave the output planes back, each
a read+write of its array, plus the charted matrices' coefficient planes.
They are the ``"relayout"`` term of every kernel route.

  ``reference``
      the joint jnp einsum path: the (T, n_csz^d) window tensor is
      materialized in HBM (write + read) on top of the field read.

Matrix bytes are counted once per level (they are fetched per grid step on
chip but stay VMEM-resident across the sample slab — the batched-serving
amortization); with ``samples > 1`` every field/ξ term scales with the
sample count while the matrix term does not.

Byte accounting is **dtype-aware** (DESIGN.md §11): pass ``dtype`` (the
policy's storage dtype) and every term scales with its itemsize — the
``"dtype"`` key in the returned breakdown is the dtype column the
benchmark JSON and ``plan()`` carry. ``itemsize`` remains accepted for
callers that sized things by hand (the dtype column then reports the raw
byte width); passing both with conflicting widths is an error, so a row
can never carry a dtype label that disagrees with its numbers.
"""
from __future__ import annotations

import numpy as np

from repro.dtypes import canonical_name, itemsize as dtype_itemsize

__all__ = ["refine_level_traffic"]


def _prod(xs) -> int:
    xs = list(xs)
    return int(np.prod(xs)) if xs else 1


def _padded_extent(geom, a: int) -> int:
    """Coarse extent along axis ``a`` as the kernels see it: reflect adds
    ``b`` per side; the fused tile rounds up to ``(T_a + q_max)·s``."""
    n = geom.coarse_shape[a]
    if geom.boundary == "reflect":
        n += 2 * geom.b
    s = max(1, geom.n_fsz // 2)
    q_max = (geom.n_csz - 1) // s
    return max(n, (geom.T[a] + q_max) * s)


def _axis_mat_bytes(geom, itemsize: int, *, fused: bool = False) -> int:
    """Per-axis Kronecker factors (R_a, sqrtD_a). A single-launch route
    (``fused``) reads sqrtD of axis 0 only: the other noise factors are
    folded into ξ before the launch."""
    f, c = geom.n_fsz, geom.n_csz
    return itemsize * sum(
        (geom.T[a] if geom.kept_T[a] > 1 else 1)
        * (f * c + (f * f if a == 0 or not fused else 0))
        for a in range(len(geom.coarse_shape))
    )


def _joint_mat_bytes(geom, itemsize: int) -> int:
    nd = len(geom.coarse_shape)
    f, c = geom.n_fsz**nd, geom.n_csz**nd
    return itemsize * _prod(geom.kept_T) * (f * c + f * f)


def refine_level_traffic(geom, route: str, *, itemsize: int | None = None,
                         dtype=None, samples: int = 1,
                         first: bool = True, last: bool = True) -> dict:
    """Estimated HBM bytes moved by one refinement level on ``route``.

    Returns a breakdown dict with a ``"total"`` key and a ``"dtype"``
    column. Field/ξ terms scale with ``samples``; matrices are counted once
    (see module docstring). ``dtype`` sets the storage itemsize (default
    float32); ``first``/``last`` only affect the ``"pyramid"`` route — a
    covered level's position in the VMEM-resident prefix.
    """
    if dtype is not None:
        # the shared table (repro.dtypes) resolves HLO spellings and the
        # fp8 types the same way the VMEM autotuners and HLO parsers do
        width, dtype_name = dtype_itemsize(dtype), canonical_name(dtype)
        if itemsize is not None and itemsize != width:
            raise ValueError(
                f"conflicting byte width: itemsize={itemsize} vs "
                f"dtype={dtype_name} ({width} bytes)"
            )
        itemsize = width
    elif itemsize is not None:
        dtype_name = f"{itemsize}-byte"  # hand-sized caller: honest label
    else:
        itemsize, dtype_name = 4, "float32"
    nd = len(geom.coarse_shape)
    fsz = geom.n_fsz
    n_out = _prod(geom.fine_shape)
    xi_elems = _prod(geom.T) * fsz**nd

    if route in ("pyramid", "stationary-1d", "charted-1d", "nd-fused"):
        pyr = route == "pyramid"
        field_read = (_prod(_padded_extent(geom, a) for a in range(nd))
                      if first or not pyr else 0)
        fine_write = n_out if last or not pyr else 0
        mats = _axis_mat_bytes(geom, itemsize, fused=True)
        out = {
            "field_read": samples * itemsize * field_read,
            "xi_read": samples * itemsize * xi_elems,
            "fine_write": samples * itemsize * fine_write,
            "matrices": mats,
            # phase-plane split of the coarse field and ξ, interleave of
            # the output, coefficient planes: read+write each
            "relayout": 2 * (samples * itemsize
                             * (field_read + xi_elems + fine_write) + mats),
        }
    elif route == "nd-axes":
        extents = list(geom.coarse_shape)
        kernel_bytes = 0
        relayout = 0
        for a in range(nd - 1, -1, -1):
            in_pad = list(extents)
            if geom.boundary == "reflect":
                in_pad[a] += 2 * geom.b
            n_in = _prod(extents)
            out_extents = list(extents)
            out_extents[a] = geom.T[a] * fsz
            n_pass_out = _prod(out_extents)
            kernel_bytes += _prod(in_pad) + n_pass_out
            # each pass's phase-plane split and interleave (read+write)
            relayout += 2 * (_prod(in_pad) + n_pass_out)
            if a == 0:
                kernel_bytes += xi_elems  # the only ξ read (noise=False mode)
                relayout += 2 * xi_elems
            if a != nd - 1:
                # moveaxis relayout around the launch: read+write the field
                # on the way in and on the way out
                relayout += 2 * n_in + 2 * n_pass_out
            extents = out_extents
        out = {
            "field_read": samples * itemsize * kernel_bytes,
            "xi_read": 0,  # folded into field_read per pass above
            "fine_write": 0,
            "matrices": _axis_mat_bytes(geom, itemsize),
            "relayout": (samples * itemsize * relayout
                         + 2 * _axis_mat_bytes(geom, itemsize)),
        }
    elif route == "reference":
        n_in = _prod(_padded_extent(geom, a) for a in range(nd))
        win = _prod(geom.T) * geom.n_csz**nd
        out = {
            "field_read": samples * itemsize * (n_in + 2 * win),
            "xi_read": samples * itemsize * xi_elems,
            "fine_write": samples * itemsize * n_out,
            "matrices": _joint_mat_bytes(geom, itemsize),
            "relayout": 0,
        }
    else:
        raise ValueError(f"unknown route {route!r}")

    out["total"] = sum(out.values())
    out["dtype"] = dtype_name
    return out
