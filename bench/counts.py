"""Least work of an ICR application, from chart geometry alone.

These are lower bounds on what any implementation must do, whatever its
routes, fusions or relayouts: every excitation read once, every matrix
read once, the level-0 field read, the final field written, and the fewest
FLOPs the refinement admits (per-axis passes where the chart is
Kronecker-factored, in the cheapest order). Intermediate fields are not
counted: a route that keeps them on chip does not have to move them.
"""
from __future__ import annotations

import itertools
import math

F32 = 4


def _prod(xs) -> int:
    return int(math.prod(xs))


def level_flops(geom, level: int) -> int:
    """Fewest FLOPs of one refinement level for one field: the window
    contraction along each axis (one multiply-add per coarse neighbour
    per output point, the axes taken in the cheapest order) and the noise
    factor of each axis applied to the fine excitations."""
    nd, csz, fsz = geom.ndim, geom.n_csz, geom.n_fsz
    coarse = geom.shape(level)
    fine = tuple(t * fsz for t in geom.families(level))
    best = None
    for order in itertools.permutations(range(nd)):
        cur, macs = list(coarse), 0
        for a in order:
            cur[a] = fine[a]
            macs += _prod(cur) * csz
        best = macs if best is None else min(best, macs)
    noise = nd * _prod(fine) * fsz
    return 2 * (best + noise)


def matrix_elems(geom, level: int) -> int:
    """Elements of the stored matrices of one level: per axis, (R, sqrtD)
    for every family, one family on an invariant axis."""
    n = 0
    for a in range(geom.ndim):
        kept = 1 if geom.invariant[a] else geom.family_count(level, a)
        n += kept * geom.n_fsz * (geom.n_csz + geom.n_fsz)
    return n


def refine_work(geom, samples: int = 1, itemsize: int = F32) -> dict:
    """All refinement levels of one application (forward or transpose)
    of ``samples`` fields: ``{"flops", "bytes"}``."""
    xi = sum(_prod(s) for s in geom.xi_shapes()[1:])
    mats = sum(matrix_elems(geom, lvl) for lvl in range(geom.n_levels))
    n0 = _prod(geom.shape0)
    flops = samples * sum(level_flops(geom, lvl)
                          for lvl in range(geom.n_levels))
    moved = (samples * (xi + n0 + geom.size) + mats) * itemsize
    return {"flops": flops, "bytes": moved}


def level0_work(geom, samples: int = 1, itemsize: int = F32) -> dict:
    """The dense level-0 product sqrt0 @ ξ0: the factor read once, the
    excitation read and the field written per sample."""
    n0 = _prod(geom.shape0)
    return {"flops": 2 * n0 * n0 * samples,
            "bytes": (n0 * n0 + 2 * n0 * samples) * itemsize}


def served_slab_work(geom, rows: int, itemsize: int = F32) -> dict:
    """One served slab of ``rows`` fields, the whole step on the device:
    level 0 and every refinement level for each row, the matrices and the
    level-0 factor read once, the posterior's mean and std read once, and
    each row's field written. The rows' excitations are drawn on the
    device and need not touch HBM."""
    n0 = _prod(geom.shape0)
    xi_all = sum(_prod(s) for s in geom.xi_shapes())
    mats = sum(matrix_elems(geom, lvl) for lvl in range(geom.n_levels))
    flops = (refine_work(geom, rows)["flops"]
             + level0_work(geom, rows)["flops"])
    moved = (mats + n0 * n0 + 2 * xi_all + rows * geom.size) * itemsize
    return {"flops": flops, "bytes": moved}


def least_seconds(work: dict, peaks: dict) -> float:
    """The roofline's least time: the larger of FLOPs over peak FLOP/s and
    bytes over peak bandwidth."""
    return max(work["flops"] / peaks["peak_flops"],
               work["bytes"] / peaks["hbm_bw"])
