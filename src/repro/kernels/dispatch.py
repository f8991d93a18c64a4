"""Backend dispatch for the fused ICR refinement kernels (DESIGN.md §5/§10).

One refinement application (paper Eq. 9) can execute three ways:

  * ``"pallas"``    — the fused kernels (icr_refine.py, nd_fused.py,
                      pyramid.py) compiled by Mosaic; the only backend on
                      a TPU, where asking for another one is an error.
  * ``"interpret"`` — the same kernels in Pallas interpret mode (the body
                      runs as pure jnp); the CPU test harness
                      (``REPRO_BACKEND=interpret``), which runs the exact
                      BlockSpec tiling.
  * ``"reference"`` — the jnp oracle path: ``core.refine.refine_level``
                      for joint matrices, ``kernels.ref.refine_axes_ref``
                      for structured N-D levels carrying only the per-axis
                      factors; the CPU default (interpret mode is slower
                      than plain jnp there).

Routing is decided per level from the geometry alone:

  1-D, all ``kept_T == 1``   -> stationary kernel (one shared stencil)
  1-D, per-family matrices   -> charted kernel (batched small-matmul)
  N-D, tile fits VMEM        -> single-launch fused level megakernel
                                (repro.kernels.nd_fused, DESIGN.md §10)
  N-D, tile too large        -> per-axis fused passes (repro.kernels.nd)
  N-D without axis factors   -> reference (an error on the pallas backend)

On top of the per-level routes, ``plan()``/``ICR.apply_sqrt`` overlay the
**pyramid** route (repro.kernels.pyramid, DESIGN.md §11): all consecutive
early levels whose combined full-extent working set fits the VMEM budget
run back-to-back in ONE launch — their inter-level field traffic never
touches HBM. ``autotune_pyramid`` owns the residency criterion.

All VMEM accounting is **dtype-aware** (DESIGN.md §11): the autotuners take
the storage itemsize (bf16 halves it, doubling what fits per tile) and
``plan(dtype=...)`` reports HBM bytes at the policy's storage dtype —
the byte model grows a dtype column.

VMEM tile sizes (``block_families`` for the 1-D kernels, the ``(b_f,
s_b)`` family/sample blocks for the N-D megakernel, the pyramid's sample
slab) are autotuned against half of the target chip's VMEM (the
``launch/mesh.py`` device table), with models that count the padded,
double-buffered blocks and the kernel body's float32 values.

``refine`` is fully differentiable on every route: the kernel entry points
carry hand-written adjoint Pallas kernels via ``jax.custom_vjp``
(icr_refine.py, DESIGN.md §9; the megakernel's backward composes them in
reverse axis order), so ``jax.grad``/``jax.vjp`` through any structured
route — including the interpret backend — runs the fused backward, never
the jnp reference. ``plan()`` reports the backward routing per level next
to the forward, plus the per-level HBM-byte estimates of
``repro.roofline.level_traffic`` for every candidate route.
"""
from __future__ import annotations

import math
import os

import jax
import jax.numpy as jnp

from repro.core.refine import LevelGeom, refine_level
from repro.launch.mesh import target_spec
from repro.roofline.level_traffic import refine_level_traffic

from . import nd as _nd
from .icr_refine import (
    halo_floor,
    refine_charted_pallas,
    refine_stationary_pallas,
)
from .launch import padded_block_elems
from .policy import DtypePolicy, resolve as resolve_policy

Array = jnp.ndarray

BACKEND_PALLAS = "pallas"
BACKEND_INTERPRET = "interpret"
BACKEND_REFERENCE = "reference"

ROUTE_STATIONARY_1D = "stationary-1d"
ROUTE_CHARTED_1D = "charted-1d"
ROUTE_ND_FUSED = "nd-fused"
ROUTE_AXES_ND = "nd-axes"
ROUTE_PYRAMID = "pyramid"
ROUTE_REFERENCE = "reference"

# Half of the target chip's VMEM (launch/mesh.py device table): the
# working-set models below count the double-buffered blocks AND the kernel
# body's float32 temporaries; kernels.launch hands Mosaic a larger scoped
# limit derived from the same entry, so an autotuned tile always compiles.
VMEM_BUDGET_BYTES = target_spec().vmem_bytes // 2

_DTYPE_OF_ITEMSIZE = {4: "float32", 2: "bfloat16"}

# Largest block value, in padded elements (64 vector registers): Mosaic
# unrolls every vector op of a kernel body over the whole block, so the
# compile time grows with it (a 1024-register 1-D block took minutes to
# compile for a v5e); bigger blocks buy nothing once the grid step
# overhead is amortized.
MAX_BLOCK_ELEMS = 1 << 16


def _f32_bytes(shape) -> int:
    """VMEM bytes of one float32 value of ``shape`` inside a kernel body."""
    return 4 * padded_block_elems(shape, 4)


def block1d_bytes(t: int, n_csz: int, n_fsz: int, *, charted: bool,
                  block_families: int, batch_block: int = 1,
                  itemsize: int = 4) -> int:
    """VMEM working set of one 1-D kernel grid step (the model both 1-D
    autotuners grow against, and the static re-derivation the VMEM lint
    pass checks autotuned plans with — repro.analysis, DESIGN.md §13).

    The double-buffered blocks of the forward launch plan at this tile
    (tile padding included), plus the body's float32 values: the coarse
    planes with their halo, one window, ξ, the ``n_fsz`` accumulators and
    their stacked copy, and the coefficient planes.
    """
    from .icr_refine import refine_fwd_launch_plan

    plan = refine_fwd_launch_plan(
        batch=max(1, batch_block), t=t, coarse_len=0, n_csz=n_csz,
        n_fsz=n_fsz, block_families=block_families,
        batch_block=batch_block, dtype=_DTYPE_OF_ITEMSIZE[itemsize],
        accum_dtype="float32", charted=charted)
    p = plan.params
    s, b_b, tb = p["s"], p["b_b"], p["b_f"]
    ext = 2 * tb if p["halo"] else p["lp"]
    rows = n_fsz * (n_csz + n_fsz)
    temps = (_f32_bytes((s, b_b, ext))
             + (1 + 3 * n_fsz) * _f32_bytes((b_b, tb))
             + _f32_bytes((rows, tb if charted else 1)))
    return plan.block_bytes() + temps


def block1d_floor(t: int, n_csz: int, n_fsz: int) -> int:
    """Smallest family block the 1-D kernels accept: one lane tile (128
    families), or the whole level when it has no more families than that.
    The floor is returned by the autotuner whether or not it fits the
    budget (a level cannot tile finer)."""
    return t if t <= 128 else 128


def autotune_block_families(t: int, n_csz: int, n_fsz: int, *, charted: bool,
                            batch_block: int = 1, itemsize: int = 4,
                            vmem_budget: int = VMEM_BUDGET_BYTES) -> int:
    """Family block of the 1-D kernels: the whole level in one block when
    its working set (the ``block1d_bytes`` model) fits the budget, else
    the largest power-of-two multiple of 128 lanes that does, floored at
    ``block1d_floor`` (always returned, budget-fitting or not).
    """
    from .icr_refine import sublane_tile

    rows = -(-max(1, batch_block) // sublane_tile(itemsize)) \
        * sublane_tile(itemsize)

    def fits(b_f):
        lanes = -(-(b_f + halo_floor(n_csz, n_fsz)) // 128) * 128
        return (rows * lanes <= MAX_BLOCK_ELEMS
                and block1d_bytes(t, n_csz, n_fsz, charted=charted,
                                  block_families=b_f,
                                  batch_block=batch_block,
                                  itemsize=itemsize) <= vmem_budget)

    floor = block1d_floor(t, n_csz, n_fsz)
    if floor >= t or fits(t):
        return t
    best = floor
    while 2 * best < t and fits(2 * best):
        best *= 2
    return best


def autotune_batch_block(samples: int, t: int, n_csz: int, n_fsz: int, *,
                         charted: bool, block_families: int,
                         itemsize: int = 4,
                         vmem_budget: int = VMEM_BUDGET_BYTES) -> int:
    """Largest batch slab the 1-D kernels can hold per grid step at the
    given family block: the whole batch, or a power-of-two multiple of the
    sublane tile (batch rows ride the sublanes) — the native sample-batch
    dimension that amortizes coefficient loads across batched sampling,
    serving and the orthogonal lines of an N-D axis pass."""
    from .icr_refine import sublane_tile

    sub = sublane_tile(itemsize)
    lanes = -(-(min(block_families, t) + halo_floor(n_csz, n_fsz))
              // 128) * 128
    best = min(samples, sub)
    while best < samples:
        nxt = min(2 * best, samples)
        if (-(-nxt // sub) * sub * lanes > MAX_BLOCK_ELEMS
                or block1d_bytes(t, n_csz, n_fsz, charted=charted,
                                 block_families=block_families,
                                 batch_block=nxt,
                                 itemsize=itemsize) > vmem_budget):
            break
        best = nxt
    return best


def axis_pass_tiles(batch: int, t: int, n_csz: int, n_fsz: int, *,
                    charted: bool, itemsize: int = 4) -> tuple:
    """``(block_families, batch_block)`` of a 1-D launch over ``batch``
    lines — the per-axis passes of an N-D level and the megakernel's
    composed backward, whose batch is every orthogonal line of the field:
    the family block at one sublane tile of lines, then the line slab."""
    from .icr_refine import sublane_tile

    b_f = autotune_block_families(
        t, n_csz, n_fsz, charted=charted, itemsize=itemsize,
        batch_block=min(batch, sublane_tile(itemsize)))
    b_b = autotune_batch_block(batch, t, n_csz, n_fsz, charted=charted,
                               block_families=b_f, itemsize=itemsize)
    return b_f, b_b


def _fused_tile_bytes(geom: LevelGeom, charted: tuple, b_f: int, s_b: int,
                      itemsize: int) -> int:
    """VMEM working set of one megakernel grid step (model, DESIGN.md §10).

    The double-buffered blocks of the launch plan at this tile (tile
    padding included) plus the body's float32 values: per contraction
    stage its input, one window, the accumulators and their stacked copy
    (at most three outputs' worth), at the widest stage.
    """
    from .nd_fused import fused_launch_shapes, nd_fused_launch_plan

    sh = fused_launch_shapes(geom, samples=s_b, b_f=b_f, s_b=s_b)
    plan = nd_fused_launch_plan(
        nd=sh["nd"], csz=geom.n_csz, fsz=geom.n_fsz, T=sh["T"],
        charted=charted, b_f=b_f, s_b=s_b, sp=sh["sp"], l0p=sh["l0p"],
        lp_trail=sh["lp_trail"], nblk=sh["nblk"], prod_f=sh["prod_f"],
        dtype=_DTYPE_OF_ITEMSIZE[itemsize], accum_dtype="float32")
    nd, fsz, csz = sh["nd"], geom.n_fsz, geom.n_csz
    layout = plan.params["layout"]
    field = plan.operand("field")
    phases = [field.block_shape[a] for a in range(nd)]
    pos = {a: field.block_shape[nd + 1 + layout.index(a)] for a in range(nd)}
    if plan.params["halo"]:
        pos[0] *= 2
    peak = 0
    for a in range(nd - 1, -1, -1):
        before = _f32_bytes(tuple(phases) + (s_b,)
                            + tuple(pos[x] for x in layout))
        phases[a] = fsz
        pos[a] = b_f if a == 0 else sh["T"][a]
        after = _f32_bytes(tuple(phases) + (s_b,)
                           + tuple(pos[x] for x in layout))
        peak = max(peak, before + 3 * after)
    return plan.block_bytes() + peak


def autotune_nd_fused(geom: LevelGeom, *, charted: tuple | None = None,
                      samples: int = 1, itemsize: int = 4,
                      vmem_budget: int = VMEM_BUDGET_BYTES):
    """Family/sample blocks ``(b_f, s_b)`` for the fused N-D level kernel,
    or None when even the minimal tile busts the VMEM budget — the fallback
    rule: dispatch then routes the level to the per-axis passes.

    Grows the axis-0 family block first (from the halo floor, doubling up
    to ``T_0``, kept legal for the tile layout), then the sample slab (up to
    ``samples``), keeping the §10 working-set model under the budget.
    """
    from .nd_fused import fused_block

    nd = len(geom.coarse_shape)
    if nd < 2:
        return None
    if charted is None:
        charted = tuple(k > 1 for k in geom.kept_T)
    t0 = geom.T[0]
    q = halo_floor(geom.n_csz, geom.n_fsz)

    def legal(b):
        return fused_block(nd, t0, b, itemsize)

    def fits(b, sb):
        return _fused_tile_bytes(geom, charted, b, sb,
                                 itemsize) <= vmem_budget

    b_f = legal(max(min(q, t0), 1))
    if not fits(b_f, 1):
        return None
    while b_f < t0:
        nxt = legal(min(2 * b_f, t0))
        if nxt <= b_f or not fits(nxt, 1):
            break
        b_f = nxt
    s_b = 1
    while s_b < samples:
        nxt = min(2 * s_b, samples)
        if not fits(b_f, nxt):
            break
        s_b = nxt
    return b_f, s_b


def _pyramid_charted(geom: LevelGeom) -> tuple:
    return tuple(k > 1 for k in geom.kept_T)


def pyramid_bytes(geoms, *, s_b: int, itemsize: int) -> int:
    """VMEM working set of one pyramid grid step over ``geoms``: the
    double-buffered blocks of its launch plan (polyphase tiles, padding
    included) plus the body's float32 values at the widest contraction
    stage of the chain (input, one window, the accumulators and their
    stacked copy)."""
    from .pyramid import pyramid_geometry, pyramid_launch_plan

    g0 = geoms[0]
    fsz, csz = g0.n_fsz, g0.n_csz
    s = fsz // 2
    levels = [(tuple(g.T), tuple(g.coarse_shape)) for g in geoms]
    plan = pyramid_launch_plan(
        levels=levels, charted=[_pyramid_charted(g) for g in geoms],
        sp=s_b, s_b=s_b, csz=csz, fsz=fsz,
        dtype=_DTYPE_OF_ITEMSIZE[itemsize], accum_dtype="float32")
    geo = pyramid_geometry(levels, s=s, fsz=fsz)
    nd, pos = geo["nd"], geo["pos"]
    phases = [s] * nd
    peak = 0
    for _ in geoms:
        for a in range(nd - 1, -1, -1):
            before = _f32_bytes(tuple(phases) + (s_b,) + pos)
            phases[a] *= 2
            after = _f32_bytes(tuple(phases) + (s_b,) + pos)
            peak = max(peak, before + 3 * after)
    return plan.block_bytes() + peak


def autotune_pyramid(geoms, *, samples: int = 1, itemsize: int = 4,
                     vmem_budget: int = VMEM_BUDGET_BYTES):
    """How many consecutive levels (from ``geoms[0]``) fit VMEM together,
    and the sample slab: ``(k, s_b)``, or None when fewer than two fit —
    a one-level "pyramid" is just the per-level route.

    The residency criterion is the ``pyramid_bytes`` model: every covered
    level's ξ and coefficient planes plus the polyphase field of the whole
    chain are simultaneously resident. The storage ``itemsize`` makes the
    criterion dtype-aware. On a 1-D chart the sample slab rides the
    sublanes, so it is all samples or a multiple of the sublane tile.
    """
    from .pyramid import sample_slab

    nd = len(geoms[0].coarse_shape)

    def legal(sb):
        return sample_slab(sb, samples, itemsize) if nd == 1 else sb

    def fits(k, sb):
        return pyramid_bytes(geoms[:k], s_b=sb,
                             itemsize=itemsize) <= vmem_budget

    s_b = legal(1)
    k = 1
    while k < len(geoms) and fits(k + 1, s_b):
        k += 1
    if k < 2 or not fits(k, s_b):
        return None
    while s_b < samples:
        nxt = legal(min(2 * s_b, samples))
        if nxt <= s_b or not fits(k, nxt):
            break
        s_b = nxt
    return k, s_b


def pyramid_cover(chart, *, have_axis_mats: bool | None = None,
                  samples: int = 1, itemsize: int = 4,
                  vmem_budget: int = VMEM_BUDGET_BYTES):
    """The pyramid prefix of `chart`: ``(k, s_b)`` covering levels
    ``0..k-1``, or None. Only structured levels can be covered (a level
    that would route to the jnp reference ends the prefix)."""
    if have_axis_mats is None:
        have_axis_mats = chart.ndim > 1
    geoms = []
    for lvl in range(chart.n_levels):
        geom = LevelGeom.for_level(chart, lvl)
        if route_for(geom, have_axis_mats=have_axis_mats,
                     itemsize=itemsize) == ROUTE_REFERENCE:
            break
        geoms.append(geom)
    if len(geoms) < 2:
        return None
    return autotune_pyramid(geoms, samples=samples, itemsize=itemsize,
                            vmem_budget=vmem_budget)


def select_backend(*, platform: str | None = None) -> str:
    """Kernel backend for `platform` (default: the runtime jax backend).

    A TPU runs the compiled Pallas kernels and nothing else: asking for
    another backend there (``REPRO_BACKEND``) is an error, never a silent
    fallback. The CPU answer is the jnp reference/oracle path — Pallas
    interpret mode emulates the BlockSpec machinery step by step and is
    strictly slower than plain jnp on CPU, so it is a correctness harness,
    not a serving path. ``REPRO_BACKEND`` (``pallas`` / ``interpret`` /
    ``reference``) is a test-only override of the runtime answer: the
    tiling-coverage test modules pin ``interpret`` through a monkeypatch
    fixture so the exact kernel tiling keeps running off-TPU. Any other
    platform has no kernel backend at all. An explicit ``platform`` is a
    pure what-would-run-there question (``plan()`` introspection) and
    ignores the override.
    """
    explicit = platform is not None
    if platform is None:
        platform = jax.default_backend()
    if platform == "tpu":
        override = None if explicit else os.environ.get("REPRO_BACKEND")
        if override and override != BACKEND_PALLAS:
            raise RuntimeError(
                f"REPRO_BACKEND={override!r} on a TPU: the chip runs the "
                f"compiled {BACKEND_PALLAS!r} kernels only")
        return BACKEND_PALLAS
    if platform != "cpu":
        raise RuntimeError(
            f"no kernel backend for platform {platform!r}: the refinement "
            f"kernels target the TPU, the jnp reference runs on the CPU")
    override = None if explicit else os.environ.get("REPRO_BACKEND")
    if override:
        if override not in (BACKEND_PALLAS, BACKEND_INTERPRET,
                            BACKEND_REFERENCE):
            raise ValueError(
                f"REPRO_BACKEND={override!r}: expected one of "
                f"{BACKEND_PALLAS!r}, {BACKEND_INTERPRET!r}, "
                f"{BACKEND_REFERENCE!r}"
            )
        return override
    return BACKEND_REFERENCE


def require_reference_allowed() -> str:
    """The jnp reference as an explicit choice (``ICR(use_pallas=False)``,
    the same in ``DistributedICR``): it runs on the CPU only. Anywhere
    else the refinement kernels are the only path, and asking for the
    reference there is an error, never a silent fallback."""
    platform = jax.default_backend()
    if platform != "cpu":
        raise RuntimeError(
            f"use_pallas=False runs the jnp reference; on {platform!r} the "
            "refinement kernels are the only path (use_pallas=True)")
    return BACKEND_REFERENCE


def pyramid_interpret(backend: str):
    """The pyramid wrapper's ``interpret`` argument for a backend."""
    if backend == BACKEND_REFERENCE:
        return "reference"
    return backend != BACKEND_PALLAS


def route_for(geom: LevelGeom, *, have_axis_mats: bool = False,
              itemsize: int = 4) -> str:
    """Which structured path covers this level's geometry (see module doc).

    ``itemsize`` is the storage-dtype byte width: the megakernel-vs-
    per-axis decision is a VMEM-fit question, so a borderline level that
    busts the budget at f32 can still take the fused route at bf16.
    """
    if geom.boundary not in ("shrink", "reflect"):
        return ROUTE_REFERENCE
    if len(geom.coarse_shape) == 1:
        if all(k == 1 for k in geom.kept_T):
            return ROUTE_STATIONARY_1D
        return ROUTE_CHARTED_1D
    if not have_axis_mats:
        return ROUTE_REFERENCE
    if autotune_nd_fused(geom, itemsize=itemsize) is not None:
        return ROUTE_ND_FUSED
    return ROUTE_AXES_ND


def plan(chart, *, have_axis_mats: bool | None = None,
         platform: str | None = None, samples: int = 1,
         dtype=None, pyramid: bool = True,
         vmem_budget: int = VMEM_BUDGET_BYTES) -> list:
    """Per-level forward AND backward routing decisions for `chart` —
    introspection for examples, benchmarks and tests (no arrays touched).

    have_axis_mats defaults to ``chart.ndim > 1`` (ICR.matrices computes the
    per-axis factors for every N-D chart when use_pallas=True).

    ``dtype`` is the storage dtype of the policy the chart will run under
    (default float32): it scales every byte estimate AND the VMEM
    autotuning — bf16 halves modeled HBM bytes and doubles what fits per
    tile. Each entry carries the dtype column (``"dtype"``).

    ``pyramid=True`` (the execution default) overlays the DESIGN.md §11
    VMEM-resident prefix: covered levels report ``route="pyramid"`` with
    zero inter-level field traffic (the first covered level carries the
    coarse read, the last the fine write). ``pyramid=False`` shows the
    per-level routing underneath — what runs when the pyramid is disabled
    (``ICR(use_pyramid=False)``) and what the covered levels fall back to.

    Each entry carries a ``"vjp"`` sub-dict describing how the *backward*
    pass of that level executes (structured routes run the hand-written
    adjoint kernels; the megakernel's backward composes the 1-D adjoints in
    reverse axis order; the pyramid's backward runs the same 1-D adjoints
    level by level, last level first; the reference route is jnp
    autodiff) and an ``"hbm_bytes"`` sub-dict: the
    ``roofline.level_traffic`` estimate for the selected route next to
    every candidate route, so the traffic win of the fused paths is visible
    without running anything.

    ``vmem_budget`` bounds the pyramid overlay only (tests shrink it to
    exercise the fallback rule); the per-level autotuners keep the global
    ``VMEM_BUDGET_BYTES``.
    """
    if have_axis_mats is None:
        have_axis_mats = chart.ndim > 1
    dtype = jnp.dtype(dtype or jnp.float32)
    itemsize = dtype.itemsize
    cover = (pyramid_cover(chart, have_axis_mats=have_axis_mats,
                           samples=samples, itemsize=itemsize,
                           vmem_budget=vmem_budget)
             if pyramid else None)
    k_cov, s_b_cov = cover if cover is not None else (0, None)
    out = []
    for lvl in range(chart.n_levels):
        geom = LevelGeom.for_level(chart, lvl)
        route = route_for(geom, have_axis_mats=have_axis_mats,
                          itemsize=itemsize)
        covered = lvl < k_cov
        backend = (BACKEND_REFERENCE if route == ROUTE_REFERENCE
                   else select_backend(platform=platform))
        blocks = {}
        sample_block = None
        if covered:
            sample_block = s_b_cov
        elif route in (ROUTE_STATIONARY_1D, ROUTE_CHARTED_1D):
            blocks[0] = autotune_block_families(
                geom.T[0], geom.n_csz, geom.n_fsz,
                charted=route == ROUTE_CHARTED_1D, itemsize=itemsize,
            )
            sample_block = autotune_batch_block(
                samples, geom.T[0], geom.n_csz, geom.n_fsz,
                charted=route == ROUTE_CHARTED_1D,
                block_families=blocks[0], itemsize=itemsize,
            )
        elif route == ROUTE_ND_FUSED:
            b_f, s_b = autotune_nd_fused(geom, samples=samples,
                                         itemsize=itemsize)
            blocks[0] = b_f
            sample_block = s_b
        elif route == ROUTE_AXES_ND:
            for a in range(len(geom.T)):
                ag = geom.axis(a)
                blocks[a] = autotune_block_families(
                    ag.T[0], ag.n_csz, ag.n_fsz,
                    charted=ag.kept_T[0] > 1, itemsize=itemsize,
                )
        candidates = ([ROUTE_ND_FUSED, ROUTE_AXES_ND, ROUTE_REFERENCE]
                      if len(geom.coarse_shape) > 1
                      else [route, ROUTE_REFERENCE])
        hbm = {
            rt: refine_level_traffic(geom, rt, samples=samples,
                                     dtype=dtype)["total"]
            for rt in candidates
        }
        if covered:
            hbm[ROUTE_PYRAMID] = refine_level_traffic(
                geom, ROUTE_PYRAMID, samples=samples, dtype=dtype,
                first=lvl == 0, last=lvl == k_cov - 1)["total"]
            route = ROUTE_PYRAMID
        hbm["selected"] = hbm[route]
        vjp = {
            "route": (ROUTE_REFERENCE if route == ROUTE_REFERENCE
                      else route + "-adjoint"),
            "backend": backend,
            "block_families": dict(blocks),
        }
        out.append({"level": lvl, "route": route, "backend": backend,
                    "block_families": blocks, "sample_block": sample_block,
                    "hbm_bytes": hbm, "dtype": dtype.name, "vjp": vjp})
    return out


# -- plan cache (serving warm path, DESIGN.md §12) ------------------------------
# plan() walks every level's autotuners and traffic models — pure geometry,
# so repeat traffic against the same (chart, dtype, backend, sample count)
# must not redo it. Charts are frozen dataclasses (hashable); the effective
# backend is part of the key so a REPRO_BACKEND flip is a miss.
_PLAN_CACHE: dict = {}
plan_cache_stats = {"hits": 0, "misses": 0}


def plan_cached(chart, *, have_axis_mats: bool | None = None,
                platform: str | None = None, samples: int = 1,
                dtype=None, pyramid: bool = True,
                vmem_budget: int = VMEM_BUDGET_BYTES,
                mesh_key=None) -> list:
    """Memoized ``plan()`` — the serving fast path asks for the same
    routing decision on every batch. The returned list is shared across
    callers: treat it as read-only.

    ``mesh_key`` is an opaque hashable describing the device mesh the plan
    will execute under (the sharded server passes its mesh fingerprint, see
    DESIGN.md §15). It does not change the per-device routing decision —
    ``samples`` is already the *local* slab height — but it keys the cache,
    so an elastic re-mesh is a deliberate plan-cache miss and can never be
    served a stale pre-resize plan.
    """
    backend = select_backend(platform=platform)
    key = (chart, have_axis_mats, backend, samples,
           jnp.dtype(dtype or jnp.float32).name, pyramid, vmem_budget,
           mesh_key)
    hit = _PLAN_CACHE.pop(key, None)
    if hit is not None:
        plan_cache_stats["hits"] += 1
        _PLAN_CACHE[key] = hit  # re-insert: LRU order, hits refresh recency
        return hit
    plan_cache_stats["misses"] += 1
    out = plan(chart, have_axis_mats=have_axis_mats, platform=platform,
               samples=samples, dtype=dtype, pyramid=pyramid,
               vmem_budget=vmem_budget)
    _PLAN_CACHE[key] = out
    while len(_PLAN_CACHE) > 32:  # bound: long-lived servers, many charts
        _PLAN_CACHE.pop(next(iter(_PLAN_CACHE)))
    return out


def plan_cache_clear() -> None:
    _PLAN_CACHE.clear()
    plan_cache_stats.update(hits=0, misses=0)


def plan_signature(chart, **plan_kwargs) -> list:
    """Canonical JSON-serializable export of ``plan()`` — the route + tile
    + byte signature the compile-fingerprint subsystem (repro.analysis,
    DESIGN.md §13) locks down as a golden.

    One dict per level, primitives only (dict keys stringified, byte
    totals as ints), deterministically ordered: ``json.dumps(...,
    sort_keys=True)`` of two signatures of the same geometry is
    byte-identical, and any routing/tiling/byte-model change shows up as a
    structured diff against the golden rather than a wall-time blip.
    """
    out = []
    for e in plan(chart, **plan_kwargs):
        out.append({
            "level": e["level"],
            "route": e["route"],
            "backend": e["backend"],
            "block_families": {str(k): int(v)
                               for k, v in e["block_families"].items()},
            "sample_block": (None if e["sample_block"] is None
                             else int(e["sample_block"])),
            "dtype": e["dtype"],
            "hbm_bytes": {str(k): int(v) for k, v in e["hbm_bytes"].items()},
            "vjp": {"route": e["vjp"]["route"],
                    "backend": e["vjp"]["backend"]},
        })
    return out


def plan_kernel(plan):
    """The kernel body of any exported launch plan (what ``run_plan``
    launches it with) — for compiling a plan without its operands."""
    from . import icr_refine, nd_fused, pyramid

    if plan.kernel == "refine_nd_fused":
        return nd_fused.plan_kernel(plan)
    if plan.kernel == "refine_pyramid":
        return pyramid.plan_kernel(plan)
    return icr_refine.plan_kernel(plan)


# -- declarative launch-plan export (DESIGN.md §14) -----------------------------
# The kernel impls build LaunchPlan records (kernels.launch) and hand them
# to run_plan; these exports rebuild the *identical* records from geometry
# alone — same builders, same autotuned tiles — so analysis.kernel_verify
# can prove coverage/bounds/halo/byte properties about exactly the
# launches that would run, without touching an array.
def level_launch_plans(geom: LevelGeom, route: str | None = None, *,
                       samples: int = 1, dtype=None,
                       accum_dtype: str = "float32",
                       have_axis_mats: bool | None = None,
                       block_families: int | None = None,
                       sample_block: int | None = None) -> list:
    """Every Pallas launch one refinement level executes on ``route``:
    the forward launch(es) followed by the adjoint launch(es) its custom
    VJP runs at fixed matrices (``[]`` for the reference route — no
    Pallas launch to verify).

    ``route`` defaults to ``route_for`` of the geometry; tiles are the
    autotuners' answers at the storage ``dtype`` unless overridden, so
    the records match the kernel impls' own plans bit for bit. The N-D
    routes mirror the composed backward exactly: the megakernel's
    ``_core_bwd`` runs the 1-D adjoints in reverse axis order (axis 0
    with noise, trailing axes without), the per-axis route one
    forward/adjoint pair per axis with orthogonal axes folded into the
    batch dimension.
    """
    from .icr_refine import refine_adjoint_launch_plan, refine_fwd_launch_plan

    dtype = jnp.dtype(dtype or jnp.float32)
    itemsize = dtype.itemsize
    if have_axis_mats is None:
        have_axis_mats = len(geom.coarse_shape) > 1
    if route is None:
        route = route_for(geom, have_axis_mats=have_axis_mats,
                          itemsize=itemsize)
    if route == ROUTE_REFERENCE:
        return []
    csz, fsz = geom.n_csz, geom.n_fsz
    s = max(1, fsz // 2)
    q_max = (csz - 1) // s
    pad = 2 * geom.b if geom.boundary == "reflect" else 0

    if route in (ROUTE_STATIONARY_1D, ROUTE_CHARTED_1D):
        charted = route == ROUTE_CHARTED_1D
        t = geom.T[0]
        b_f = block_families or autotune_block_families(
            t, csz, fsz, charted=charted, itemsize=itemsize)
        b_b = sample_block or autotune_batch_block(
            samples, t, csz, fsz, charted=charted, block_families=b_f,
            itemsize=itemsize)
        kw = dict(batch=samples, t=t, coarse_len=geom.coarse_shape[0] + pad,
                  n_csz=csz, n_fsz=fsz, block_families=b_f, batch_block=b_b,
                  dtype=dtype, accum_dtype=accum_dtype, charted=charted)
        return [refine_fwd_launch_plan(**kw),
                refine_adjoint_launch_plan(**kw)]

    if route == ROUTE_ND_FUSED:
        from .nd_fused import (
            fused_block,
            fused_launch_shapes,
            nd_fused_launch_plan,
        )

        charted = _pyramid_charted(geom)
        T = tuple(geom.T)
        tuned = autotune_nd_fused(geom, charted=charted, samples=samples,
                                  itemsize=itemsize)
        if tuned is None:
            raise ValueError(
                "nd-fused route on a level whose minimal tile busts the "
                "VMEM budget — dispatch would route it to the per-axis "
                "passes")
        b_f, s_b = tuned
        if block_families is not None:
            b_f = fused_block(len(T), T[0],
                              max(min(block_families, T[0]), q_max, 1),
                              itemsize)
        if sample_block is not None:
            s_b = max(1, min(sample_block, samples))
        sh = fused_launch_shapes(geom, samples=samples, b_f=b_f, s_b=s_b)
        nd, sp, l0p = sh["nd"], sh["sp"], sh["l0p"]
        lp_trail, prod_f = sh["lp_trail"], sh["prod_f"]
        plans = [nd_fused_launch_plan(
            nd=nd, csz=csz, fsz=fsz, T=T, charted=charted, b_f=b_f,
            s_b=s_b, sp=sp, l0p=l0p, lp_trail=lp_trail, nblk=sh["nblk"],
            prod_f=prod_f, dtype=dtype, accum_dtype=accum_dtype)]
        # fixed-matrix backward (nd_fused.axes_adjoint): 1-D adjoints in
        # reverse axis order on the padded operand extents
        return plans + axes_adjoint_plans(
            T, t0p=sh["nblk"] * b_f, samples=sp,
            coarse_lens=(l0p,) + tuple(lp_trail), charted=charted, csz=csz,
            fsz=fsz, dtype=dtype, accum_dtype=accum_dtype)

    if route == ROUTE_AXES_ND:
        nd = len(geom.coarse_shape)
        T = tuple(geom.T)
        fwd, bwd = [], []
        for a in range(nd - 1, -1, -1):
            ag = geom.axis(a)
            charted_a = ag.kept_T[0] > 1
            batch_a = samples
            for j in range(a):
                batch_a *= geom.coarse_shape[j]
            for j in range(a + 1, nd):
                batch_a *= T[j] * fsz
            bf, bb = axis_pass_tiles(batch_a, T[a], csz, fsz,
                                     charted=charted_a, itemsize=itemsize)
            bf = block_families or bf
            kw = dict(batch=batch_a, t=T[a],
                      coarse_len=geom.coarse_shape[a] + pad, n_csz=csz,
                      n_fsz=fsz, block_families=bf, batch_block=bb,
                      dtype=dtype, accum_dtype=accum_dtype,
                      charted=charted_a, noise=a == 0)
            fwd.append(refine_fwd_launch_plan(**kw))
            bwd.append(refine_adjoint_launch_plan(**kw))
        return fwd + bwd[::-1]

    raise ValueError(f"no launch plans for route {route!r}")


def axes_adjoint_plans(T: tuple, *, t0p: int, samples: int, coarse_lens,
                       charted, csz: int, fsz: int, dtype,
                       accum_dtype: str = "float32") -> list:
    """The launches of ``nd_fused.axes_adjoint`` (one N-D level's
    transpose at fixed matrices): axis 0 with noise over the ``samples ·
    prod_f`` fine columns, then each trailing axis ``noise=False`` with
    the other axes folded into the batch — the megakernel's backward and,
    level by level, the pyramid's."""
    from .icr_refine import refine_adjoint_launch_plan

    itemsize = jnp.dtype(dtype).itemsize
    nd = len(T)
    s = max(1, fsz // 2)
    q_max = (csz - 1) // s
    l0p, lp_trail = coarse_lens[0], tuple(coarse_lens[1:])
    f_trail = tuple(T[a] * fsz for a in range(1, nd))
    prod_f = math.prod(f_trail)
    kw = dict(n_csz=csz, n_fsz=fsz, dtype=dtype, accum_dtype=accum_dtype)
    bf0, bb0 = axis_pass_tiles(samples * prod_f, t0p, csz, fsz,
                               charted=charted[0], itemsize=itemsize)
    plans = [refine_adjoint_launch_plan(
        batch=samples * prod_f, t=t0p, coarse_len=l0p, block_families=bf0,
        batch_block=bb0, charted=charted[0], **kw)]
    for a in range(1, nd):
        batch_a = samples * l0p
        for j in range(1, a):
            batch_a *= lp_trail[j - 1]
        for j in range(a + 1, nd):
            batch_a *= f_trail[j - 1]
        bf_a, bb_a = axis_pass_tiles(batch_a, T[a], csz, fsz,
                                     charted=charted[a], itemsize=itemsize)
        plans.append(refine_adjoint_launch_plan(
            batch=batch_a, t=T[a], coarse_len=(T[a] + q_max) * s,
            block_families=bf_a, batch_block=bb_a, charted=charted[a],
            noise=False, **kw))
    return plans


def chart_launch_plans(chart, *, samples: int = 1, dtype=None,
                       accum_dtype: str = "float32",
                       have_axis_mats: bool | None = None,
                       pyramid: bool = True,
                       sample_block: int | None = None,
                       vmem_budget: int = VMEM_BUDGET_BYTES) -> list:
    """Launch-plan export for a whole chart, mirroring ``plan()`` routing.

    One group dict per launch unit: ``{"level", "route", "geom",
    "plans"}``. When the §11 pyramid cover fires, the covered prefix is
    ONE group (``route="pyramid"``, ``level=(0, k-1)``, ``geom`` the list
    of covered geometries) whose plans are the multi-level launch and
    the adjoint launches of its backward; the remaining levels follow with their per-level forward + adjoint
    plans. Reference-routed levels appear with an empty plan list so the
    verifier can still see them. ``sample_block`` overrides the pyramid
    cover's autotuned sample block (the tile-sweep tests drive it).
    """
    from .pyramid import pyramid_launch_plan, sample_slab

    if have_axis_mats is None:
        have_axis_mats = chart.ndim > 1
    dtype = jnp.dtype(dtype or jnp.float32)
    itemsize = dtype.itemsize
    cover = (pyramid_cover(chart, have_axis_mats=have_axis_mats,
                           samples=samples, itemsize=itemsize,
                           vmem_budget=vmem_budget) if pyramid else None)
    k_cov, s_b_cov = cover if cover is not None else (0, None)
    groups = []
    if k_cov:
        geoms = [LevelGeom.for_level(chart, lvl) for lvl in range(k_cov)]
        fsz, csz = geoms[0].n_fsz, geoms[0].n_csz
        s_b = max(1, min(sample_block or s_b_cov or 1, samples))
        if chart.ndim == 1:
            s_b = sample_slab(s_b, samples, itemsize)
        sp = -(-samples // s_b) * s_b
        plans = [pyramid_launch_plan(
            levels=[(tuple(g.T), tuple(g.coarse_shape)) for g in geoms],
            charted=[_pyramid_charted(g) for g in geoms], sp=sp,
            s_b=s_b, csz=csz, fsz=fsz, dtype=dtype,
            accum_dtype=accum_dtype, boundary=geoms[0].boundary,
            b=geoms[0].b)]
        # the backward (pyramid._pyramid_adjoint): each covered level's
        # 1-D adjoints, last level first
        s = max(1, fsz // 2)
        q_max = (csz - 1) // s
        for g in geoms[::-1]:
            T = tuple(g.T)
            plans += axes_adjoint_plans(
                T, t0p=T[0], samples=sp,
                coarse_lens=tuple((t + q_max) * s for t in T),
                charted=_pyramid_charted(g), csz=csz, fsz=fsz, dtype=dtype,
                accum_dtype=accum_dtype)
        groups.append({"level": (0, k_cov - 1), "route": ROUTE_PYRAMID,
                       "geom": geoms, "plans": plans})
    for lvl in range(k_cov, chart.n_levels):
        geom = LevelGeom.for_level(chart, lvl)
        route = route_for(geom, have_axis_mats=have_axis_mats,
                          itemsize=itemsize)
        groups.append({"level": lvl, "route": route, "geom": geom,
                       "plans": level_launch_plans(
                           geom, route, samples=samples, dtype=dtype,
                           accum_dtype=accum_dtype,
                           have_axis_mats=have_axis_mats)})
    return groups


def refine(field: Array, xi: Array, r: Array, d: Array, geom: LevelGeom, *,
           axis_mats=None, backend: str | None = None,
           block_families: int | None = None,
           sample_axis: bool = False,
           sample_block: int | None = None,
           policy: DtypePolicy | str | None = None) -> Array:
    """Route one refinement application to the best available backend.

    Arguments follow ``core.refine.refine_level``; ``axis_mats`` optionally
    carries the per-axis factors ``(rs, ds)`` from
    ``axis_refinement_matrices_level``, enabling the fused N-D paths (when
    present, the joint ``r``/``d`` are ignored on N-D levels).

    ``sample_axis=True`` marks the leading dimension of ``field``/``xi`` as
    a sample batch: the kernels process a whole sample slab per grid step
    (matrix loads amortized — DESIGN.md §10) instead of looping.

    ``policy`` (DESIGN.md §11): when given, every operand is cast to the
    policy's storage dtype on entry and the kernels accumulate in its accum
    dtype; when None, the storage dtype is whatever the operands carry and
    accumulation is f32. VMEM autotuning always follows the actual storage
    itemsize, so bf16 operands get twice the families per tile.

    Differentiable w.r.t. every array argument on every route: the kernel
    entry points carry custom VJPs running the fused adjoint kernels, the
    surrounding pads/reshapes are plain jnp.
    """
    accum_name = "float32"
    if policy is not None:
        pol = resolve_policy(policy)
        field, xi, r, d, axis_mats = pol.cast_storage(
            (field, xi, r, d, axis_mats))
        accum_name = pol.accum_name
    itemsize = jnp.dtype(field.dtype).itemsize
    route = route_for(geom, have_axis_mats=axis_mats is not None,
                      itemsize=itemsize)
    if backend is None:
        backend = select_backend()
    if route == ROUTE_REFERENCE and backend == BACKEND_PALLAS:
        raise ValueError(
            f"level {geom.coarse_shape} has no kernel route (an N-D level "
            "needs its per-axis factors — ICR.matrices(axes=True)); on the "
            "chip the jnp reference is not a fallback")
    if route == ROUTE_REFERENCE or backend == BACKEND_REFERENCE:
        if r is None or d is None:
            if axis_mats is None:
                raise ValueError(
                    "reference route needs the joint (r, d) matrices; this "
                    "level has none (ICR.matrices skipped the joint build) — "
                    "pass matrices(joint=True) or provide axis_mats covering "
                    "it"
                )
            # structured N-D level carrying only the per-axis factors: run
            # the jnp oracle of the factored path (kernels/ref.py) — the
            # production CPU answer. Honor the accumulation contract the
            # same way the joint branch below does: sub-accum storage is
            # upcast for the math and the result rounded back once per
            # level (the oracle's own per-pass accumulation rule then
            # operates at the policy's accum width or wider).
            from . import ref as _ref  # lazy: keeps import order flexible

            rs, ds = axis_mats
            out_dtype = field.dtype
            accum = jnp.dtype(accum_name)
            if jnp.dtype(out_dtype).itemsize < accum.itemsize:
                field, xi = field.astype(accum), xi.astype(accum)
                rs = [a.astype(accum) for a in rs]
                ds = [a.astype(accum) for a in ds]
            oracle = lambda f, x: _ref.refine_axes_ref(
                f, x, rs, ds, T=geom.T, n_fsz=geom.n_fsz,
                boundary=geom.boundary, b=geom.b)
            out = jax.vmap(oracle)(field, xi) if sample_axis \
                else oracle(field, xi)
            return out.astype(out_dtype)
        # honor the policy's accumulation contract here too: refine_level's
        # einsums carry no preferred_element_type, so sub-f32 storage is
        # upcast for the math and the result rounded back — same per-level
        # rounding the kernels produce, not a bf16-accumulated level
        out_dtype = field.dtype
        accum = jnp.dtype(accum_name)
        if jnp.dtype(out_dtype).itemsize < jnp.dtype(accum).itemsize:
            field, xi, r, d = (a.astype(accum) for a in (field, xi, r, d))
        if sample_axis:
            out = jax.vmap(
                lambda f, x: refine_level(f, x, r, d, geom))(field, xi)
        else:
            out = refine_level(field, xi, r, d, geom)
        return out.astype(out_dtype)
    if backend not in (BACKEND_PALLAS, BACKEND_INTERPRET):
        raise ValueError(f"unknown kernel backend {backend!r}")
    interpret = backend == BACKEND_INTERPRET

    if route == ROUTE_ND_FUSED:
        from . import nd_fused  # lazy: keeps import order flexible

        return nd_fused.refine_nd_fused(
            field, xi, axis_mats[0], axis_mats[1], geom,
            interpret=interpret, block_families=block_families,
            sample_block=sample_block, sample_axis=sample_axis,
            accum_dtype=accum_name,
        )
    if route == ROUTE_AXES_ND:
        return _nd.refine_axes(field, xi, axis_mats[0], axis_mats[1], geom,
                               interpret=interpret,
                               block_families=block_families,
                               sample_axis=sample_axis,
                               accum_dtype=accum_name)

    n_csz, n_fsz = geom.n_csz, geom.n_fsz
    t = geom.T[0]
    charted = route == ROUTE_CHARTED_1D
    if sample_axis:
        n_s = field.shape[0]
        coarse = field.reshape(n_s, -1)
        xi_k = xi.reshape(n_s, t, n_fsz)
    else:
        n_s = 1
        coarse = field.reshape(1, -1)
        xi_k = xi.reshape(1, t, n_fsz)
    if geom.boundary == "reflect":
        coarse = jnp.pad(coarse, [(0, 0), (geom.b, geom.b)], mode="reflect")
    b_f = block_families or autotune_block_families(
        t, n_csz, n_fsz, charted=charted, itemsize=itemsize
    )
    b_b = sample_block or autotune_batch_block(
        n_s, t, n_csz, n_fsz, charted=charted, block_families=b_f,
        itemsize=itemsize
    )
    if charted:
        out = refine_charted_pallas(
            coarse, xi_k, r.reshape(t, n_fsz, n_csz),
            d.reshape(t, n_fsz, n_fsz), n_csz=n_csz, n_fsz=n_fsz,
            block_families=b_f, batch_block=b_b, interpret=interpret,
            accum_dtype=accum_name,
        )
    else:
        out = refine_stationary_pallas(
            coarse, xi_k, r.reshape(n_fsz, n_csz),
            d.reshape(n_fsz, n_fsz), n_csz=n_csz, n_fsz=n_fsz,
            block_families=b_f, batch_block=b_b, interpret=interpret,
            accum_dtype=accum_name,
        )
    if sample_axis:
        return out.reshape((n_s,) + geom.fine_shape)
    return out.reshape(geom.fine_shape)


# A note on buffer donation (investigated for the §11 ping-pong chain and
# deliberately NOT used): jax donation is input->output aliasing, which
# needs a donated input whose shape/dtype matches an output. Refinement is
# strictly expansive — the fine output is 2^d times the coarse input, the
# adjoint's the reverse — so no level has an aliasable pair; a
# donate_argnums wrapper here compiles to a no-op plus a "donated buffer
# not usable" warning per geometry. Inside a jitted apply, XLA's buffer
# liveness already reclaims the coarse buffer for temporaries after its
# last read, which is all a donation could have achieved.
