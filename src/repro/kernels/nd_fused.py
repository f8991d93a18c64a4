"""Single-launch fused N-D refinement level — the megakernel (DESIGN.md §10).

The per-axis N-D path (``nd.refine_axes``) executes one refinement level as
``d`` separate Pallas launches with the intermediate field round-tripping
through HBM between axis passes (plus a relayout around every pass). For the
flagship 3-D dust map that is ~3x the minimal field traffic. This module
collapses a whole level into ONE ``pallas_call``:

  * each grid step loads a coarse tile — a slab of ``b_f`` axis-0 families
    (halo via the second-shifted-view trick, DESIGN.md §3) times the FULL
    extent of every trailing axis — into VMEM,
  * performs all ``d`` per-axis Kronecker contractions back-to-back in
    VMEM on a phase-plane tile (``planes.py``: every axis split into
    ``s`` phases outside the kernel, so a window along any axis is an
    offset slice — no gather, no strided access),
  * adds the correlated noise ``sqrt(D_0) ξ`` (the noise factors of axes
    ``1..d-1`` are pre-contracted into ξ outside, exactly like
    ``nd.refine_axes``), and
  * writes the fine tile once.

HBM traffic per level drops from ``d·(read+write N)`` plus relayouts to
``read L + read ξ + write N`` — the 1-D kernel's traffic equation, now at
any dimensionality (``roofline.level_traffic`` carries the model).

Tiling is along axis 0 only; the trailing axes ride whole inside the tile,
the longest of them on the lanes.
When the joint tile + halos exceed the VMEM budget the dispatch layer falls
back to the per-axis passes (``dispatch.autotune_nd_fused`` returns None —
the fallback rule of DESIGN.md §10).

A native leading **sample-batch dimension** (``s_b`` samples per tile) lets
batched posterior sampling / serving amortize every matrix load across the
slab instead of lifting the batch into the grid.

Differentiation: the core carries a ``jax.custom_vjp``. At fixed matrices
(MAP/ADVI inference, ``apply_sqrt_T``) the backward hand-composes the
existing 1-D *adjoint* kernels in reverse axis order — each a fused
gather-free launch, the non-axis-0 ones in ``noise=False`` mode (no dxi).
When the matrices are perturbed (learning θ) the backward falls back to
``jax.vjp`` of the independent jnp reference ``_nd_fused_ref`` — the
parameter-sized window einsums of DESIGN.md §9, gated by
``symbolic_zeros`` so inference never pays them.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.custom_derivatives import SymbolicZero

from repro.core.refine import LevelGeom

from .icr_refine import (
    refine_charted_adjoint_pallas,
    refine_stationary_adjoint_pallas,
)
from .launch import (
    IndexMap,
    LaunchPlan,
    OperandSpec,
    pad_to,
    run_plan,
    smem_scalars,
)
from .planes import axis_step, fine_to_planes, from_planes, to_planes
from .ref import windows_1d

Array = jnp.ndarray


# -- in-VMEM building blocks ----------------------------------------------------
def _slice_axis(x: Array, ax: int, length: int) -> Array:
    if x.shape[ax] == length:
        return x
    idx = [slice(None)] * x.ndim
    idx[ax] = slice(0, length)
    return x[tuple(idx)]


def _axis_windows(x: Array, ax: int, t: int, s: int, n_csz: int) -> Array:
    """Window matrix along axis ``ax``: (..., rows*s, ...) -> (..., t, n_csz,
    ...) with the window dim inserted right after ``ax``.

    Same contiguous-reshape + static-row-shift construction as the 1-D
    ``_window_cols`` (element ``t·s + k`` = reshape(rows, s)[t + k//s, k%s])
    applied to an interior axis — no gather, no strided access.
    """
    q_max = (n_csz - 1) // s
    shp = x.shape
    rows = shp[ax] // s  # == t + q_max by construction
    assert rows >= t + q_max
    resh = x.reshape(shp[:ax] + (rows, s) + shp[ax + 1 :])
    cols = []
    for k in range(n_csz):
        q, r = divmod(k, s)
        idx = [slice(None)] * resh.ndim
        idx[ax] = slice(q, q + t)
        idx[ax + 1] = r
        cols.append(resh[tuple(idx)])
    return jnp.stack(cols, axis=ax + 1)


def _contract_windows(w: Array, r: Array, ax: int, *, merge: bool = True,
                      accum=jnp.float32) -> Array:
    """Contract the window dim (at ``ax + 1``) with a refinement factor.

    w: (..., t, n_csz, ...); r: (n_fsz, n_csz) shared or (t, n_fsz, n_csz)
    per-family -> (..., t*n_fsz, ...) (or unmerged (..., t, n_fsz, ...)).
    """
    n = w.ndim
    ls = [chr(ord("a") + i) for i in range(n)]
    t_l, c_l = ls[ax], ls[ax + 1]
    f_l = chr(ord("a") + n)
    out_ls = list(ls)
    out_ls[ax + 1] = f_l
    rsub = (t_l + f_l + c_l) if r.ndim == 3 else (f_l + c_l)
    out = jnp.einsum(f"{''.join(ls)},{rsub}->{''.join(out_ls)}", w, r,
                     preferred_element_type=accum)
    if merge:
        shp = out.shape
        out = out.reshape(shp[:ax] + (shp[ax] * shp[ax + 1],) + shp[ax + 2 :])
    return out


# -- the megakernel -------------------------------------------------------------
def fused_layout(nd: int, lq_trail) -> tuple:
    """Position-dim order of the megakernel tile: axis 0 (the tiled axis)
    first, then the trailing axes by plane length, longest on the lanes."""
    trail = sorted(range(1, nd), key=lambda a: lq_trail[a - 1])
    return (0,) + tuple(trail)


def fused_block(nd: int, t0: int, b_f: int, itemsize: int) -> int:
    """Legal axis-0 family block: the whole axis, or (on a 2-D tile, where
    axis 0 rides the sublanes) a multiple of the sublane tile."""
    if b_f >= t0:
        return t0
    if nd == 2:
        sub = 8 * max(1, 4 // itemsize)
        b_f = -(-b_f // sub) * sub
        return t0 if b_f >= t0 else b_f
    return b_f


def _coef_shape(nd: int, layout, a: int, n: int) -> tuple:
    """Broadcast shape of axis ``a``'s coefficient planes in the tile:
    ``n`` families on axis a's position dim (1 when stationary)."""
    shape = [1] * (2 * nd + 1)
    shape[nd + 1 + list(layout).index(a)] = n
    return tuple(shape)


def _coef_fn(ref, width, charted):
    """Coefficient accessor of one axis: a float32 broadcast plane of a
    charted factor (kept f32 in HBM: Mosaic broadcasts a value with unit
    sublane and lane dims only from its own f32 layout), or an SMEM
    scalar of a stationary one."""
    if charted:
        m = ref[...]
        return lambda f, k: m[f * width + k]
    return lambda f, k: ref[f * width + k]


def _nd_fused_kernel(*refs, nd: int, csz: int, fsz: int, T: tuple,
                     layout: tuple, b_f: int, halo: bool, charted: tuple):
    it = iter(refs)
    c_ref = next(it)
    h_ref = next(it) if halo else None
    xi_ref = next(it)
    r0_ref = next(it)
    d0_ref = next(it)
    rt_refs = [next(it) for _ in range(nd - 1)]
    out_ref = next(it)
    s = fsz // 2
    ud = [nd + 1 + list(layout).index(a) for a in range(nd)]
    x = c_ref[...].astype(jnp.float32)
    if halo:
        x = jnp.concatenate([x, h_ref[...].astype(jnp.float32)], axis=ud[0])
    # all d contractions on this tile in VMEM; nothing intermediate ever
    # goes back to HBM
    for a in range(nd - 1, 0, -1):
        x = axis_step(x, pd=a, ud=ud[a], s=s, csz=csz, fsz=fsz, off=0,
                      lout=T[a],
                      coef_r=_coef_fn(rt_refs[a - 1], csz, charted[a]))
    x = axis_step(x, pd=0, ud=ud[0], s=s, csz=csz, fsz=fsz, off=0,
                  lout=b_f, coef_r=_coef_fn(r0_ref, csz, charted[0]),
                  coef_d=_coef_fn(d0_ref, fsz, charted[0]),
                  xi=xi_ref[...].astype(jnp.float32))
    out_ref[...] = x.astype(out_ref.dtype)


def fused_launch_shapes(geom: LevelGeom, *, samples: int, b_f: int,
                        s_b: int) -> dict:
    """Padded operand extents of one megakernel launch, natural layout.

    The single source of truth shared by ``refine_nd_fused``'s padding and
    the geom-level plan export (``dispatch.level_launch_plans``) — the
    shapes the wrapper pads to and the shapes the verifier proves coverage
    for cannot drift apart.
    """
    nd = len(geom.coarse_shape)
    fsz, csz, b = geom.n_fsz, geom.n_csz, geom.b
    s = fsz // 2
    q_max = (csz - 1) // s
    T = tuple(geom.T)
    pad = 2 * b if geom.boundary == "reflect" else 0
    lp_trail = tuple(
        -(-max(geom.coarse_shape[a] + pad, (T[a] + q_max) * s) // s) * s
        for a in range(1, nd))
    nblk = -(-T[0] // b_f)
    l0 = geom.coarse_shape[0] + pad
    nblk2 = max(nblk + 1, -(-l0 // (b_f * s)))
    prod_f = 1
    for a in range(1, nd):
        prod_f *= T[a] * fsz
    return dict(nd=nd, T=T, nblk=nblk, l0p=nblk2 * b_f * s,
                lp_trail=lp_trail, sp=-(-samples // s_b) * s_b,
                prod_f=prod_f)


def nd_fused_launch_plan(*, nd: int, csz: int, fsz: int, T: tuple,
                         charted: tuple, b_f: int, s_b: int, sp: int,
                         l0p: int, lp_trail: tuple, nblk: int, prod_f: int,
                         dtype, accum_dtype) -> LaunchPlan:
    """Declarative launch geometry of one fused N-D level launch.

    The tile is the phase-plane layout of ``planes.py``: ``s`` phases per
    axis, the sample block, then the positions with axis 0 (blocked by
    ``b_f`` families, halo from the next block) first and the longest
    trailing axis on the lanes.
    """
    s = fsz // 2
    q_max = (csz - 1) // s
    nbs = sp // s_b
    dtype = jnp.dtype(dtype).name
    lq_trail = tuple(n // s for n in lp_trail)
    layout = fused_layout(nd, lq_trail)
    halo = nblk > 1
    pos_in = {0: None, **{a: lq_trail[a - 1] for a in range(1, nd)}}
    pos_out = {a: T[a] for a in range(nd)}
    l0q = (nblk + 1) * b_f if halo else T[0] + q_max
    pos_in[0] = l0q
    pos_out[0] = nblk * b_f
    trail_in = tuple(pos_in[a] for a in layout[1:])
    trail_out = tuple(pos_out[a] for a in layout[1:])
    zeros = (0,) * (nd - 1)

    def im(name, shift=0):
        return IndexMap(f"({', '.join(['0'] * nd)}, b, i{name}"
                        f"{', 0' * (nd - 1)})",
                        lambda i, b, _s=shift: (0,) * nd + (b, i + _s)
                        + zeros)

    im_main, im_halo = im(""), im(" + 1", 1)
    blk0 = b_f if halo else l0q
    field_shape = (s,) * nd + (sp, l0q) + trail_in
    field_blk = (s,) * nd + (s_b, blk0) + trail_in
    if halo:
        over = [(0, 0)] * (2 * nd + 1)
        over[nd + 1] = (0, q_max)
        inputs = [
            OperandSpec("field", field_blk, im_main, field_shape, dtype,
                        overhang=tuple(over)),
            OperandSpec("field_halo", field_blk, im_halo, field_shape, dtype,
                        halo_of="field"),
        ]
    else:
        inputs = [OperandSpec("field", field_blk, im_main, field_shape,
                              dtype)]
    out_shape = (fsz,) * nd + (sp, nblk * b_f) + trail_out
    out_blk = (fsz,) * nd + (s_b, b_f) + trail_out
    inputs.append(OperandSpec("xi", out_blk, im_main, out_shape, dtype))
    rank = 2 * nd + 1
    for name, rows in (("r0", fsz * csz), ("d0", fsz * fsz)):
        if charted[0]:
            cshape = (rows,) + _coef_shape(nd, layout, 0, nblk * b_f)
            cblk = (rows,) + _coef_shape(nd, layout, 0, b_f)
            cim = IndexMap("(0, ..., i, ...)",
                           lambda i, b, _r=rank: (0,) * (_r - nd + 1)
                           + (i,) + (0,) * (nd - 1))
            inputs.append(OperandSpec(name, cblk, cim, cshape, "float32"))
        else:
            inputs.append(smem_scalars(name, rows))
    for a in range(1, nd):
        if charted[a]:
            cshape = (fsz * csz,) + _coef_shape(nd, layout, a, T[a])
            inputs.append(OperandSpec(
                f"r{a}", cshape,
                IndexMap("(0, ..., 0)",
                         lambda i, b, _r=rank: (0,) * (_r + 1)),
                cshape, "float32"))
        else:
            inputs.append(smem_scalars(f"r{a}", fsz * csz))
    out = OperandSpec("fine", out_blk, im_main, out_shape, dtype)
    return LaunchPlan(
        kernel="refine_nd_fused", grid=(nblk, nbs),
        inputs=tuple(inputs), outputs=(out,),
        accum_dtype=jnp.dtype(accum_dtype).name,
        params=dict(kind="fwd", nd=nd, csz=csz, fsz=fsz, T=tuple(T),
                    charted=tuple(charted), s=s, b_f=b_f, s_b=s_b,
                    nblk=nblk, nbs=nbs, l0p=l0p, halo=halo,
                    layout=layout, lp_trail=tuple(lp_trail),
                    prod_f=prod_f),
    )


def plan_kernel(plan: LaunchPlan):
    """The kernel body a megakernel plan launches."""
    p = plan.params
    return functools.partial(
        _nd_fused_kernel, nd=p["nd"], csz=p["csz"], fsz=p["fsz"], T=p["T"],
        layout=p["layout"], b_f=p["b_f"], halo=p["halo"],
        charted=p["charted"])


def _fused_coef(m, nd: int, layout, a: int, n: int, *, charted: bool):
    """Per-axis factor -> coefficient planes in the tile's broadcast
    shape (families on axis a's position dim, zero-padded to ``n``)."""
    if not charted:
        return m.reshape(-1).astype(jnp.float32)
    t = m.shape[0]
    cp = jnp.pad(m.reshape(t, -1).T, [(0, 0), (0, n - t)])
    return cp.reshape((cp.shape[0],) + _coef_shape(nd, layout, a, n)) \
        .astype(jnp.float32)


def _nd_fused_impl(meta, field: Array, xi0: Array, r0: Array, d0: Array,
                   rts: tuple) -> Array:
    nd, csz, fsz, T, charted, b_f, s_b, interpret, accum_name = meta
    s = fsz // 2
    t0p = xi0.shape[1] // fsz
    plan = nd_fused_launch_plan(
        nd=nd, csz=csz, fsz=fsz, T=T, charted=charted, b_f=b_f, s_b=s_b,
        sp=field.shape[0], l0p=field.shape[1], lp_trail=field.shape[2:],
        nblk=t0p // b_f, prod_f=xi0.shape[2],
        dtype=field.dtype, accum_dtype=accum_name)
    p = plan.params
    layout = p["layout"]
    dt = field.dtype
    l0q = plan.operand("field").array_shape[nd + 1]
    lens = (l0q,) + tuple(n // s for n in field.shape[2:])
    fp = to_planes(field, lens, s=s, lead=1, layout=layout)
    t_full = (t0p,) + tuple(T[1:])
    xp = fine_to_planes(xi0.astype(dt), t_full, fsz=fsz, layout=layout)
    ops = [fp, fp] if p["halo"] else [fp]
    ops.append(xp)
    ops.append(_fused_coef(r0, nd, layout, 0, t0p, charted=charted[0]))
    ops.append(_fused_coef(d0, nd, layout, 0, t0p, charted=charted[0]))
    for a in range(1, nd):
        ops.append(_fused_coef(rts[a - 1], nd, layout, a, T[a],
                               charted=charted[a]))
    out = run_plan(plan_kernel(plan), plan, ops, interpret=interpret)
    out = from_planes(out, layout=layout)
    return out.reshape(out.shape[0], t0p * fsz, -1)


def _nd_fused_ref(meta, field: Array, xi0: Array, r0: Array, d0: Array,
                  rts: tuple) -> Array:
    """Pure-jnp reference of the megakernel core (same padded operands).

    Ground truth for the parity tests and the learned-θ backward: windows
    via strided slices, contractions as einsums — materializes what the
    kernel keeps in VMEM.
    """
    nd, csz, fsz, T, charted, b_f, s_b, interpret, accum_name = meta
    accum = jnp.dtype(accum_name)
    s = fsz // 2
    q_max = (csz - 1) // s
    sp = field.shape[0]
    t0p = xi0.shape[1] // fsz
    prod_f = xi0.shape[2]

    x = field
    for a in range(nd - 1, 0, -1):
        ax = 1 + a
        arr = jnp.moveaxis(x, ax, -1)[..., : (T[a] + q_max) * s]
        w = windows_1d(arr, T[a], csz, s)
        eq = "...tc,tfc->...tf" if rts[a - 1].ndim == 3 else "...tc,fc->...tf"
        fine = jnp.einsum(eq, w, rts[a - 1], preferred_element_type=accum)
        fine = fine.reshape(arr.shape[:-1] + (T[a] * fsz,))
        x = jnp.moveaxis(fine, -1, ax)

    arr = jnp.moveaxis(x, 1, -1)                  # (sp, *F_trail, L0p)
    w = windows_1d(arr, t0p, csz, s)
    eq = "...tc,tfc->...tf" if r0.ndim == 3 else "...tc,fc->...tf"
    fine = jnp.einsum(eq, w, r0,                  # (sp, *F_trail, T0p, fsz)
                      preferred_element_type=accum)
    fine = fine.reshape(sp, prod_f, t0p, fsz).transpose(0, 2, 3, 1)

    xi3 = xi0.reshape(sp, t0p, fsz, prod_f)
    eq = "stjp,tfj->stfp" if d0.ndim == 3 else "stjp,fj->stfp"
    fine = fine + jnp.einsum(eq, xi3, d0, preferred_element_type=accum)
    return fine.reshape(sp, t0p * fsz, prod_f).astype(field.dtype)


# -- custom VJP -----------------------------------------------------------------
@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _nd_fused_core(meta, field, xi0, r0, d0, rts):
    return _nd_fused_impl(meta, field, xi0, r0, d0, rts)


def _core_fwd(meta, field, xi0, r0, d0, rts):
    vals = (field.value, xi0.value, r0.value, d0.value,
            tuple(t.value for t in rts))
    out = _nd_fused_impl(meta, *vals[:4], vals[4])
    mats_pert = (r0.perturbed or d0.perturbed
                 or any(t.perturbed for t in rts))
    return out, vals + (() if mats_pert else None,)


def _core_bwd(meta, res, g):
    nd, csz, fsz, T, charted, b_f, s_b, interpret, accum_name = meta
    field, xi0, r0, d0, rts, mats_pert = res
    zeros = (jnp.zeros_like(field), jnp.zeros_like(xi0),
             jnp.zeros_like(r0), jnp.zeros_like(d0),
             tuple(jnp.zeros_like(t) for t in rts))
    if isinstance(g, SymbolicZero):
        return zeros
    if mats_pert is not None:
        # learning θ: the matrix cotangents need the per-stage window
        # tensors; replay the jnp reference under jax.vjp (parameter-sized
        # einsums, DESIGN.md §9 — never the hot inference path).
        _, vjp = jax.vjp(
            lambda fl, x, a, b, c: _nd_fused_ref(meta, fl, x, a, b, c),
            field, xi0, r0, d0, rts)
        return vjp(g)

    # fixed matrices: compose the 1-D adjoint kernels in reverse axis order.
    df, dxi = axes_adjoint(
        g, r0, d0, rts, T=T, csz=csz, fsz=fsz, coarse_lens=field.shape[1:],
        charted=charted, interpret=interpret, accum_dtype=accum_name)
    return (df.astype(field.dtype), dxi.astype(xi0.dtype), zeros[2],
            zeros[3], zeros[4])


def axes_adjoint(g: Array, r0: Array, d0: Array, rts, *, T: tuple, csz: int,
                 fsz: int, coarse_lens: tuple, charted: tuple, interpret,
                 accum_dtype: str):
    """Transpose of one N-D level at fixed matrices, as 1-D adjoint kernels
    in reverse axis order: the forward contracts the trailing axes
    (noise-free) and then axis 0 (with the noise add), so the adjoint runs
    axis 0 first — ``dξ`` shares its fine-cotangent read — then axes
    1..d-1 with ``noise=False``.

    g: (S, T0·fsz, prod_f), ``T0 = g.shape[1] // fsz`` families along axis
    0 (padded ones included); ``coarse_lens``: the forward's coarse extent
    per axis (the tail past a trailing axis's window reach gets zero).
    Returns ``(dfield (S, *coarse_lens), dξ0 (S, T0·fsz, prod_f))`` in the
    cotangent's dtype. Shared by the megakernel and the pyramid backward.
    """
    from .dispatch import axis_pass_tiles  # lazy: import cycle

    nd = len(T)
    s = fsz // 2
    q_max = (csz - 1) // s
    sp = g.shape[0]
    l0p, lp_trail = coarse_lens[0], tuple(coarse_lens[1:])
    t0p = g.shape[1] // fsz
    prod_f = g.shape[2]
    f_trail = tuple(T[a] * fsz for a in range(1, nd))
    itemsize = jnp.dtype(g.dtype).itemsize

    # axis-0 adjoint (with noise: dxi shares the fine-cotangent read)
    gb = jnp.moveaxis(g, 1, -1).reshape(sp * prod_f, t0p * fsz)
    bf0, bb0 = axis_pass_tiles(gb.shape[0], t0p, csz, fsz,
                               charted=charted[0], itemsize=itemsize)
    adj0 = (refine_charted_adjoint_pallas if charted[0]
            else refine_stationary_adjoint_pallas)
    dc0, dxi0 = adj0(gb, r0, d0, coarse_len=l0p, n_csz=csz, n_fsz=fsz,
                     block_families=bf0, batch_block=bb0,
                     interpret=interpret, accum_dtype=accum_dtype)
    dxi = dxi0.reshape(sp, prod_f, t0p, fsz).transpose(0, 2, 3, 1)
    dxi = dxi.reshape(sp, t0p * fsz, prod_f)
    cur = dc0.reshape((sp,) + f_trail + (l0p,))
    cur = jnp.moveaxis(cur, -1, 1)                # (sp, L0p, *F_trail)

    # trailing-axis adjoints, noise=False: no ξ was injected on those passes
    for a in range(1, nd):
        ax = 1 + a
        arr = jnp.moveaxis(cur, ax, -1)
        bshape = arr.shape[:-1]
        g_a = arr.reshape(-1, T[a] * fsz)
        bf_a, bb_a = axis_pass_tiles(g_a.shape[0], T[a], csz, fsz,
                                     charted=charted[a], itemsize=itemsize)
        adj = (refine_charted_adjoint_pallas if charted[a]
               else refine_stationary_adjoint_pallas)
        used = (T[a] + q_max) * s
        dca = adj(g_a, rts[a - 1], coarse_len=used, n_csz=csz, n_fsz=fsz,
                  block_families=bf_a, batch_block=bb_a,
                  interpret=interpret, noise=False, accum_dtype=accum_dtype)
        if lp_trail[a - 1] > used:  # tail the forward's tile slice dropped
            dca = jnp.pad(dca, [(0, 0), (0, lp_trail[a - 1] - used)])
        cur = jnp.moveaxis(dca.reshape(bshape + (lp_trail[a - 1],)), -1, ax)
    return cur, dxi


_nd_fused_core.defvjp(_core_fwd, _core_bwd, symbolic_zeros=True)


# -- excitation layout (shared by the megakernel, the per-axis passes and
# the §11 pyramid — one implementation of the §10 ξ convention) ---------------
def precontract_noise(xi_nd, ds, *, off: int, accum) -> Array:
    """Fold the trailing-axis noise factors ``sqrt(D_a)``, a >= 1, into the
    ``(..., T_0..T_{d-1}, f_0..f_{d-1})`` excitation tensor (only the
    axis-0 pass injects noise in-kernel; ``off`` counts leading batch/sample
    dims). Cheap batched small GEMMs, accumulated in ``accum``."""
    nd = (xi_nd.ndim - off) // 2
    for a in range(1, nd):
        x2 = jnp.moveaxis(xi_nd, (off + a, off + nd + a), (-2, -1))
        eq = "...tj,fj->...tf" if ds[a].ndim == 2 else "...tj,tfj->...tf"
        x2 = jnp.einsum(eq, x2, ds[a], preferred_element_type=accum,
                        precision=jax.lax.Precision.HIGHEST)
        xi_nd = jnp.moveaxis(x2, (-2, -1), (off + a, off + nd + a))
    return xi_nd


def prepare_xi0(xi: Array, ds, T: tuple, fsz: int, *, accum,
                storage) -> Array:
    """``(S, prod T, fsz^d)`` ξ -> the megakernel tile layout
    ``(S, T_0·fsz, prod_f)`` with trailing noise pre-contracted."""
    nd = len(T)
    n_s = xi.shape[0]
    xi_nd = precontract_noise(
        xi.reshape((n_s,) + tuple(T) + (fsz,) * nd), ds, off=1, accum=accum)
    perm = [0, 1, 1 + nd]
    for a in range(1, nd):
        perm += [1 + a, 1 + nd + a]
    return xi_nd.transpose(perm).reshape(n_s, T[0] * fsz, -1).astype(storage)


# -- public wrapper -------------------------------------------------------------
def refine_nd_fused(field: Array, xi: Array, rs, ds, geom: LevelGeom, *,
                    interpret: bool,
                    block_families: int | None = None,
                    sample_block: int | None = None,
                    sample_axis: bool = False,
                    accum_dtype: str = "float32") -> Array:
    """One fused Pallas launch for a whole N-D refinement level.

    Drop-in for ``nd.refine_axes`` (bit-compatible at 1e-5 given the same
    per-axis factors). With ``sample_axis=True`` the leading dimension of
    ``field``/``xi`` is a sample batch processed natively inside the kernel
    tiles (``s_b`` samples per grid step).

    field: (*geom.coarse_shape) or (S, *coarse_shape);
    xi: (prod(T), n_fsz^d) or (S, prod(T), n_fsz^d);
    rs[a]/ds[a]: per-axis factors from ``axis_refinement_matrices_level``.
    """
    from .dispatch import autotune_nd_fused  # lazy: avoid import cycle

    nd = len(geom.coarse_shape)
    if nd < 2:
        raise ValueError("refine_nd_fused needs an N-D level (ndim >= 2)")
    fsz, csz, b = geom.n_fsz, geom.n_csz, geom.b
    s = fsz // 2
    q_max = (csz - 1) // s
    T = tuple(geom.T)
    charted = tuple(rs[a].ndim == 3 for a in range(nd))
    accum = jnp.dtype(accum_dtype)

    if not sample_axis:
        field, xi = field[None], xi[None]
    n_s = field.shape[0]

    blocks = autotune_nd_fused(geom, charted=charted, samples=n_s,
                               itemsize=jnp.dtype(field.dtype).itemsize)
    if blocks is None:
        raise ValueError(
            "fused N-D tile exceeds the VMEM budget; dispatch should have "
            "routed this level to the per-axis passes (nd.refine_axes)"
        )
    b_f, s_b = blocks
    if block_families is not None:
        b_f = max(min(block_families, T[0]), q_max, 1)
    b_f = fused_block(nd, T[0], b_f, jnp.dtype(field.dtype).itemsize)
    if sample_block is not None:
        s_b = max(1, min(sample_block, n_s))

    # -- excitation: pre-contract noise factors of axes 1..d-1 -----------------
    xi0 = prepare_xi0(xi, ds, T, fsz, accum=accum, storage=field.dtype)

    # -- pad to the plan's operand extents (reflect pre-pad is a real array
    # op, the rest is zero fill up to the shared launch-shape record) ---------
    shapes = fused_launch_shapes(geom, samples=n_s, b_f=b_f, s_b=s_b)
    nblk, sp = shapes["nblk"], shapes["sp"]
    if geom.boundary == "reflect":
        field = jnp.pad(field, [(0, 0)] + [(b, b)] * nd, mode="reflect")
    field = pad_to(field, (sp, shapes["l0p"]) + shapes["lp_trail"])
    xi0 = pad_to(xi0, (sp, nblk * b_f * fsz, xi0.shape[2]))
    r0, d0 = rs[0], ds[0]
    if charted[0]:
        r0 = pad_to(r0, (nblk * b_f,) + r0.shape[1:])
        d0 = pad_to(d0, (nblk * b_f,) + d0.shape[1:])

    meta = (nd, csz, fsz, T, charted, b_f, s_b, interpret, accum_dtype)
    out = _nd_fused_core(meta, field, xi0, r0, d0,
                         tuple(rs[a] for a in range(1, nd)))
    out = out[:n_s, : T[0] * fsz]
    f_trail = tuple(T[a] * fsz for a in range(1, nd))
    out = out.reshape((n_s, T[0] * fsz) + f_trail)
    return out if sample_axis else out[0]
