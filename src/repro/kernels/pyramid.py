"""VMEM-resident pyramid kernel: many refinement levels, ONE launch
(DESIGN.md §11).

Every per-level route — even the §10 megakernel — writes its fine field to
HBM and reads it back as the next level's coarse input. For the *early*
levels of a chart that round trip is pure waste: a 3-D chart's levels grow
8x per step, so the first k levels are tiny and their combined working set
(fields + excitations + matrices) fits comfortably in VMEM. This module
collapses all consecutive early levels whose combined working set fits the
VMEM budget (``dispatch.autotune_pyramid`` owns the residency criterion)
into one ``pallas_call``:

  * the grid runs over sample slabs only (``s_b`` samples per step) — no
    spatial tiling, the whole field of every covered level is resident,
  * the field lives in a polyphase tile (``planes.py``): positions fixed
    at the first level's, the phase count of every axis doubling per
    level, so each level's per-axis contractions are phase selections and
    position shifts (reflected-boundary fills at the ends), then the fused
    noise add ``sqrt(D_0)·ξ`` (trailing noise factors pre-contracted into
    ξ outside, exactly like §10),
  * the fine field of level l feeds level l+1 *in registers/VMEM* — the
    inter-level HBM field traffic of the covered prefix is ZERO,
  * only the final level's field is written to HBM.

HBM traffic for the covered prefix drops from ``Σ_l (read L_l + read ξ_l +
write N_l)`` to ``read L_0 + Σ_l read ξ_l + write N_{k-1}`` (+ matrices) —
``roofline.level_traffic`` carries the per-level model (``route=
"pyramid"`` with first/last flags).

1-D charts are covered too (the per-axis factor list has one entry); the
dtype policy (§11) threads through: storage dtype = operand dtype, every
contraction accumulates in ``accum_dtype``, and each level's in-VMEM output
is rounded to the storage dtype so the pyramid is numerically identical to
the level-by-level routes under the same policy.

Backward: the core carries a ``jax.custom_vjp``. At fixed matrices the
chain is linear in (field, ξ), and its transpose runs each covered level's
1-D adjoint kernels (``nd_fused.axes_adjoint``) last level first: the
covered levels are by construction the smallest in the chart, so a
level-by-level backward through HBM costs little next to the uncovered big
levels; the forward is where the pyramid pays for itself. Learning θ (the
matrix cotangents, gated by ``symbolic_zeros`` exactly like §9/§10)
replays the jnp reference of the chain (natural layout, ``_apply_levels``)
under ``jax.vjp``, as does the CPU reference backend.
"""
from __future__ import annotations

import functools

import numpy as np
import jax
import jax.numpy as jnp
from jax.custom_derivatives import SymbolicZero

from .launch import (
    IndexMap,
    LaunchPlan,
    OperandSpec,
    run_plan,
    smem_scalars,
)
from .nd_fused import (
    _axis_windows,
    axes_adjoint,
    _coef_fn,
    _contract_windows,
    _slice_axis,
    prepare_xi0,
)
from .planes import axis_step, from_planes, to_planes

Array = jnp.ndarray


def _fit_axis(x: Array, ax: int, want: int) -> Array:
    """Slice or zero-pad axis ``ax`` to exactly ``want`` — the window build
    needs ``(T + q_max)·s`` elements, never more (§10 tile rule)."""
    have = x.shape[ax]
    if have > want:
        return _slice_axis(x, ax, want)
    if have < want:
        pads = [(0, 0)] * x.ndim
        pads[ax] = (0, want - have)
        return jnp.pad(x, pads)
    return x


def _level_body(x: Array, xi0: Array, rl, d0, *, T: tuple, csz: int,
                fsz: int, boundary: str, b: int, accum, storage) -> Array:
    """One refinement level at full extent, entirely in VMEM.

    x: (s_b, *coarse_shape) -> (s_b, *fine_shape); xi0: (s_b, T0·fsz,
    prod_f) with trailing noise factors pre-contracted (§10 layout).
    """
    nd = len(T)
    s = fsz // 2
    q_max = (csz - 1) // s
    s_b = x.shape[0]

    if boundary == "reflect":
        x = jnp.pad(x, [(0, 0)] + [(b, b)] * nd, mode="reflect")
    for a in range(nd):
        x = _fit_axis(x, 1 + a, (T[a] + q_max) * s)

    for a in range(nd - 1, 0, -1):
        ax = 1 + a
        w = _axis_windows(x, ax, T[a], s, csz)
        x = _contract_windows(w, rl[a], ax, accum=accum)

    w0 = _axis_windows(x, 1, T[0], s, csz)
    fine = _contract_windows(w0, rl[0], 1, merge=False, accum=accum)
    f_trail = fine.shape[3:]
    prod_f = int(np.prod(f_trail)) if f_trail else 1
    fine = fine.reshape(s_b, T[0], fsz, prod_f)

    xi = xi0.reshape(s_b, T[0], fsz, prod_f)
    if d0.ndim == 2:
        fine = fine + jnp.einsum("stjp,fj->stfp", xi, d0,
                                 preferred_element_type=accum)
    else:
        fine = fine + jnp.einsum("stjp,tfj->stfp", xi, d0,
                                 preferred_element_type=accum)
    # round to the storage dtype between levels: bit-identical to what the
    # per-level routes would have written to (and re-read from) HBM
    return fine.reshape((s_b, T[0] * fsz) + f_trail).astype(storage)


def _apply_levels(meta, field: Array, xi0s, r_all, d0s) -> Array:
    """The whole covered prefix as plain jnp in the natural layout — the
    reference the kernel is tested against and the learned-θ backward
    replays."""
    (csz, fsz, boundary, b, levels, s_b, interpret, accum_name) = meta
    accum = jnp.dtype(accum_name)
    storage = field.dtype
    x = field
    for lvl, (T, _) in enumerate(levels):
        x = _level_body(x, xi0s[lvl], r_all[lvl], d0s[lvl], T=T, csz=csz,
                        fsz=fsz, boundary=boundary, b=b, accum=accum,
                        storage=storage)
    T_last = levels[-1][0]
    prod_f = int(np.prod([t * fsz for t in T_last[1:]])) or 1
    return x.reshape(x.shape[0], T_last[0] * fsz, prod_f)


def pyramid_layout(U) -> tuple:
    """Position-dim order of the pyramid tile: shortest axis outermost,
    longest on the lanes."""
    return tuple(sorted(range(len(U)), key=lambda a: U[a]))


def pyramid_geometry(levels, *, s: int, fsz: int) -> dict:
    """Polyphase extents of a covered prefix (``planes.py``): positions
    ``U_a = ceil(N_a / s)`` of the first coarse level stay fixed while the
    phase count of every axis doubles per level (``s·2^l`` phases, i.e.
    ``2^l`` family rows, before level ``l``).

    ``pos`` are the tile's position dims in ``layout`` order. On a chart
    of three or more axes the two innermost merge into one (row-major):
    as separate sublane/lane dims, short planes such as a dust chart's
    8×8 would pad 16-fold to (8, 128). ``axes[a] = (dim, unit, period)``
    locates axis ``a`` for ``planes.window``."""
    T0, shape0 = levels[0]
    nd = len(T0)
    U = tuple(-(-n // s) for n in shape0)
    layout = pyramid_layout(U)
    pos = [U[a] for a in layout]
    axes = [None] * nd
    for i, a in enumerate(layout):
        axes[a] = (nd + 1 + i, 1, None)
    if nd >= 3:
        outer, inner = layout[-2], layout[-1]
        axes[outer] = (nd + nd - 1, U[inner], None)
        axes[inner] = (nd + nd - 1, 1, U[inner])
        pos = pos[:-2] + [U[outer] * U[inner]]
    return dict(nd=nd, U=U, layout=layout, pos=tuple(pos), axes=axes,
                Q=[2 ** lvl for lvl in range(len(levels))],
                P_out=s * 2 ** len(levels))


def sample_slab(s_b: int, n_s: int, itemsize: int) -> int:
    """Legal sample block of a 1-D pyramid tile, whose samples ride the
    sublanes: all samples, or a multiple of the sublane tile."""
    sub = 8 * max(1, 4 // itemsize)
    if s_b >= n_s or n_s <= sub:
        return n_s
    s_b = -(-s_b // sub) * sub
    return n_s if s_b >= n_s else s_b


def _tile_shape(geo, phases, batch) -> tuple:
    return tuple(phases) + (batch,) + geo["pos"]


def _poly_coef_shape(geo, a: int, q: int) -> tuple:
    """Broadcast shape of axis ``a``'s coefficient planes in the tile:
    ``q`` family rows, the axis's position dim at full extent."""
    nd = geo["nd"]
    shape = [1] * (nd + 1 + len(geo["pos"]))
    shape[a] = q
    dim = geo["axes"][a][0]
    shape[dim] = geo["pos"][dim - nd - 1]
    return tuple(shape)


def _to_tile(x, geo):
    """Merge the innermost position dims of a ``[..., U in layout]``
    array into the tile's position dims (a row-major reshape)."""
    return x.reshape(x.shape[:geo["nd"] + 1] + geo["pos"])


def _from_tile(y, geo):
    U, layout = geo["U"], geo["layout"]
    return y.reshape(y.shape[:geo["nd"] + 1] + tuple(U[a] for a in layout))


def _pyramid_kernel(*refs, meta, geo):
    (csz, fsz, boundary, b, levels, s_b, interpret, accum_name) = meta
    nd, U = geo["nd"], geo["U"]
    s = fsz // 2
    it = iter(refs)
    field_ref = next(it)
    per_level = [(next(it), [next(it) for _ in range(nd)], next(it))
                 for _ in levels]
    out_ref = next(it)
    storage = out_ref.dtype
    off = b if boundary == "reflect" else 0
    fill = "reflect" if boundary == "reflect" else "zero"
    x = field_ref[...].astype(jnp.float32)
    for lvl, (xi_ref, r_refs, d_ref) in enumerate(per_level):
        ch = geo["charted"][lvl]
        for a in range(nd - 1, -1, -1):
            kw = {}
            if a == 0:
                kw = dict(coef_d=_coef_fn(d_ref, fsz, ch[0]),
                          xi=xi_ref[...].astype(jnp.float32))
            ud, unit, period = geo["axes"][a]
            x = axis_step(x, pd=a, ud=ud, s=s, csz=csz, fsz=fsz, off=off,
                          lout=U[a], fill=fill, unit=unit, period=period,
                          coef_r=_coef_fn(r_refs[a], csz, ch[a]), **kw)
        # round to the storage dtype between levels: bit-identical to what
        # the per-level routes write to (and re-read from) HBM
        x = x.astype(storage).astype(jnp.float32)
    out_ref[...] = x.astype(storage)


def _tile_spec(name: str, shape, nd: int, s_b: int, dtype) -> OperandSpec:
    """Sample-slab operand: the grid runs over sample blocks only."""
    npos = len(shape) - nd - 1
    im = IndexMap("(" + "0, " * nd + "s" + ", 0" * npos + ")",
                  lambda i, _n=nd, _p=npos: (0,) * _n + (i,) + (0,) * _p)
    blk = tuple(shape[:nd]) + (s_b,) + tuple(shape[nd + 1:])
    return OperandSpec(name, blk, im, tuple(shape), dtype)


def _resident_spec(name: str, shape, dtype) -> OperandSpec:
    """Fully VMEM-resident operand (coefficients): one block, zero map."""
    zeros = (0,) * len(shape)
    im = IndexMap("(" + ", ".join(["0"] * len(shape)) + ")",
                  lambda i, _z=zeros: _z)
    return OperandSpec(name, tuple(shape), im, tuple(shape), dtype)


def _coef_spec(name, rows, charted, geo, a, q):
    if not charted:
        return smem_scalars(name, rows)
    return _resident_spec(name, (rows,) + _poly_coef_shape(geo, a, q),
                          "float32")


def pyramid_launch_plan(*, levels, charted, sp: int, s_b: int, csz: int,
                        fsz: int, dtype, accum_dtype,
                        boundary: str = "reflect", b: int = 0
                        ) -> LaunchPlan:
    """Declarative launch geometry of one pyramid (multi-level) launch.

    One grid axis — sample slabs — over polyphase tiles (``planes.py``):
    the coarse field in ``s`` phases per axis, per covered level the
    operand bundle [ξ0 in that level's output phases, per-axis coefficient
    planes of R, coefficient planes of sqrt(D)0]; only the last level's
    fine field is an output (inter-level fields never touch HBM).
    """
    s = fsz // 2
    geo = pyramid_geometry(levels, s=s, fsz=fsz)
    nd, U, layout = geo["nd"], geo["U"], geo["layout"]
    dtype = jnp.dtype(dtype).name
    inputs = [_tile_spec("field", _tile_shape(geo, (s,) * nd, sp), nd, s_b,
                         dtype)]
    for lvl, q in enumerate(geo["Q"]):
        inputs.append(_tile_spec(
            f"xi{lvl}", _tile_shape(geo, (q * fsz,) * nd, sp), nd, s_b,
            dtype))
        for a in range(nd):
            inputs.append(_coef_spec(f"r{lvl}_{a}", fsz * csz,
                                     charted[lvl][a], geo, a, q))
        inputs.append(_coef_spec(f"d{lvl}", fsz * fsz, charted[lvl][0], geo,
                                 0, q))
    out = _tile_spec("fine", _tile_shape(geo, (geo["P_out"],) * nd, sp), nd,
                     s_b, dtype)
    return LaunchPlan(
        kernel="refine_pyramid", grid=(sp // s_b,),
        inputs=tuple(inputs), outputs=(out,),
        accum_dtype=jnp.dtype(accum_dtype).name,
        params=dict(kind="fwd", levels=tuple(levels), s_b=s_b, fsz=fsz,
                    csz=csz, boundary=boundary, b=b,
                    charted=tuple(tuple(c) for c in charted),
                    n_levels=len(levels), U=U, layout=layout),
    )


def plan_kernel(plan: LaunchPlan):
    """The kernel body a pyramid plan launches."""
    p = plan.params
    meta = (p["csz"], p["fsz"], p["boundary"], p["b"], p["levels"],
            p["s_b"], False, plan.accum_dtype)
    geo = dict(pyramid_geometry(p["levels"], s=p["fsz"] // 2, fsz=p["fsz"]),
               charted=[list(c) for c in p["charted"]])
    return functools.partial(_pyramid_kernel, meta=meta, geo=geo)


def _poly_xi(xi0, T, q: int, geo, fsz: int):
    """Natural ``(S, T_0·fsz, prod_f)`` excitation -> the level's output
    polyphase tile ``[q·fsz]*d + [S] + pos`` (family ``t = v·q + w`` at
    row ``w``, position ``v``; missing families zero)."""
    U, layout = geo["U"], geo["layout"]
    nd = len(T)
    n_s = xi0.shape[0]
    x = xi0.reshape((n_s,) + sum(((t, fsz) for t in T), ()))
    pads = [(0, 0)] + [p for a in range(nd)
                       for p in ((0, q * U[a] - T[a]), (0, 0))]
    x = jnp.pad(x, pads)
    x = x.reshape((n_s,) + sum(((U[a], q, fsz) for a in range(nd)), ()))
    perm = sum(([2 + 3 * a, 3 + 3 * a] for a in range(nd)), []) + [0] \
        + [1 + 3 * a for a in layout]
    x = x.transpose(perm)
    return x.reshape((q * fsz,) * nd + (n_s,) + geo["pos"])


def _poly_coef(m, geo, a: int, q: int, *, charted: bool):
    """Per-axis factor -> polyphase coefficient planes (family
    ``t = v·q + w`` at (row w, position v); missing families zero). On a
    merged position dim each coefficient repeats over the other axis."""
    if not charted:
        return m.reshape(-1).astype(jnp.float32)
    U = geo["U"]
    u = U[a]
    t = m.shape[0]
    cp = jnp.pad(m.reshape(t, -1), [(0, q * u - t), (0, 0)])
    cp = cp.reshape(u, q, -1).transpose(2, 1, 0)
    _, unit, period = geo["axes"][a]
    if unit > 1:
        cp = jnp.repeat(cp, unit, axis=-1)
    if period is not None:
        cp = jnp.tile(cp, (1, 1, geo["pos"][-1] // period))
    return cp.reshape((cp.shape[0],) + _poly_coef_shape(geo, a, q)
                      ).astype(jnp.float32)


def _pyramid_impl(meta, field: Array, xi0s, r_all, d0s) -> Array:
    (csz, fsz, boundary, b, levels, s_b, interpret, accum_name) = meta
    if interpret == "reference":
        # production off-TPU backend (dispatch.select_backend): the same
        # fused multi-level chain as ONE jnp jit region — no Pallas
        # interpret emulation, which is slower than plain jnp on CPU
        return _pyramid_ref(meta, field, xi0s, r_all, d0s)
    s = fsz // 2
    charted = [tuple(r.ndim == 3 for r in rl) for rl in r_all]
    plan = pyramid_launch_plan(levels=levels, charted=charted,
                               sp=field.shape[0], s_b=s_b, csz=csz, fsz=fsz,
                               dtype=field.dtype, accum_dtype=accum_name,
                               boundary=boundary, b=b)
    geo = dict(pyramid_geometry(levels, s=s, fsz=fsz), charted=charted)
    nd, U, layout = geo["nd"], geo["U"], geo["layout"]
    dt = field.dtype
    operands = [_to_tile(to_planes(field, U, s=s, lead=1, layout=layout),
                         geo)]
    for lvl, (T, _) in enumerate(levels):
        q = geo["Q"][lvl]
        operands.append(_poly_xi(xi0s[lvl].astype(dt), T, q, geo, fsz))
        for a in range(nd):
            operands.append(_poly_coef(r_all[lvl][a], geo, a, q,
                                       charted=charted[lvl][a]))
        operands.append(_poly_coef(d0s[lvl], geo, 0, q,
                                   charted=charted[lvl][0]))
    out = run_plan(plan_kernel(plan), plan, operands, interpret=interpret)
    out = from_planes(_from_tile(out, geo), layout=layout)
    T_last = levels[-1][0]
    out = out[(slice(None),) + tuple(slice(0, t * fsz) for t in T_last)]
    return out.reshape(out.shape[0], T_last[0] * fsz, -1)


def _pyramid_ref(meta, field: Array, xi0s, r_all, d0s) -> Array:
    """jnp replay of the chain over the full sample batch (the CPU
    backend and the learned-θ backward)."""
    meta_full = meta[:5] + (field.shape[0],) + meta[6:]
    return _apply_levels(meta_full, field, xi0s, r_all, d0s)


# -- custom VJP -----------------------------------------------------------------
@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _pyramid_core(meta, field, xi0s, r_all, d0s):
    return _pyramid_impl(meta, field, xi0s, r_all, d0s)


def _core_fwd(meta, field, xi0s, r_all, d0s):
    vals = (field.value,
            tuple(x.value for x in xi0s),
            tuple(tuple(r.value for r in rl) for rl in r_all),
            tuple(d.value for d in d0s))
    out = _pyramid_impl(meta, *vals)
    mats_pert = (any(r.perturbed for rl in r_all for r in rl)
                 or any(d.perturbed for d in d0s))
    return out, vals + (() if mats_pert else None,)


def _core_bwd(meta, res, g):
    field, xi0s, r_all, d0s, mats_pert = res
    zeros_r = tuple(tuple(jnp.zeros_like(r) for r in rl) for rl in r_all)
    zeros_d = tuple(jnp.zeros_like(d) for d in d0s)
    if isinstance(g, SymbolicZero):
        return (jnp.zeros_like(field),
                tuple(jnp.zeros_like(x) for x in xi0s), zeros_r, zeros_d)
    if mats_pert is not None:
        # learning θ: parameter-sized window einsums via the reference VJP
        # (§9 gating — never the fixed-matrix inference path)
        _, vjp = jax.vjp(
            lambda f, x, r, d: _pyramid_ref(meta, f, x, r, d),
            field, xi0s, r_all, d0s)
        return vjp(g)
    if meta[6] == "reference":
        # the CPU backend: the same chain as jnp, transposed by autodiff
        _, vjp = jax.vjp(
            lambda f, x: _pyramid_ref(meta, f, x, r_all, d0s), field, xi0s)
        df, dxi = vjp(g)
        return df, dxi, zeros_r, zeros_d
    df, dxi = _pyramid_adjoint(meta, g, field, r_all, d0s)
    return (df, tuple(d.astype(x.dtype) for d, x in zip(dxi, xi0s)),
            zeros_r, zeros_d)


def _pyramid_adjoint(meta, g: Array, field: Array, r_all, d0s):
    """Transpose of the covered chain at fixed matrices (it is linear in
    (field, ξ) there): each level's 1-D adjoint kernels
    (``nd_fused.axes_adjoint``), last level first, with the transpose of
    the level's reflect pad / tile fit in between. No intermediate field
    of the forward is needed."""
    (csz, fsz, boundary, b, levels, s_b, interpret, accum_name) = meta
    s = fsz // 2
    q_max = (csz - 1) // s
    sp = g.shape[0]
    dxis = [None] * len(levels)
    cur = g.astype(field.dtype)
    for lvl in range(len(levels) - 1, -1, -1):
        T, coarse = levels[lvl]
        nd = len(T)
        lens = tuple((T[a] + q_max) * s for a in range(nd))
        rl = r_all[lvl]
        dpad, dxis[lvl] = axes_adjoint(
            cur.reshape(sp, T[0] * fsz, -1), rl[0], d0s[lvl], rl[1:], T=T,
            csz=csz, fsz=fsz, coarse_lens=lens,
            charted=tuple(r.ndim == 3 for r in rl), interpret=interpret,
            accum_dtype=accum_name)

        def prep(x, lens=lens, nd=nd):
            if boundary == "reflect":
                x = jnp.pad(x, [(0, 0)] + [(b, b)] * nd, mode="reflect")
            for a in range(nd):
                x = _fit_axis(x, 1 + a, lens[a])
            return x

        _, pull = jax.vjp(prep, jnp.zeros((sp,) + tuple(coarse),
                                          field.dtype))
        (cur,) = pull(dpad.astype(field.dtype))
    return cur, tuple(dxis)


_pyramid_core.defvjp(_core_fwd, _core_bwd, symbolic_zeros=True)


# -- public wrapper -------------------------------------------------------------
def refine_pyramid(field: Array, xis, mats, geoms, *,
                   interpret,
                   sample_block: int | None = None,
                   sample_axis: bool = False,
                   accum_dtype: str = "float32") -> Array:
    """Run the covered level prefix as ONE Pallas launch.

    ``interpret``: False compiles the kernel (TPU), True runs it in Pallas
    interpret mode, ``"reference"`` runs the same chain as plain jnp (the
    CPU production backend) — the caller decides, from
    ``dispatch.select_backend()``.

    field: (*geoms[0].coarse_shape) (or (S, ...) with ``sample_axis``);
    xis[l]: (prod(T_l), n_fsz^d) per covered level (sample dim leading when
    ``sample_axis``); mats[l] = (rs_l, ds_l) per-axis factors (1-D charts:
    single-entry lists). geoms must be consecutive:
    ``geoms[l+1].coarse_shape == geoms[l].fine_shape``.
    """
    from .dispatch import autotune_pyramid  # lazy: avoid import cycle

    g0 = geoms[0]
    nd = len(g0.coarse_shape)
    fsz, csz, b, boundary = g0.n_fsz, g0.n_csz, g0.b, g0.boundary
    accum = jnp.dtype(accum_dtype)
    for lo, hi in zip(geoms[:-1], geoms[1:]):
        if tuple(hi.coarse_shape) != tuple(lo.fine_shape):
            raise ValueError("pyramid levels must be consecutive")

    if not sample_axis:
        field = field[None]
        xis = [x[None] for x in xis]
    n_s = field.shape[0]
    storage = field.dtype

    if interpret == "reference":
        # one jnp jit region: VMEM sample blocking (and the padding to a
        # block multiple) is meaningless here — run the whole batch
        s_b = n_s
    else:
        s_b = sample_block
        if s_b is None:
            tuned = autotune_pyramid(
                geoms, samples=n_s, itemsize=jnp.dtype(storage).itemsize)
            s_b = tuned[1] if tuned is not None else 1
    s_b = max(1, min(s_b, n_s))
    if nd == 1 and interpret != "reference":
        # 1-D tiles carry the sample block on the sublanes
        s_b = sample_slab(s_b, n_s, jnp.dtype(storage).itemsize)

    xi0s, r_all, d0s, levels = [], [], [], []
    for lvl, geom in enumerate(geoms):
        rs, ds = mats[lvl]
        T = tuple(geom.T)
        xi0s.append(prepare_xi0(xis[lvl], ds, T, fsz, accum=accum,
                                storage=storage))
        r_all.append(tuple(jnp.asarray(r, storage) for r in rs))
        d0s.append(jnp.asarray(ds[0], storage))
        levels.append((T, tuple(geom.coarse_shape)))

    nbs = -(-n_s // s_b)
    pad_s = nbs * s_b - n_s
    if pad_s > 0:
        field = jnp.pad(field, [(0, pad_s)] + [(0, 0)] * nd)
        xi0s = [jnp.pad(x, [(0, pad_s), (0, 0), (0, 0)]) for x in xi0s]

    meta = (csz, fsz, boundary, b, tuple(levels), s_b, interpret,
            accum_dtype)
    out = _pyramid_core(meta, field.astype(storage), tuple(xi0s),
                        tuple(r_all), tuple(d0s))
    out = out[:n_s]
    fine_shape = tuple(geoms[-1].fine_shape)
    out = out.reshape((n_s,) + fine_shape)
    return out if sample_axis else out[0]
