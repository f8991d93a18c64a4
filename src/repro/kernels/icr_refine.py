"""Pallas TPU kernels for the ICR refinement hot-spot (paper Eq. 11–12).

Why a kernel: one refinement level reads the coarse field once, builds
overlapping ``n_csz``-windows, contracts them with the stencil(s) and adds the
correlated correction ``sqrt(D) ξ``. Done naively in XLA this materializes the
(T, n_csz) window tensor in HBM (n_csz-fold read amplification) and runs the
noise add as a separate elementwise pass. The fused kernel builds the
windows in VMEM and adds the noise in the same pass.

Layout (DESIGN.md §3, ``planes.py``): families ride the 128 lanes. Outside
the kernel the halo-padded coarse field ``(B, L)`` is split into ``s =
n_fsz//2`` phase planes ``(s, B, L/s)``, so window column ``k`` of family
``t`` is ``plane[k % s][t + k // s]`` — a lane-offset slice, no gather. ξ
and the output are ``(n_fsz, B, T)`` planes (child ``f`` of every family in
plane ``f``), and the refinement matrices are coefficient planes
``(n_fsz·n_csz, T)`` (charted: one column per family) or ``(n_fsz·n_csz,
1)`` (stationary: broadcast scalars). The contraction is a float32
multiply-accumulate of whole planes on the VPU: a refinement stencil is
(4×5) at most, far below an MXU tile, and the VPU keeps full f32
precision. Batch rows (samples, or the orthogonal lines of an N-D axis
pass) ride the sublanes.

Tiling: a level whose planes fit one block runs as a single block over
the whole plane (any width). Otherwise blocks of ``b_f`` families (a
multiple of 128) tile the lanes and the window overhang (``q_max =
(n_csz-1)//s`` positions) comes from a second, shifted view of the same
plane array — the next block — so every BlockSpec stays a plain Blocked
map.

``noise=False`` (DESIGN.md §10): every N-D per-axis pass except the final
one injects no excitation, so those launches drop the ξ and sqrt(D)
operands entirely (forward skips the read and the add, the adjoint skips
``dxi``).

Dtype policy (DESIGN.md §11): the storage dtype is the dtype of the
operands — bf16 arrays are read and written as bf16 — and every
contraction accumulates in float32 (``accum_dtype``), which is also the
only arithmetic the v5e VPU has.

Adjoints (DESIGN.md §9): every entry point carries a ``jax.custom_vjp``
whose backward runs a hand-written adjoint kernel. The transpose of the
window build is an overlap-add: coarse position ``p`` of plane ``r``
receives ``Rᵀg`` from the families ``p - k//s`` with ``k % s == r`` — the
same lane-offset slices run in reverse, with the *previous* block as the
halo view. Matrix cotangents (∂R, ∂sqrtD) are parameter-sized reductions,
computed as jnp einsums outside the kernel and only when the matrices are
perturbed (``symbolic_zeros``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.custom_derivatives import SymbolicZero

from .launch import (
    IndexMap,
    LaunchPlan,
    OperandSpec,
    pad_to,
    run_plan,
    smem_scalars,
)
from .planes import axis_step
from .ref import windows_1d

Array = jnp.ndarray

F32 = jnp.float32


def sublane_tile(itemsize: int) -> int:
    """Rows per (sublane) tile of a dtype: 8 for 32-bit, 16 for 16-bit."""
    return 8 * max(1, 4 // itemsize)


def _fwd_kernel(*refs, s: int, csz: int, fsz: int, tb: int, halo: bool,
                noise: bool, charted: bool):
    it = iter(refs)
    c_ref = next(it)
    h_ref = next(it) if halo else None
    xi_ref = next(it) if noise else None
    r_ref = next(it)
    d_ref = next(it) if noise else None
    o_ref = next(it)
    x = c_ref[...].astype(F32)                        # (s, b_b, Bl)
    if halo:
        x = jnp.concatenate([x, h_ref[...].astype(F32)], axis=-1)
    y = axis_step(
        x, pd=0, ud=2, s=s, csz=csz, fsz=fsz, off=0, lout=tb,
        coef_r=_coef_fn(r_ref, csz, charted=charted),
        coef_d=_coef_fn(d_ref, fsz, charted=charted) if noise else None,
        xi=xi_ref[...].astype(F32) if noise else None)
    o_ref[...] = y.astype(o_ref.dtype)


def _adj_kernel(*refs, s: int, csz: int, fsz: int, tb: int, halo: bool,
                noise: bool, charted: bool):
    it = iter(refs)
    g_ref = next(it)
    gh_ref = next(it) if halo else None
    r_ref = next(it)
    rh_ref = next(it) if (halo and charted) else None
    d_ref = next(it) if noise else None
    dc_ref = next(it)
    dxi_ref = next(it) if noise else None
    q_max = (csz - 1) // s
    g = g_ref[...].astype(F32)                        # (fsz, b_b, tb)
    ext = g
    if halo:
        ext = jnp.concatenate([gh_ref[...].astype(F32), g], axis=-1)
    if charted:
        r = r_ref[...].astype(F32)
        if halo:
            r = jnp.concatenate([rh_ref[...].astype(F32), r], axis=-1)
        coef = lambda f, k: r[f * csz + k: f * csz + k + 1]  # noqa: E731
    else:
        coef = lambda f, k: r_ref[f * csz + k]  # noqa: E731
    for rr in range(s):
        acc = None
        for k in range(rr, csz, s):
            qq = k // s
            dw = coef(0, k) * ext[0]
            for f in range(1, fsz):
                dw = dw + coef(f, k) * ext[f]
            if halo:
                piece = dw[:, tb - qq: 2 * tb - qq]
            else:
                parts = [dw]
                if qq:
                    parts.insert(0, jnp.zeros(dw.shape[:-1] + (qq,), F32))
                if q_max - qq:
                    parts.append(jnp.zeros(dw.shape[:-1] + (q_max - qq,),
                                           F32))
                piece = jnp.concatenate(parts, axis=-1) \
                    if len(parts) > 1 else dw
            acc = piece if acc is None else acc + piece
        dc_ref[rr] = acc.astype(dc_ref.dtype)
    if noise:
        dcoef = _coef_fn(d_ref, fsz, charted=charted)
        for j in range(fsz):
            acc = dcoef(0, j) * g[0]
            for f in range(1, fsz):
                acc = acc + dcoef(f, j) * g[f]
            dxi_ref[j] = acc.astype(dxi_ref.dtype)


def halo_floor(n_csz: int, n_fsz: int) -> int:
    """Window overhang ``q_max`` in plane positions: a block must be at
    least this wide for its one-block halo view to cover it (the single
    source of truth for this clamp; dispatch's autotuners use it too)."""
    s = max(1, n_fsz // 2)
    return (n_csz - 1) // s


def block_geometry(*, batch: int, t: int, block_families: int,
                   batch_block: int, itemsize: int):
    """Legal ``(b_f, nblk, halo, b_b, nbb)`` for a requested tile.

    Lanes: a request covering every family is one block over the whole
    plane (any width, no halo view); anything smaller rounds up to a
    multiple of 128 lanes. Sublanes: the batch block is the whole batch
    or a multiple of the dtype's sublane tile.
    """
    if block_families >= t:
        b_f = t
    else:
        b_f = -(-max(block_families, 1) // 128) * 128
        b_f = t if b_f >= t else b_f
    nblk = -(-t // b_f)
    sub = sublane_tile(itemsize)
    if batch_block >= batch or batch <= sub:
        b_b = batch
    else:
        b_b = -(-max(batch_block, 1) // sub) * sub
        b_b = batch if b_b >= batch else b_b
    nbb = -(-batch // b_b)
    return b_f, nblk, nblk > 1, b_b, nbb


# Named index maps (DESIGN.md §14): the grid is (family block i, batch
# block b) with batch innermost, so blocked coefficient planes stay VMEM-
# resident across the whole batch. The names appear verbatim in verifier
# findings and plan descriptions.
_IM_0BI = IndexMap("(0, b, i)", lambda i, b: (0, b, i))
_IM_0BI1 = IndexMap("(0, b, i + 1)", lambda i, b: (0, b, i + 1))
_IM_0I = IndexMap("(0, i)", lambda i, b: (0, i))
_IM_0I1 = IndexMap("(0, i + 1)", lambda i, b: (0, i + 1))


def _coef_spec(name, rows, *, charted, b_f, length, dtype, im):
    if charted:
        return OperandSpec(name, (rows, b_f), im, (rows, length), dtype)
    return smem_scalars(name, rows)


def _coef_fn(ref, width, *, charted):
    """Coefficient accessor: a (1, lanes) row of a charted coefficient
    plane, or an SMEM scalar of a stationary stencil."""
    if charted:
        m = ref[...].astype(F32)
        return lambda f, k: m[f * width + k: f * width + k + 1]
    return lambda f, k: ref[f * width + k]


def refine_fwd_launch_plan(*, batch: int, t: int, coarse_len: int,
                           n_csz: int, n_fsz: int, block_families: int,
                           batch_block: int, dtype, accum_dtype,
                           charted: bool, noise: bool = True) -> LaunchPlan:
    """Declarative launch geometry of one forward 1-D refinement launch.

    This is the single source of truth: the impls relayout their operands
    to the plan's array shapes and :func:`run_plan` builds the
    pallas_call from it, and ``dispatch.level_launch_plans`` exports the
    identical record to ``analysis.kernel_verify``.
    """
    s = n_fsz // 2
    q_max = (n_csz - 1) // s
    dtype = jnp.dtype(dtype)
    b_f, nblk, halo, b_b, nbb = block_geometry(
        batch=batch, t=t, block_families=block_families,
        batch_block=batch_block, itemsize=dtype.itemsize)
    dtype = dtype.name
    bp = nbb * b_b
    tp = nblk * b_f
    if halo:
        lp = (nblk + 1) * b_f
        inputs = [
            OperandSpec("coarse", (s, b_b, b_f), _IM_0BI, (s, bp, lp), dtype,
                        overhang=((0, 0), (0, 0), (0, q_max))),
            OperandSpec("coarse_halo", (s, b_b, b_f), _IM_0BI1, (s, bp, lp),
                        dtype, halo_of="coarse"),
        ]
    else:
        lp = t + q_max
        inputs = [OperandSpec("coarse", (s, b_b, lp), _IM_0BI, (s, bp, lp),
                              dtype)]
    if noise:
        inputs.append(OperandSpec("xi", (n_fsz, b_b, b_f), _IM_0BI,
                                  (n_fsz, bp, tp), dtype))
    inputs.append(_coef_spec("r", n_fsz * n_csz, charted=charted, b_f=b_f,
                             length=tp, dtype=dtype, im=_IM_0I))
    if noise:
        inputs.append(_coef_spec("d", n_fsz * n_fsz, charted=charted,
                                 b_f=b_f, length=tp, dtype=dtype,
                                 im=_IM_0I))
    out = OperandSpec("fine", (n_fsz, b_b, b_f), _IM_0BI, (n_fsz, bp, tp),
                      dtype)
    name = ("charted" if charted else "stationary") + ("" if noise else "_nn")
    return LaunchPlan(
        kernel=f"refine_{name}_fwd", grid=(nblk, nbb),
        inputs=tuple(inputs), outputs=(out,),
        accum_dtype=jnp.dtype(accum_dtype).name,
        params=dict(kind="fwd", charted=charted, noise=noise, t=t,
                    batch=batch, coarse_len=coarse_len, n_csz=n_csz,
                    n_fsz=n_fsz, s=s, b_f=b_f, b_b=b_b, nblk=nblk, nbb=nbb,
                    halo=halo, lp=lp),
    )


def _coef_planes(m, t: int, length: int, *, charted: bool, dtype,
                 front: int = 0):
    """(T, f, c) per-family matrices -> coefficient planes ``(f·c,
    length)``; (f, c) shared stencils -> the ``f·c`` float32 scalars."""
    if not charted:
        return m.reshape(-1).astype(F32)
    rows = [m[:, f, k] for f in range(m.shape[1]) for k in range(m.shape[2])]
    cp = jnp.stack(rows).astype(dtype)
    return jnp.pad(cp, [(0, 0), (front, length - front - t)])


def split_phases(x, s: int):
    """(..., n) -> (s, ..., n/s) with ``out[r, ..., m] = x[..., m·s + r]``:
    strided slices, so no array with a tiny minor dimension ever exists
    in HBM (XLA would pad it to 128 lanes)."""
    with jax.named_scope("icr.relayout"):
        return jnp.stack([x[..., r::s] for r in range(s)])


def interleave(planes, length: int):
    """Inverse of ``split_phases``: (f, ..., n) -> (..., n·f) cropped to
    ``length``. Strided updates of one output buffer: the TPU compiler
    handles them at any size, where a stack-and-reshape or an
    interior-padded sum of billion-element planes does not finish
    compiling."""
    f = planes.shape[0]
    with jax.named_scope("icr.interleave"):
        out = jnp.zeros(planes.shape[1:-1] + (planes.shape[-1] * f,),
                        planes.dtype)
        for i in range(f):
            out = out.at[..., i::f].set(planes[i])
        return out[..., :length]


def stack_minor(planes):
    """(f, ..., n) -> (..., n, f) by per-slot updates (see
    ``interleave``)."""
    f = planes.shape[0]
    with jax.named_scope("icr.relayout"):
        out = jnp.zeros(planes.shape[1:] + (f,), planes.dtype)
        for i in range(f):
            out = out.at[..., i].set(planes[i])
        return out


def _coarse_planes(coarse, plan: LaunchPlan):
    """(B, L) halo-padded coarse -> the plan's (s, Bp, Lp) phase planes."""
    p = plan.params
    s, lp = p["s"], p["lp"]
    bp = plan.operand("coarse").array_shape[1]
    c = pad_to(coarse[:, : s * lp], (bp, s * lp))
    return split_phases(c, s)


def _fine_planes(x, shape, *, front: int = 0):
    """(B, T, fsz) natural -> (fsz, Bp, Tp) planes, ``front`` zero
    families prepended."""
    x = jnp.stack([x[..., f] for f in range(x.shape[-1])])
    if front:
        x = jnp.pad(x, [(0, 0), (0, 0), (front, 0)])
    return pad_to(x, shape)


def plan_kernel(plan: LaunchPlan):
    """The kernel body a 1-D forward or adjoint plan launches."""
    p = plan.params
    body = _fwd_kernel if p["kind"] == "fwd" else _adj_kernel
    return functools.partial(
        body, s=p["s"], csz=p["n_csz"], fsz=p["n_fsz"], tb=p["b_f"],
        halo=p["halo"], noise=p["noise"], charted=p["charted"])


def _run_fwd(plan: LaunchPlan, coarse, xi, r, d, interpret) -> Array:
    p = plan.params
    kern = plan_kernel(plan)
    dtype = jnp.dtype(plan.operand("fine").dtype)
    tp = plan.operand("fine").array_shape[2]
    cp = _coarse_planes(coarse.astype(dtype), plan)
    operands = [cp, cp] if p["halo"] else [cp]
    if p["noise"]:
        operands.append(_fine_planes(xi.astype(dtype),
                                     plan.operand("xi").array_shape))
    operands.append(_coef_planes(r, p["t"], tp, charted=p["charted"],
                                 dtype=dtype))
    if p["noise"]:
        operands.append(_coef_planes(d, p["t"], tp, charted=p["charted"],
                                     dtype=dtype))
    out = run_plan(kern, plan, operands, interpret=interpret)
    return interleave(out[:, : p["batch"]], p["t"] * p["n_fsz"])


def _refine_fwd_impl(charted, meta, coarse: Array, xi: Array, r: Array,
                     d: Array) -> Array:
    n_csz, n_fsz, block_families, batch_block, interpret, accum_name = meta
    plan = refine_fwd_launch_plan(
        batch=coarse.shape[0], t=xi.shape[-2], coarse_len=coarse.shape[-1],
        n_csz=n_csz, n_fsz=n_fsz, block_families=block_families,
        batch_block=batch_block, dtype=coarse.dtype, accum_dtype=accum_name,
        charted=charted)
    return _run_fwd(plan, coarse, xi, r, d, interpret)


def _refine_nn_impl(charted, meta, coarse: Array, r: Array) -> Array:
    t, n_csz, n_fsz, block_families, batch_block, interpret, accum_name = meta
    plan = refine_fwd_launch_plan(
        batch=coarse.shape[0], t=t, coarse_len=coarse.shape[-1],
        n_csz=n_csz, n_fsz=n_fsz, block_families=block_families,
        batch_block=batch_block, dtype=coarse.dtype, accum_dtype=accum_name,
        charted=charted, noise=False)
    return _run_fwd(plan, coarse, None, r, None, interpret)


_refine_stationary_impl = functools.partial(_refine_fwd_impl, False)
_refine_charted_impl = functools.partial(_refine_fwd_impl, True)
_refine_stationary_nn_impl = functools.partial(_refine_nn_impl, False)
_refine_charted_nn_impl = functools.partial(_refine_nn_impl, True)


# -- adjoint launches -----------------------------------------------------------
def refine_adjoint_launch_plan(*, batch: int, t: int, coarse_len: int,
                               n_csz: int, n_fsz: int, block_families: int,
                               batch_block: int, dtype, accum_dtype,
                               charted: bool, noise: bool = True
                               ) -> LaunchPlan:
    """Declarative launch geometry of one adjoint (transpose) launch.

    The adjoint flips the halo direction: coarse block ``i`` receives
    window cotangents from its own families plus the *previous* block's
    tail. Front-padding the cotangent planes by one zero block lets the
    halo view use index map ``(0, b, i)`` while the main view uses
    ``(0, b, i + 1)``; the grid runs over as many blocks as the coarse
    planes need (``t + q_max`` positions). Charted coefficient planes
    ride along twice exactly like the cotangent.
    """
    s = n_fsz // 2
    q_max = (n_csz - 1) // s
    dtype = jnp.dtype(dtype)
    b_f, nblk, halo, b_b, nbb = block_geometry(
        batch=batch, t=t, block_families=block_families,
        batch_block=batch_block, itemsize=dtype.itemsize)
    dtype = dtype.name
    bp = nbb * b_b
    rows_r, rows_d = n_fsz * n_csz, n_fsz * n_fsz
    if halo:
        nblk_c = -(-(t + q_max) // b_f)
        gl = (nblk_c + 1) * b_f
        inputs = [
            OperandSpec("g", (n_fsz, b_b, b_f), _IM_0BI1, (n_fsz, bp, gl),
                        dtype, overhang=((0, 0), (0, 0), (q_max, 0))),
            OperandSpec("g_halo", (n_fsz, b_b, b_f), _IM_0BI,
                        (n_fsz, bp, gl), dtype, halo_of="g"),
        ]
        if charted:
            inputs.append(OperandSpec("r", (rows_r, b_f), _IM_0I1,
                                      (rows_r, gl), dtype,
                                      overhang=((0, 0), (q_max, 0))))
            inputs.append(OperandSpec("r_halo", (rows_r, b_f), _IM_0I,
                                      (rows_r, gl), dtype, halo_of="r"))
        else:
            inputs.append(_coef_spec("r", rows_r, charted=False, b_f=b_f,
                                     length=gl, dtype=dtype, im=_IM_0I))
        if noise:
            inputs.append(_coef_spec("d", rows_d, charted=charted, b_f=b_f,
                                     length=gl, dtype=dtype, im=_IM_0I1))
        dc_block, lc = (s, b_b, b_f), nblk_c * b_f
        dxi_shape = (n_fsz, bp, nblk_c * b_f)
        grid = (nblk_c, nbb)
    else:
        nblk_c = 1
        inputs = [OperandSpec("g", (n_fsz, b_b, t), _IM_0BI, (n_fsz, bp, t),
                              dtype)]
        inputs.append(_coef_spec("r", rows_r, charted=charted, b_f=t,
                                 length=t, dtype=dtype, im=_IM_0I))
        if noise:
            inputs.append(_coef_spec("d", rows_d, charted=charted, b_f=t,
                                     length=t, dtype=dtype, im=_IM_0I))
        dc_block, lc = (s, b_b, t + q_max), t + q_max
        dxi_shape = (n_fsz, bp, t)
        grid = (1, nbb)
    outputs = [OperandSpec("dcoarse", dc_block, _IM_0BI, (s, bp, lc), dtype)]
    if noise:
        outputs.append(OperandSpec("dxi", (n_fsz, b_b, b_f), _IM_0BI,
                                   dxi_shape, dtype))
    name = ("charted" if charted else "stationary") + ("" if noise else "_nn")
    return LaunchPlan(
        kernel=f"refine_{name}_adjoint", grid=grid,
        inputs=tuple(inputs), outputs=tuple(outputs),
        accum_dtype=jnp.dtype(accum_dtype).name,
        params=dict(kind="bwd", charted=charted, noise=noise, t=t,
                    batch=batch, coarse_len=coarse_len, n_csz=n_csz,
                    n_fsz=n_fsz, s=s, b_f=b_f, b_b=b_b, nblk=nblk_c,
                    nbb=nbb, halo=halo),
    )


def _run_adjoint(plan: LaunchPlan, g, r, d, interpret):
    p = plan.params
    batch, t, b_f, halo = p["batch"], p["t"], p["b_f"], p["halo"]
    kern = plan_kernel(plan)
    dtype = jnp.dtype(plan.operand("dcoarse").dtype)
    g_shape = plan.operand("g").array_shape
    front = b_f if halo else 0
    g = g.astype(dtype)
    gp = split_phases(g, p["n_fsz"])
    if front:
        gp = jnp.pad(gp, [(0, 0), (0, 0), (front, 0)])
    gp = pad_to(gp, g_shape)
    operands = [gp, gp] if halo else [gp]
    length = g_shape[2]
    rp = _coef_planes(r, t, length, charted=p["charted"], dtype=dtype,
                      front=front)
    operands += [rp, rp] if (halo and p["charted"]) else [rp]
    if p["noise"]:
        operands.append(_coef_planes(d, t, length, charted=p["charted"],
                                     dtype=dtype, front=front))
    out = run_plan(kern, plan, operands, interpret=interpret)
    dc = out[0] if p["noise"] else out
    dc = dc[:, :batch]
    dc = interleave(dc, min(p["coarse_len"], dc.shape[-1] * p["s"]))
    dc = pad_to(dc, (batch, p["coarse_len"]))
    if p["noise"]:
        dxi = out[1][:, :batch, :t]
        return dc, stack_minor(dxi)
    return dc


@functools.partial(
    jax.jit,
    static_argnames=("coarse_len", "n_csz", "n_fsz", "block_families",
                     "batch_block", "interpret", "noise", "accum_dtype"),
)
def refine_stationary_adjoint_pallas(g: Array, r: Array, d: Array = None, *,
                                     coarse_len: int, n_csz: int, n_fsz: int,
                                     block_families: int = 256,
                                     batch_block: int = 1,
                                     interpret: bool = False,
                                     noise: bool = True,
                                     accum_dtype: str = "float32"):
    """Fused adjoint of ``refine_stationary_pallas`` in (coarse, xi).

    g: (B, T*n_fsz) fine cotangent -> (dcoarse: (B, coarse_len),
    dxi: (B, T, n_fsz)). One launch computes both: the overlap-add of the
    window cotangents ``g R`` and the noise transpose ``g D`` share the
    cotangent read. With ``noise=False`` the launch computes (and
    returns) only ``dcoarse``.
    """
    batch = g.shape[0]
    plan = refine_adjoint_launch_plan(
        batch=batch, t=g.shape[-1] // n_fsz, coarse_len=coarse_len,
        n_csz=n_csz, n_fsz=n_fsz, block_families=block_families,
        batch_block=batch_block, dtype=g.dtype, accum_dtype=accum_dtype,
        charted=False, noise=noise)
    return _run_adjoint(plan, g, r, d, interpret)


@functools.partial(
    jax.jit,
    static_argnames=("coarse_len", "n_csz", "n_fsz", "block_families",
                     "batch_block", "interpret", "noise", "accum_dtype"),
)
def refine_charted_adjoint_pallas(g: Array, r: Array, d: Array = None, *,
                                  coarse_len: int, n_csz: int, n_fsz: int,
                                  block_families: int = 256,
                                  batch_block: int = 1,
                                  interpret: bool = False,
                                  noise: bool = True,
                                  accum_dtype: str = "float32"):
    """Fused adjoint of ``refine_charted_pallas`` (per-family matrices).

    See ``refine_adjoint_launch_plan`` for the halo-flip geometry; the
    coefficient planes ride along twice exactly like g (main + shifted
    view).
    """
    batch = g.shape[0]
    plan = refine_adjoint_launch_plan(
        batch=batch, t=g.shape[-1] // n_fsz, coarse_len=coarse_len,
        n_csz=n_csz, n_fsz=n_fsz, block_families=block_families,
        batch_block=batch_block, dtype=g.dtype, accum_dtype=accum_dtype,
        charted=True, noise=noise)
    return _run_adjoint(plan, g, r, d, interpret)


# -- custom VJP registration ----------------------------------------------------
# The matrices (r, d) only need cotangents when the kernel parameters θ are
# being learned; symbolic_zeros=True lets the forward record perturbation per
# argument so fixed-matrix inference skips the window-tensor einsums. The
# flags are encoded in the residue *structure* (() vs None) — pytree treedefs
# are static, so the backward branches at trace time.
def _matrix_cotangents(coarse, xi, g3, r, d, r_pert, d_pert, *, charted,
                       accum=jnp.float32):
    s = r.shape[-2] // 2
    t = g3.shape[-2]
    if r_pert is not None:
        w = windows_1d(coarse, t, r.shape[-1], s)
        eq = "...tf,...tc->tfc" if charted else "...tf,...tc->fc"
        dr = jnp.einsum(eq, g3, w,
                        preferred_element_type=accum).astype(r.dtype)
    else:
        dr = jnp.zeros_like(r)
    if d_pert is not None:
        eq = "...tf,...tj->tfj" if charted else "...tf,...tj->fj"
        dd = jnp.einsum(eq, g3, xi,
                        preferred_element_type=accum).astype(d.dtype)
    else:
        dd = jnp.zeros_like(d)
    return dr, dd


def _make_refine_vjp(impl, adjoint, *, charted):
    """custom_vjp wrapper shared by both kernel variants: residual packing,
    symbolic-zero handling, adjoint dispatch and matrix-cotangent gating
    differ only in (impl, adjoint, charted)."""

    @functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
    def refine(meta, coarse, xi, r, d):
        return impl(meta, coarse, xi, r, d)

    def fwd(meta, coarse, xi, r, d):
        out = impl(meta, coarse.value, xi.value, r.value, d.value)
        res = (coarse.value, xi.value, r.value, d.value,
               () if r.perturbed else None, () if d.perturbed else None)
        return out, res

    def bwd(meta, res, g):
        n_csz, n_fsz, block_families, batch_block, interpret, accum_name \
            = meta
        coarse, xi, r, d, r_pert, d_pert = res
        if isinstance(g, SymbolicZero):
            return (jnp.zeros_like(coarse), jnp.zeros_like(xi),
                    jnp.zeros_like(r), jnp.zeros_like(d))
        dc, dxi = adjoint(
            g, r, d, coarse_len=coarse.shape[-1], n_csz=n_csz, n_fsz=n_fsz,
            block_families=block_families, batch_block=batch_block,
            interpret=interpret, accum_dtype=accum_name,
        )
        g3 = g.reshape(g.shape[:-1] + (xi.shape[-2], n_fsz))
        dr, dd = _matrix_cotangents(coarse, xi, g3, r, d, r_pert, d_pert,
                                    charted=charted,
                                    accum=jnp.dtype(accum_name))
        return dc.astype(coarse.dtype), dxi.astype(xi.dtype), dr, dd

    refine.defvjp(fwd, bwd, symbolic_zeros=True)
    return refine


def _make_refine_nn_vjp(impl, adjoint, *, charted):
    """Noise-free counterpart: two diff args (coarse, r), the adjoint launch
    skips the dxi computation entirely."""

    @functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
    def refine(meta, coarse, r):
        return impl(meta, coarse, r)

    def fwd(meta, coarse, r):
        out = impl(meta, coarse.value, r.value)
        return out, (coarse.value, r.value, () if r.perturbed else None)

    def bwd(meta, res, g):
        t, n_csz, n_fsz, block_families, batch_block, interpret, accum_name \
            = meta
        coarse, r, r_pert = res
        if isinstance(g, SymbolicZero):
            return jnp.zeros_like(coarse), jnp.zeros_like(r)
        dc = adjoint(
            g, r, coarse_len=coarse.shape[-1], n_csz=n_csz, n_fsz=n_fsz,
            block_families=block_families, batch_block=batch_block,
            interpret=interpret, noise=False, accum_dtype=accum_name,
        )
        if r_pert is not None:
            g3 = g.reshape(g.shape[:-1] + (t, n_fsz))
            w = windows_1d(coarse, t, n_csz, n_fsz // 2)
            eq = "...tf,...tc->tfc" if charted else "...tf,...tc->fc"
            dr = jnp.einsum(eq, g3, w,
                            preferred_element_type=jnp.dtype(accum_name)
                            ).astype(r.dtype)
        else:
            dr = jnp.zeros_like(r)
        return dc.astype(coarse.dtype), dr

    refine.defvjp(fwd, bwd, symbolic_zeros=True)
    return refine


_refine_stationary = _make_refine_vjp(
    _refine_stationary_impl, refine_stationary_adjoint_pallas, charted=False)
_refine_charted = _make_refine_vjp(
    _refine_charted_impl, refine_charted_adjoint_pallas, charted=True)
_refine_stationary_nn = _make_refine_nn_vjp(
    _refine_stationary_nn_impl, refine_stationary_adjoint_pallas,
    charted=False)
_refine_charted_nn = _make_refine_nn_vjp(
    _refine_charted_nn_impl, refine_charted_adjoint_pallas, charted=True)


# -- public entry points --------------------------------------------------------
@functools.partial(
    jax.jit,
    static_argnames=("n_csz", "n_fsz", "block_families", "batch_block",
                     "interpret", "noise", "t", "accum_dtype"),
)
def refine_stationary_pallas(coarse: Array, xi: Array, r: Array,
                             d: Array = None, *, n_csz: int, n_fsz: int,
                             block_families: int = 256,
                             batch_block: int = 1,
                             interpret: bool = False,
                             noise: bool = True,
                             t: int = None,
                             accum_dtype: str = "float32") -> Array:
    """Fused stationary refinement (differentiable). See module docstring.

    coarse: (B, L) halo-padded (L >= T*s + n_csz - s); xi: (B, T, n_fsz)
    r: (n_fsz, n_csz); d: (n_fsz, n_fsz)  ->  fine: (B, T*n_fsz)

    batch_block: leading-batch rows processed per kernel invocation (the
    sample-batch slab; matrices are fetched once per slab).
    noise=False skips the ξ read and the noise add entirely (``xi``/``d``
    may be None); the family count then comes from ``t`` (static).
    """
    if noise:
        return _refine_stationary(
            (n_csz, n_fsz, block_families, batch_block, interpret,
             accum_dtype),
            coarse, xi, r, d,
        )
    tt = t if t is not None else (xi.shape[-2] if xi is not None else None)
    if tt is None:
        raise ValueError("noise=False needs the family count: pass t=")
    return _refine_stationary_nn(
        (tt, n_csz, n_fsz, block_families, batch_block, interpret,
         accum_dtype),
        coarse, r,
    )


@functools.partial(
    jax.jit,
    static_argnames=("n_csz", "n_fsz", "block_families", "batch_block",
                     "interpret", "noise", "t", "accum_dtype"),
)
def refine_charted_pallas(coarse: Array, xi: Array, r: Array,
                          d: Array = None, *, n_csz: int, n_fsz: int,
                          block_families: int = 256,
                          batch_block: int = 1,
                          interpret: bool = False,
                          noise: bool = True,
                          t: int = None,
                          accum_dtype: str = "float32") -> Array:
    """Fused charted refinement with per-family matrices (paper §4.3),
    differentiable via the hand-written adjoint kernels.

    coarse: (B, L); xi: (B, T, n_fsz); r: (T, n_fsz, n_csz);
    d: (T, n_fsz, n_fsz)  ->  fine: (B, T*n_fsz)

    See ``refine_stationary_pallas`` for batch_block / noise semantics;
    with noise=False the family count is taken from ``r``.
    """
    if noise:
        return _refine_charted(
            (n_csz, n_fsz, block_families, batch_block, interpret,
             accum_dtype),
            coarse, xi, r, d,
        )
    return _refine_charted_nn(
        (r.shape[0], n_csz, n_fsz, block_families, batch_block, interpret,
         accum_dtype),
        coarse, r,
    )
