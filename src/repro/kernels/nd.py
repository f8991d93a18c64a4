"""Fused N-D refinement as per-axis 1-D Pallas passes (DESIGN.md §4).

The per-axis N-D path applies Kronecker-factored refinement matrices
(``core.refine.axis_refinement_matrices_level``) one axis at a time, folding
every other axis into the 1-D kernels' leading batch dimension — the same
batch mechanism that carries the paper's §4.3 invariant-axis broadcast. Each
pass is one fused kernel launch: window build, MXU contraction and (on the
final pass) the correlated noise add all happen in VMEM, so nothing
``(T, n_csz)``-shaped is ever materialized in HBM, for any ndim.

This is the *fallback* N-D route since the single-launch megakernel landed
(``nd_fused.refine_nd_fused``, DESIGN.md §10): dispatch prefers the fused
level kernel and only comes here when the joint tile + halos exceed the
VMEM budget. The per-axis passes pay ``d`` field round-trips through HBM
plus a relayout around every pass (the byte model in
``roofline.level_traffic`` quantifies both).

Pass order is axis ``d-1 .. 0``; mixed stationary/charted axes are supported
per axis (shared ``(n_fsz, n_csz)`` stencil vs per-family ``(T_a, ...)``
matrices). Only the final (axis-0) pass injects the excitation ξ: the noise
factors of axes ``1..d-1`` are pre-contracted into ξ outside the kernel
(cheap batched small GEMMs at fine resolution). Every non-final pass runs
the kernels in ``noise=False`` mode — no ξ operand at all, where they used
to read an all-zeros array from HBM per pass.

With ``sample_axis=True`` the leading dimension of ``field``/``xi`` is a
sample batch; it simply folds into the kernels' batch dimension, so batched
sampling shares every matrix fetch.

Boundaries are handled per axis: ``"shrink"`` needs no padding (family ``t``
reads ``coarse[t*s : t*s + n_csz]`` directly), ``"reflect"`` pre-pads ``b``
pixels per side in HBM once per pass.

The jnp ground truth is ``repro.kernels.ref.refine_axes_ref`` (written
independently); parity is asserted in tests/test_kernels_pallas.py.

Differentiation: the 1-D kernel entry points carry custom VJPs (fused
adjoint kernels, DESIGN.md §9; the noise-free passes use the dxi-free
adjoint), and everything else here — moveaxis, reshapes, the ξ
pre-contraction einsums, the reflect pad — is plain jnp. So ``jax.grad``
through ``refine_axes`` runs the per-axis passes in reverse, each one a
fused adjoint launch: the N-D backward is Kronecker-factored exactly like
the forward, with no joint window tensor ever materialized.
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.core.refine import LevelGeom

from .icr_refine import refine_charted_pallas, refine_stationary_pallas
from .nd_fused import precontract_noise

Array = jnp.ndarray


def refine_axes(field: Array, xi: Array, rs, ds, geom: LevelGeom, *,
                interpret: bool,
                block_families: int | None = None,
                sample_axis: bool = False,
                accum_dtype: str = "float32") -> Array:
    """Fused per-axis N-D refinement (drop-in for refine_level given factors).

    field: (*geom.coarse_shape); xi: (prod(geom.T), n_fsz^ndim) — each with
    an extra leading sample dimension when ``sample_axis=True``.
    rs[a]: (n_fsz, n_csz) on stationary axes else (T_a, n_fsz, n_csz);
    ds[a]:  likewise with n_csz -> n_fsz.
    Returns the fine field, shape ``geom.fine_shape`` (sample dim leading
    when ``sample_axis``). Storage dtype follows the operands; every
    contraction (in-kernel and the ξ pre-contraction here) accumulates in
    ``accum_dtype`` (DESIGN.md §11).
    """
    from .dispatch import axis_pass_tiles  # lazy: avoid import cycle

    nd = len(geom.coarse_shape)
    fsz, csz, b = geom.n_fsz, geom.n_csz, geom.b
    T = tuple(geom.T)
    accum = jnp.dtype(accum_dtype)
    off = 1 if sample_axis else 0
    lead = field.shape[:off]

    # -- excitation: pre-contract noise factors of axes 1..d-1 -----------------
    xi_nd = precontract_noise(xi.reshape(lead + T + (fsz,) * nd), ds,
                              off=off, accum=accum)
    # interleave (T_a, f_a) for a>=1 into the final pass' fine batch layout
    perm = list(range(off))
    for a in range(1, nd):
        perm += [off + a, off + nd + a]
    perm += [off, off + nd]
    xi0 = xi_nd.transpose(perm).reshape(-1, T[0], fsz).astype(field.dtype)

    # -- field: one fused kernel pass per axis, orthogonal axes as batch -------
    out = field
    for a in range(nd - 1, -1, -1):
        ag = geom.axis(a)  # 1-D geometry of this pass
        arr = jnp.moveaxis(out, off + a, -1)
        bshape = arr.shape[:-1]
        coarse = arr.reshape(-1, arr.shape[-1])
        if ag.boundary == "reflect":
            coarse = jnp.pad(coarse, [(0, 0), (b, b)], mode="reflect")
        charted = rs[a].ndim == 3
        bf, bb = axis_pass_tiles(coarse.shape[0], ag.T[0], csz, fsz,
                                 charted=charted,
                                 itemsize=jnp.dtype(field.dtype).itemsize)
        bf = block_families or bf
        kern = refine_charted_pallas if charted else refine_stationary_pallas
        if a == 0:
            res = kern(
                coarse, xi0, rs[a], ds[a], n_csz=csz, n_fsz=fsz,
                block_families=bf, batch_block=bb, interpret=interpret,
                accum_dtype=accum_dtype,
            )
        else:
            # noise already folded into xi0: run the ξ-free kernel variant
            # (no zero-excitation array is ever built or read)
            res = kern(
                coarse, None, rs[a], None, n_csz=csz, n_fsz=fsz,
                block_families=bf, batch_block=bb, interpret=interpret,
                noise=False,
                t=ag.T[0], accum_dtype=accum_dtype,
            )
        out = jnp.moveaxis(res.reshape(bshape + (T[a] * fsz,)), -1, off + a)
    return out
