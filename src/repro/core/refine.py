"""Refinement matrices and one refinement step (paper §4.1–4.4).

A refinement family conditions ``n_fsz^d`` fine pixels on their ``n_csz^d``
nearest coarse pixels:

    R      = K_fc K_cc^{-1}                      (paper Eq. 7)
    D      = K_ff − K_fc K_cc^{-1} K_cf          (paper Eq. 8)
    s_f    = R s_c + sqrt(D) ξ_f                 (paper Eq. 9)

On chart-invariant axes the matrices are identical for every family along
that axis and are broadcast (paper §4.3). The reference apply path below is
pure jnp; the TPU hot path lives in repro.kernels (Pallas).
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Sequence

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax

from .charts import Chart
from .kernels import kernel_matrix

Array = jnp.ndarray


def _family_positions(chart: Chart, level: int):
    """Per-axis chart coords of family windows, collapsed on invariant axes.

    Returns (coarse_axes, fine_axes, full_T, kept_T):
      coarse_axes[a]: (T'_a, n_csz) chart coords (T'_a == 1 if invariant)
      fine_axes[a]:   (T'_a, n_fsz)
      full_T: true family counts per axis; kept_T: materialized counts.
    """
    coarse_axes, fine_axes, full_T, kept_T = [], [], [], []
    for a in range(chart.ndim):
        t_a = chart.family_count(level, a)
        # invariant axis: one representative family, the interior one (away
        # from a reflect boundary); the others' windows are never built
        fams = [min(t_a - 1, chart.b)] if chart.invariant[a] else None
        cw = chart.axis_coarse_windows(level, a, fams)
        fw = chart.axis_fine_windows(level, a, fams)
        full_T.append(t_a)
        coarse_axes.append(cw)
        fine_axes.append(fw)
        kept_T.append(cw.shape[0])
    return coarse_axes, fine_axes, tuple(full_T), tuple(kept_T)


def _psd_sqrt(mat: Array, eps: Array) -> Array:
    """Square root of a (nearly) PSD matrix via eigh with eigenvalue clipping.

    The paper only requires SOME sqrt with sqrt·sqrtᵀ = D (§3.2: "the
    square-root ... is not uniquely defined"). For strongly correlated fine
    points D is numerically semi-definite in f32; eigh+clip is robust where
    Cholesky NaNs.
    """
    evals, evecs = jnp.linalg.eigh(mat)
    evals = jnp.maximum(evals, eps)
    return evecs * jnp.sqrt(evals)[..., None, :]


def _nd_points(axes_windows: Sequence[Array]) -> Array:
    """Tensor-product of per-axis window coords -> (..., W^d, ndim).

    axes_windows[a]: (w_a,) chart coords along axis a for ONE family.
    Returns (prod(w_a), ndim).
    """
    grids = jnp.meshgrid(*axes_windows, indexing="ij")
    return jnp.stack([g.reshape(-1) for g in grids], axis=-1)


# Batched small solves and eigendecompositions pad every family's matrix
# to an (8, 128) tile on the TPU, ~4 KB a family: a 1-D charted level of
# 8.4M families needed 32 GB at once. Past this many families the batch
# runs in chunks (~1 GB each).
FAMILY_CHUNK = 1 << 18


def refinement_matrices_level(chart: Chart, kernel_fn: Callable, level: int,
                              *, jitter: float = 1e-6):
    """Refinement matrices (R, sqrt(D)) for all families refining `level`.

    Returns (R, sqrtD) with leading dims = kept_T (invariant axes collapsed
    to 1): R: (*kept_T, n_fsz^d, n_csz^d), sqrtD: (*kept_T, n_fsz^d, n_fsz^d).
    """
    coarse_axes, fine_axes, full_T, kept_T = _family_positions(chart, level)
    nd = chart.ndim
    csz, fsz = chart.n_csz**nd, chart.n_fsz**nd

    def one_family(cws, fws):
        # cws[a]: (n_csz,), fws[a]: (n_fsz,) chart coords
        cpos = chart.map_to_D(_nd_points(cws))  # (csz, dim_D)
        fpos = chart.map_to_D(_nd_points(fws))  # (fsz, dim_D)
        k_cc = kernel_matrix(kernel_fn, cpos)
        k_fc = kernel_matrix(kernel_fn, fpos, cpos)
        k_ff = kernel_matrix(kernel_fn, fpos)
        eps = jitter * jnp.mean(jnp.diag(k_cc))
        k_cc = k_cc + eps * jnp.eye(csz, dtype=k_cc.dtype)
        r = jnp.linalg.solve(k_cc, k_fc.T).T              # (fsz, csz), Eq. 7
        d = k_ff - r @ k_fc.T                             # Eq. 8
        d = 0.5 * (d + d.T)
        sqrt_d = _psd_sqrt(d, jitter * jnp.mean(jnp.diag(k_ff)))
        return r, sqrt_d

    fn = one_family
    # vmap over each axis' family dimension (size 1 on invariant axes)
    for a in reversed(range(nd)):
        in_axes = ([0 if i == a else None for i in range(nd)],
                   [0 if i == a else None for i in range(nd)])
        fn = jax.vmap(fn, in_axes=in_axes)
    # one program per level (and chunk shape): run eagerly, each of its
    # ~50 ops compiles on its own, ~0.3 s apiece on a TPU
    fn = jax.jit(fn)
    cws = [jnp.asarray(coarse_axes[a]) for a in range(nd)]
    fws = [jnp.asarray(fine_axes[a]) for a in range(nd)]
    n = cws[0].shape[0]
    if nd == 1 and n > FAMILY_CHUNK:
        parts = [fn([cws[0][i:i + FAMILY_CHUNK]], [fws[0][i:i + FAMILY_CHUNK]])
                 for i in range(0, n, FAMILY_CHUNK)]
        return (jnp.concatenate([p[0] for p in parts]),
                jnp.concatenate([p[1] for p in parts]))
    r, sqrt_d = fn(cws, fws)
    return r, sqrt_d


def axis_refinement_matrices_level(chart: Chart, kernel_fn: Callable,
                                   level: int, *, jitter: float = 1e-6):
    """Per-axis 1-D refinement factors for the separable N-D fast path.

    For each chart axis ``a`` this computes 1-D refinement matrices (Eq. 7/8)
    from the axis-``a`` coarse/fine windows, with every other coordinate
    pinned at a representative chart position (the grid midpoint). The fused
    N-D path (repro.kernels.nd) applies them as a sequence of per-axis
    passes, which is exactly the Kronecker-factored refinement

        R_joint = R_0 ⊗ ... ⊗ R_{d-1},   sqrtD_joint = sqrtD_0 ⊗ ...

    For product (separable) kernels the interpolation factorization of R is
    exact; for isotropic kernels it is the nearest-separable surrogate in the
    spirit of the paper's §4.3 chart approximations (and of KISS-GP-style
    Kronecker interpolation). The noise factors are normalized so the product
    carries the kernel variance ``k(0)`` exactly once.

    Returns ``(rs, ds)``: ``rs[a]`` is ``(n_fsz, n_csz)`` on invariant axes,
    else ``(T_a, n_fsz, n_csz)``; ``ds[a]`` likewise with ``n_csz -> n_fsz``.
    """
    nd = chart.ndim
    csz, fsz = chart.n_csz, chart.n_fsz
    k0 = kernel_matrix(kernel_fn, jnp.zeros((1, max(1, nd))))[0, 0]
    rep_coord = [
        chart.axis_coords(level, o)[chart.shape(level)[o] // 2]
        for o in range(nd)
    ]

    rs, ds = [], []
    for a in range(nd):
        fams = ([min(chart.family_count(level, a) - 1, chart.b)]
                if chart.invariant[a] else None)
        cw = jnp.asarray(chart.axis_coarse_windows(level, a, fams))
        fw = jnp.asarray(chart.axis_fine_windows(level, a, fams))

        def one_family(cw_t, fw_t, axis=a):
            def pts(wins):
                cols = [
                    wins if o == axis
                    else jnp.full(wins.shape, rep_coord[o], wins.dtype)
                    for o in range(nd)
                ]
                return chart.map_to_D(jnp.stack(cols, axis=-1))

            cpos, fpos = pts(cw_t), pts(fw_t)
            k_cc = kernel_matrix(kernel_fn, cpos)
            k_fc = kernel_matrix(kernel_fn, fpos, cpos)
            k_ff = kernel_matrix(kernel_fn, fpos)
            eps = jitter * jnp.mean(jnp.diag(k_cc))
            k_cc = k_cc + eps * jnp.eye(csz, dtype=k_cc.dtype)
            r = jnp.linalg.solve(k_cc, k_fc.T).T
            d = k_ff - r @ k_fc.T
            d = 0.5 * (d + d.T)
            if axis > 0:  # variance enters the Kronecker product once
                d = d / k0
                k_ff = k_ff / k0
            return r, _psd_sqrt(d, jitter * jnp.mean(jnp.diag(k_ff)))

        r, sqrt_d = jax.jit(jax.vmap(one_family))(cw, fw)
        if chart.invariant[a]:
            r, sqrt_d = r[0], sqrt_d[0]
        rs.append(r)
        ds.append(sqrt_d)
    return rs, ds


def level0_sqrt(chart: Chart, kernel_fn: Callable, *, jitter: float = 1e-6):
    """Exact PSD sqrt of the level-0 kernel matrix (small by design).

    A concrete matrix is factored on the host's CPU device when the
    default device is an accelerator, and the factor moved back: the TPU's
    eigh of a 4096-point level 0 (the dust chart) compiled for ~500 s and
    took ~20 GiB of host memory, where LAPACK takes seconds. A traced one
    (θ learned inside a jitted step) stays in the trace."""
    pos = chart.grid_positions(0)
    k = kernel_matrix(kernel_fn, pos)
    k, eps = 0.5 * (k + k.T), jitter * jnp.mean(jnp.diag(k))
    if jax.default_backend() == "cpu" or isinstance(k, jax.core.Tracer):
        return _psd_sqrt(k, eps)
    cpu = jax.devices("cpu")[0]
    dev = next(iter(k.devices()))
    return jax.device_put(
        _psd_sqrt(jax.device_put(k, cpu), jax.device_put(eps, cpu)), dev)


@dataclasses.dataclass(frozen=True)
class LevelGeom:
    """Static geometry of one refinement application (trace-time constants)."""

    coarse_shape: tuple
    fine_shape: tuple
    T: tuple          # families per axis
    kept_T: tuple     # materialized matrix counts per axis (1 on invariant)
    n_csz: int
    n_fsz: int
    stride: int
    b: int
    boundary: str

    @classmethod
    def for_level(cls, chart: Chart, level: int) -> "LevelGeom":
        # counts only: ``_family_positions`` would build every family's
        # window coordinates (gigabytes at deployment sizes)
        full_T = tuple(chart.family_count(level, a)
                       for a in range(chart.ndim))
        kept_T = tuple(1 if chart.invariant[a] else t
                       for a, t in enumerate(full_T))
        return cls(
            coarse_shape=chart.shape(level),
            fine_shape=chart.shape(level + 1),
            T=full_T,
            kept_T=kept_T,
            n_csz=chart.n_csz,
            n_fsz=chart.n_fsz,
            stride=chart.stride,
            b=chart.b,
            boundary=chart.boundary,
        )

    def axis(self, a: int) -> "LevelGeom":
        """1-D geometry of the per-axis pass along `a` (N-D fast path)."""
        return LevelGeom(
            coarse_shape=(self.coarse_shape[a],),
            fine_shape=(self.T[a] * self.n_fsz,),
            T=(self.T[a],),
            kept_T=(self.kept_T[a],),
            n_csz=self.n_csz,
            n_fsz=self.n_fsz,
            stride=self.stride,
            b=self.b,
            boundary=self.boundary,
        )


def _axis_windows(arr: Array, axis: int, geom: LevelGeom) -> Array:
    """Extract per-family coarse windows along `axis` with shifted strided
    slices (TPU-friendly: no gather). Appends a window dim at the end.

    arr: (..., N_axis, ...) -> (..., T_axis, ..., n_csz) with the window dim
    appended as the new last dimension.
    """
    t = geom.T[axis]
    if geom.boundary == "reflect":
        b = geom.b
        pad = [(0, 0)] * arr.ndim
        pad[axis] = (b, b)
        arr = jnp.pad(arr, pad, mode="reflect")
    slices = []
    for k in range(geom.n_csz):
        limit = k + geom.stride * (t - 1) + 1
        slices.append(
            lax.slice_in_dim(arr, k, limit, stride=geom.stride, axis=axis)
        )
    return jnp.stack(slices, axis=-1)


def refine_level(coarse: Array, xi: Array, r: Array, sqrt_d: Array,
                 geom: LevelGeom) -> Array:
    """One refinement application (paper Eq. 9 / Alg. 1 inner loop).

    coarse: (*coarse_shape); xi: (prod(T), n_fsz^d)
    r: (*kept_T, fsz^d, csz^d); sqrt_d: (*kept_T, fsz^d, fsz^d)
    Returns fine field (*fine_shape).
    """
    nd = len(geom.coarse_shape)
    w = coarse
    for a in range(nd):
        w = _axis_windows(w, a, geom)
    # w: (T_0..T_{nd-1}, csz, csz, ...) -> (*T, csz^d)
    csz, fsz = geom.n_csz**nd, geom.n_fsz**nd
    w = w.reshape(geom.T + (csz,))

    # Batched GEMM over the NON-invariant family dims only: invariant axes
    # become the GEMM row dim, so the shared matrices are NEVER broadcast-
    # materialized to (F, fsz, csz) — at dust-map scale that expansion is
    # ~100 GB/device (EXPERIMENTS.md §Perf iteration 4).
    kept_axes = [a for a in range(nd) if geom.kept_T[a] > 1]
    inv_axes = [a for a in range(nd) if geom.kept_T[a] == 1]
    perm = kept_axes + inv_axes
    k_tot = int(np.prod([geom.T[a] for a in kept_axes])) or 1
    i_tot = int(np.prod([geom.T[a] for a in inv_axes])) or 1

    w_p = w.transpose(perm + [nd]).reshape(k_tot, i_tot, csz)
    xi_p = xi.reshape(geom.T + (fsz,)).transpose(perm + [nd]) \
        .reshape(k_tot, i_tot, fsz)
    r_b = r.reshape(k_tot, fsz, csz)
    d_b = sqrt_d.reshape(k_tot, fsz, fsz)

    fine = jnp.einsum("kic,kfc->kif", w_p, r_b)
    fine = fine + jnp.einsum("kif,kgf->kig", xi_p, d_b)

    # back to (*T, fsz^d), then interleave family and child dims
    t_perm = [geom.T[a] for a in perm]
    inv_perm = [perm.index(a) for a in range(nd)]
    fine = fine.reshape(t_perm + [fsz]).transpose(inv_perm + [nd])
    fine = fine.reshape(geom.T + (geom.n_fsz,) * nd)
    interleave = []
    for a in range(nd):
        interleave += [a, nd + a]
    fine = fine.transpose(interleave)
    return fine.reshape(geom.fine_shape)


def refine_level_T(fine_cot: Array, r: Array, sqrt_d: Array,
                   geom: LevelGeom):
    """Adjoint of ``refine_level`` in (coarse, xi) at fixed matrices.

    The refinement application is linear in (coarse, xi), so its VJP at the
    origin IS the transpose operator. This is the jnp reference the fused
    adjoint kernels (repro.kernels) are validated against, and the per-level
    building block of ``ICR.apply_sqrt_T``.

    fine_cot: (*fine_shape) -> (dcoarse: (*coarse_shape),
    dxi: (prod(T), n_fsz^d)).
    """
    nd = len(geom.coarse_shape)
    zc = jnp.zeros(geom.coarse_shape, fine_cot.dtype)
    zx = jnp.zeros((int(np.prod(geom.T)), geom.n_fsz**nd), fine_cot.dtype)
    _, vjp = jax.vjp(lambda c, x: refine_level(c, x, r, sqrt_d, geom), zc, zx)
    return vjp(fine_cot)
