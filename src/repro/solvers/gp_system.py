"""The data-conditioning linear system for ICR GPs (DESIGN.md §16).

Exact GP regression conditions the ICR prior on noisy observations
``y = W s + ε``, ``ε ~ N(0, σ²I)``: with ``K = S Sᵀ`` (``S`` the ICR
square root, applied matrix-free) the posterior mean is

    m = K Wᵀ α,   (W K Wᵀ + σ² I) α = y

so one matvec of the observation-space operator ``A = W K Wᵀ + σ²I``
is *two* applications of the square root (``Sᵀ`` then ``S`` — the
paper's §1 cost unit) bracketed by the sparse interpolation ``W``. This
module builds everything the guarded batched CG needs to solve with A:

  * observation operators — :class:`ObsSelect` for on-grid index
    observations and :class:`GridInterp` for off-grid points via the
    KISS-GP sparse linear interpolation (arXiv 2101.11751 cost model;
    ``core/kissgp.py`` is the 1-D reference implementation);
  * the batched matvec, optionally sharded over the RHS axis through
    ``shard_map`` on a device mesh (the serving path);
  * the **ICR-whitened preconditioner**: the coarse-level prefix of ξ
    spans the top of the kernel spectrum, so ``M = σ²I + U Uᵀ`` with
    ``U = W S_c`` (one batched sqrt application over coarse basis
    excitations) captures the dominant eigenspace; ``M⁻¹`` applies by a
    small Cholesky-factored Woodbury correction;
  * the dense fallback (materialize A column-block by batched matvec,
    ``jnp.linalg.solve``) for small charts — the ladder's last rung.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Optional

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

Array = jnp.ndarray


# -- observation operators -------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class ObsSelect:
    """On-grid observations: W selects ``idx`` out of the flattened field."""

    idx: tuple            # flat finest-grid indices (hashable for caching)
    n_grid: int

    @property
    def n_obs(self) -> int:
        return len(self.idx)

    def apply(self, f: Array) -> Array:
        """(k, N) field rows -> (k, O) observed rows."""
        return f[:, jnp.asarray(self.idx)]

    def apply_t(self, v: Array) -> Array:
        """(k, O) -> (k, N) scatter-add (Wᵀ)."""
        out = jnp.zeros((v.shape[0], self.n_grid), v.dtype)
        return out.at[:, jnp.asarray(self.idx)].add(v)

    def fingerprint(self) -> tuple:
        return ("select", self.n_grid, self.idx)


@dataclasses.dataclass(frozen=True)
class GridInterp:
    """Off-grid 1-D observations: sparse linear interpolation rows of W
    (two nonzeros per observation — the KISS-GP stencil, applied in
    O(n_obs) like ``KissGP.apply_w``/``apply_wt``)."""

    idx: tuple            # left grid neighbor per observation
    w_lo: tuple
    w_hi: tuple
    n_grid: int

    @classmethod
    def from_points(cls, grid_x: np.ndarray, x_obs: np.ndarray):
        """Build W from sorted uniform grid coordinates and observation
        locations (clipped to the grid span, as ``KissGP.interp_weights``
        does)."""
        grid_x = np.asarray(grid_x, np.float64)
        x_obs = np.asarray(x_obs, np.float64)
        h = float(grid_x[1] - grid_x[0])
        p = (x_obs - float(grid_x[0])) / h
        idx = np.clip(np.floor(p).astype(np.int64), 0, len(grid_x) - 2)
        frac = np.clip(p - idx, 0.0, 1.0)
        return cls(idx=tuple(int(i) for i in idx),
                   w_lo=tuple(float(w) for w in 1.0 - frac),
                   w_hi=tuple(float(w) for w in frac),
                   n_grid=len(grid_x))

    @property
    def n_obs(self) -> int:
        return len(self.idx)

    def apply(self, f: Array) -> Array:
        idx = jnp.asarray(self.idx)
        wl = jnp.asarray(self.w_lo, f.dtype)
        wr = jnp.asarray(self.w_hi, f.dtype)
        return wl[None, :] * f[:, idx] + wr[None, :] * f[:, idx + 1]

    def apply_t(self, v: Array) -> Array:
        idx = jnp.asarray(self.idx)
        wl = jnp.asarray(self.w_lo, v.dtype)
        wr = jnp.asarray(self.w_hi, v.dtype)
        out = jnp.zeros((v.shape[0], self.n_grid), v.dtype)
        out = out.at[:, idx].add(wl[None, :] * v)
        return out.at[:, idx + 1].add(wr[None, :] * v)

    def fingerprint(self) -> tuple:
        return ("interp", self.n_grid, self.idx, self.w_lo, self.w_hi)


def obs_operator(icr, *, obs_idx=None, x_obs=None):
    """Build the observation operator for a chart: flat finest-grid
    indices (any dimension) or off-grid 1-D locations, exactly one."""
    n = int(np.prod(icr.chart.final_shape))
    if (obs_idx is None) == (x_obs is None):
        raise ValueError("pass exactly one of obs_idx (on-grid) or "
                         "x_obs (off-grid 1-D)")
    if obs_idx is not None:
        idx = np.asarray(obs_idx, np.int64).ravel()
        if idx.size == 0 or idx.min() < 0 or idx.max() >= n:
            raise ValueError(f"obs_idx out of range for a {n}-pixel chart")
        return ObsSelect(idx=tuple(int(i) for i in idx), n_grid=n)
    if icr.chart.ndim != 1:
        raise ValueError("off-grid x_obs interpolation is 1-D only; "
                         "use on-grid obs_idx for N-D charts")
    grid_x = icr.chart.axis_coords(icr.chart.n_levels, 0)
    return GridInterp.from_points(grid_x, x_obs)


# -- the observation-space operator A = W K Wᵀ + σ²I ----------------------------
@dataclasses.dataclass
class ConditionSystem:
    """Everything one data-conditioning solve needs, built once per
    (chart, θ, obs, σ²) and cached by the serving layer."""

    icr: object
    obs: object
    noise_var: float
    mats: dict
    matvec: Callable[[Array], Array]     # (k, O) -> (k, O)
    precond: Optional[Callable]          # ICR-whitened M⁻¹, or None
    mesh: object = None
    # jitted (mats, ·) programs: one compile per apply, where an eager
    # apply compiles every op of every level on its own (minutes on a TPU)
    _sqrt_batch: Optional[Callable] = None   # (mats, ξ batch) -> fields
    _correct: Optional[Callable] = None      # (mats, α) -> K Wᵀ α

    @property
    def n_obs(self) -> int:
        return self.obs.n_obs

    def dense_solve(self, b: Array) -> Array:
        """Materialize A by batched matvec on the identity and solve
        directly — the ladder's dense rung (small charts only; gated by
        ``CGConfig.dense_max``). A is symmetric, so batching identity
        *rows* through the matvec yields A itself."""
        n = self.n_obs
        eye = jnp.eye(n, dtype=b.dtype)
        a = condition_matvec(self.icr, self.mats, self.obs,
                             self.noise_var, eye)
        return jnp.linalg.solve(a, jnp.asarray(b).T).T

    def sqrt_batch(self, xi: list) -> Array:
        """S ξ for a batch of excitations: (k, *final_shape) fields."""
        return self._sqrt_batch(self.mats, xi)

    def correct(self, alpha: Array) -> Array:
        """K Wᵀ α for a batch of solutions: (k, O) -> (k, *final_shape)
        posterior corrections (one Sᵀ + one S application)."""
        return self._correct(self.mats, jnp.asarray(alpha))

    def project_xi(self, alpha: Array) -> list:
        """Sᵀ Wᵀ α: the whitened (ξ-space) representation of the
        conditioning correction — a delta ``Posterior.mean`` serves the
        CG posterior mean through the existing sampling slab unchanged."""
        return _project_xi(self.icr, self.obs, self.mats, jnp.asarray(alpha))


def _project_xi(icr, obs, mats, alpha: Array) -> list:
    u = obs.apply_t(alpha)
    u = u.reshape((u.shape[0],) + tuple(icr.chart.final_shape))
    return _sqrt_t_batch(icr, mats, u)


def _sqrt_t_batch(icr, mats, u: Array) -> list:
    """Batched Sᵀ: VJP of ``apply_sqrt_batch`` at zero ξ (linear in ξ at
    fixed matrices, so the VJP *is* the transpose — ``ICR.apply_sqrt_T``
    batched over the sample axis)."""
    k = u.shape[0]
    zero = [jnp.zeros((k,) + tuple(s), u.dtype) for s in icr.xi_shapes()]
    out, vjp = jax.vjp(lambda xi: icr.apply_sqrt_batch(mats, xi), zero)
    # under a bf16 storage policy the sqrt emits bf16: the cotangent must
    # match the primal output dtype (f32 solves are unaffected)
    return vjp(u.astype(out.dtype))[0]


def condition_matvec(icr, mats, obs, noise_var, v: Array) -> Array:
    """(W S Sᵀ Wᵀ + σ²I) v for a batch of observation-space vectors."""
    k = v.shape[0]
    shape = tuple(icr.chart.final_shape)
    u = obs.apply_t(v).reshape((k,) + shape)
    xi = _sqrt_t_batch(icr, mats, u)
    f = icr.apply_sqrt_batch(mats, xi).reshape(k, -1)
    return obs.apply(f) + noise_var * v


def icr_whitening_precond(icr, mats, obs, noise_var: float, *,
                          max_basis: int = 512) -> Optional[Callable]:
    """The ICR-whitened (coarse-subspace Woodbury) preconditioner.

    Take the coarse prefix of ξ levels whose total size fits
    ``max_basis`` (always at least level 0): their span carries the
    top of the kernel spectrum — the slowly-converging CG directions.
    With ``U = W S_c`` (obs × m, built by ONE batched sqrt application
    over the m basis excitations) precondition with

        M = σ² I + U Uᵀ,
        M⁻¹ r = (r − U C⁻¹ Uᵀ r) / σ²,   C = σ² I_m + Uᵀ U  (Cholesky).

    Exact on the coarse subspace, identity/σ² on its complement —
    clusters the preconditioned spectrum near 1 ∪ {fine-scale tail}.
    Returns None when even level 0 exceeds ``max_basis`` (the ladder
    then starts at the unpreconditioned rung).
    """
    sizes = [int(np.prod(s)) for s in icr.xi_shapes()]
    take = 0
    total = 0
    for s in sizes:
        if take > 0 and total + s > max_basis:
            break
        take += 1
        total += s
    if total > max_basis:
        return None
    m = total
    # m basis excitations: row j is e_j within the taken coarse prefix
    basis = []
    off = 0
    for lvl, s in enumerate(sizes):
        shape = tuple(icr.xi_shapes()[lvl])
        if lvl < take:
            block = jnp.eye(m, dtype=jnp.float32)[:, off:off + s]
            basis.append(block.reshape((m,) + shape))
            off += s
        else:
            basis.append(jnp.zeros((m,) + shape, jnp.float32))
    fields = icr.apply_sqrt_batch(mats, basis).reshape(m, -1)
    fields = fields.astype(jnp.float32)
    u = obs.apply(fields).T                       # (O, m)
    c = noise_var * jnp.eye(m, dtype=u.dtype) + u.T @ u
    chol = jax.scipy.linalg.cho_factor(c)

    def precond(r: Array) -> Array:
        t = r @ u                                  # (k, m)
        s = jax.scipy.linalg.cho_solve(chol, t.T).T
        return (r - s @ u.T) / noise_var

    return precond


def build_condition_system(icr, obs, noise_var: float, *, theta=None,
                           mats=None, mesh=None,
                           precond_max_basis: int = 512,
                           use_precond: bool = True) -> ConditionSystem:
    """Assemble the jitted (optionally RHS-sharded) conditioning system.

    With ``mesh``, the matvec runs under ``shard_map`` split over the
    RHS axis (matrices replicated) — callers must pad the RHS batch to a
    multiple of the mesh size (``solve_guarded`` keeps widths constant
    across rungs, and ``pcg_solve`` re-pads the carry after an elastic
    shrink)."""
    if mats is None:
        mats = icr.matrices_cached(theta)
    noise_var = float(noise_var)

    def core(mats_, v):
        return condition_matvec(icr, mats_, obs, noise_var, v)

    if mesh is None:
        fn = jax.jit(core)
    else:
        from repro.compat import shard_map

        axes = tuple(mesh.axis_names)
        repl = jax.tree.map(lambda _: P(), mats)
        fn = jax.jit(shard_map(core, mesh=mesh, in_specs=(repl, P(axes)),
                               out_specs=P(axes), check_vma=False))

    matvec = lambda v: fn(mats, v)  # noqa: E731 — bound operator
    precond = (icr_whitening_precond(icr, mats, obs, noise_var,
                                     max_basis=precond_max_basis)
               if use_precond else None)
    return ConditionSystem(
        icr=icr, obs=obs, noise_var=noise_var, mats=mats, matvec=matvec,
        precond=precond, mesh=mesh,
        _sqrt_batch=jax.jit(lambda m, xi: icr.apply_sqrt_batch(m, xi)),
        _correct=jax.jit(lambda m, a: icr.apply_sqrt_batch(
            m, _project_xi(icr, obs, m, a))))
