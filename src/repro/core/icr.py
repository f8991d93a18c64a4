"""Iterative Charted Refinement — the paper's core algorithm (§4, Alg. 1).

``ICR`` is a *generative* representation of a GP: it applies an O(N)
approximate square root of the kernel matrix to a standard-normal excitation
vector ξ (paper §3.2):

    s = sqrt(K_ICR)(ξ)  with  <s sᵀ> ≈ K_XX.

There is no inversion and no log-determinant anywhere — evaluating the model
(and its VJP) is two applications of the square root (paper §1).

The excitation ξ is a list of arrays, one per level:
  ξ[0]: (prod(shape0),)           — exact coarse-grid excitation
  ξ[l]: (F_l, n_fsz^d), l=1..L    — per-family fine corrections

Matrices depend on the kernel parameters θ and are (re)computed *inside* the
jitted step when θ is learned; they are a pytree so they can also be
precomputed and donated for fixed-θ sampling.
"""
from __future__ import annotations

import dataclasses
from typing import List, Mapping, Sequence

import numpy as np
import jax
import jax.numpy as jnp

from .charts import Chart
from .kernels import Kernel
from .refine import (
    LevelGeom,
    axis_refinement_matrices_level,
    level0_sqrt,
    refine_level,
    refinement_matrices_level,
)

Array = jnp.ndarray


def level0_field(sqrt0: Array, xi0: Array) -> Array:
    """The dense level-0 field ``sqrt0 @ ξ_0`` (``xi0`` is one excitation
    vector or a leading-sample batch of them), at full float32 precision:
    the TPU's default would round the operands to bf16 and put a ~1e-3
    relative error into every field before the first refinement level.
    Every path that starts a field (single device, the chart-sharded ring)
    goes through here."""
    hi = jax.lax.Precision.HIGHEST
    if xi0.ndim == 1:
        return jnp.matmul(sqrt0, xi0, precision=hi)
    return jnp.matmul(xi0, sqrt0.T, precision=hi)


@dataclasses.dataclass(frozen=True)
class ICR:
    """Iterative Charted Refinement model over `chart` with `kernel`.

    ``dtype_policy`` (DESIGN.md §11) sets the storage/accumulation dtypes
    of the whole refinement stack: ``"bf16"`` (or ``DtypePolicy()`` — the
    policy's own default) stores fields/ξ/matrices in bfloat16 with f32
    accumulation, halving HBM bytes per level; ``None`` keeps the
    historical all-float32 behavior (the ``"fp32"`` opt-out), bit-stable
    with the fp32 reference suites.

    ``use_pyramid`` (with ``use_pallas=True``): run all consecutive early
    levels whose combined working set fits VMEM as ONE kernel launch
    (repro.kernels.pyramid) — their intermediate fields never touch HBM.
    The remaining big levels run per level through ``dispatch.refine``.
    """

    chart: Chart
    kernel: Kernel
    jitter: float = 1e-6
    use_pallas: bool = False  # route stationary levels through repro.kernels
    dtype_policy: object = None  # None -> fp32; "bf16"/DtypePolicy() -> mixed
    use_pyramid: bool = True  # VMEM-resident multi-level prefix (needs pallas)

    @property
    def policy(self):
        """The resolved DtypePolicy (fp32 when ``dtype_policy`` is None)."""
        from repro.kernels.policy import resolve

        return resolve(self.dtype_policy)

    # -- shapes ---------------------------------------------------------------
    def xi_shapes(self) -> List[tuple]:
        nd = self.chart.ndim
        shapes = [(int(np.prod(self.chart.shape0)),)]
        for lvl in range(self.chart.n_levels):
            t = tuple(
                self.chart.family_count(lvl, a) for a in range(nd)
            )
            shapes.append((int(np.prod(t)), self.chart.n_fsz**nd))
        return shapes

    def xi_size(self) -> int:
        return sum(int(np.prod(s)) for s in self.xi_shapes())

    @property
    def out_shape(self) -> tuple:
        return self.chart.final_shape

    # -- parameters -----------------------------------------------------------
    def init_xi(self, key, dtype=None, *,
                batch: int | None = None) -> List[Array]:
        """Standard-normal excitations; ``batch`` prepends a sample dim to
        every level (the layout ``apply_sqrt_batch`` consumes). ``dtype``
        defaults to the dtype policy's storage dtype (f32 without one)."""
        dtype = self.policy.storage_dtype if dtype is None else dtype
        keys = jax.random.split(key, self.chart.n_levels + 1)
        lead = () if batch is None else (batch,)
        return [
            jax.random.normal(k, lead + s, dtype)
            for k, s in zip(keys, self.xi_shapes())
        ]

    def zero_xi(self, dtype=None) -> List[Array]:
        dtype = self.policy.storage_dtype if dtype is None else dtype
        return [jnp.zeros(s, dtype) for s in self.xi_shapes()]

    # -- matrices (functions of theta) ----------------------------------------
    def matrices(self, theta: Mapping[str, Array] | None = None, *,
                 joint: bool | None = None,
                 axes: bool | None = None) -> dict:
        """Refinement matrices for kernel parameters theta (paper Eq. 7/8).

        O(n_csz^{3d} · N) work, dominated by the finest level; differentiable
        w.r.t. theta.

        `axes` adds the per-axis Kronecker factors consumed by the fused N-D
        path (tiny next to the joint matrices); default: ``use_pallas`` on an
        N-D chart. `joint` builds the joint per-level (R, sqrtD); default:
        skipped exactly when the factors are built, because apply_sqrt then
        routes every level through them and the joint O(n_csz^{3d}) build
        would be dead weight. DistributedICR forces ``joint=True`` (its
        sharded body runs the joint reference).
        """
        build_axes = (self.use_pallas and self.chart.ndim > 1
                      if axes is None else axes)
        build_joint = (not build_axes) if joint is None else joint
        k = self.kernel(theta)
        # D = K_ff - R K_cf cancels to a tiny remainder on fine levels: the
        # TPU's default matmul (bf16 operands) left a 3.6% error in a tod
        # field (v5e, 12 levels); float32 products everywhere
        with jax.default_matmul_precision("highest"):
            out = {"sqrt0": level0_sqrt(self.chart, k, jitter=self.jitter)}
            if build_joint:
                out["R"], out["sqrtD"] = [], []
                for lvl in range(self.chart.n_levels):
                    r, sd = refinement_matrices_level(
                        self.chart, k, lvl, jitter=self.jitter
                    )
                    out["R"].append(r)
                    out["sqrtD"].append(sd)
            if build_axes:
                out["Rax"], out["sqrtDax"] = [], []
                for lvl in range(self.chart.n_levels):
                    rs, ds = axis_refinement_matrices_level(
                        self.chart, k, lvl, jitter=self.jitter
                    )
                    out["Rax"].append(rs)
                    out["sqrtDax"].append(ds)
        pol = self.policy
        if jnp.dtype(pol.storage_dtype) != jnp.float32:
            # matrix *math* stays f32 (solves/eigh above); only what is
            # stored — and re-read every level — drops to the storage dtype
            out = pol.cast_storage(out)
        return out

    def _theta_key(self, theta: Mapping | None):
        """Hashable fingerprint of θ (None for traced values — uncacheable)."""
        if theta is None:
            return ()
        items = []
        for name in sorted(theta):
            v = theta[name]
            if isinstance(v, jax.core.Tracer):
                return None
            a = np.asarray(v)
            items.append((name, a.dtype.str, a.shape, a.tobytes()))
        return tuple(items)

    def matrices_cached(self, theta: Mapping[str, Array] | None = None, *,
                        joint: bool | None = None,
                        axes: bool | None = None) -> dict:
        """``matrices()`` behind a per-instance cache keyed on θ
        (DESIGN.md §12). The instance already pins the chart geometry and
        the dtype policy, so the full serving cache key
        (chart geometry, θ, dtype policy) is (instance, θ): repeat traffic
        against a fitted posterior rebuilds nothing, a θ change is a miss.
        Traced θ (learning θ inside a jitted step) bypasses the cache —
        the matrices are rebuilt inside the trace exactly as before."""
        tkey = self._theta_key(theta)
        if tkey is None:
            return self.matrices(theta, joint=joint, axes=axes)
        key = (tkey, joint, axes)
        cache = self.__dict__.get("_mats_cache")
        if cache is None:
            cache = {}
            object.__setattr__(self, "_mats_cache", cache)
            object.__setattr__(self, "matrices_cache_stats",
                               {"hits": 0, "misses": 0})
        hit = cache.pop(key, None)  # re-insert below: LRU order
        if hit is not None:
            self.matrices_cache_stats["hits"] += 1
            cache[key] = hit
            return hit
        self.matrices_cache_stats["misses"] += 1
        out = cache[key] = self.matrices(theta, joint=joint, axes=axes)
        while len(cache) > 8:  # bound: don't pin every historical θ's mats
            cache.pop(next(iter(cache)))
        return out

    # -- forward --------------------------------------------------------------
    def _level_axis_mats(self, mats: dict, lvl: int):
        """Per-axis factor convention for level `lvl`: the Kronecker factors
        when built, else the 1-D joint matrices squeezed to the factor
        shapes (a 1-D chart's joint (kept_T, f, c) IS its only factor)."""
        if "Rax" in mats:
            return mats["Rax"][lvl], mats["sqrtDax"][lvl]
        r, d = mats["R"][lvl], mats["sqrtD"][lvl]
        if r.shape[0] == 1:
            r, d = r.reshape(r.shape[-2:]), d.reshape(d.shape[-2:])
        return [r], [d]

    def _refine_levels(self, mats: dict, xi: Sequence[Array], field: Array,
                       *, sample_axis: bool) -> Array:
        """Run every refinement level on `field` (the shared body of
        apply_sqrt and apply_sqrt_batch; `sample_axis` marks a leading
        sample dimension that the kernels consume natively).

        With ``use_pallas``: the pyramid prefix (all early levels whose
        combined working set fits VMEM, DESIGN.md §11) runs as ONE launch,
        then each remaining level goes through ``dispatch.refine`` (buffer
        donation deliberately does not apply to the expansive ping-pong
        chain — see the note in kernels/dispatch.py).
        """
        start = 0
        from repro.kernels import dispatch, pyramid

        if not self.use_pallas:
            dispatch.require_reference_allowed()
            for lvl in range(self.chart.n_levels):
                geom = LevelGeom.for_level(self.chart, lvl)
                ref = lambda f, x: refine_level(
                    f, x, mats["R"][lvl], mats["sqrtD"][lvl], geom)
                field = (jax.vmap(ref)(field, xi[lvl + 1]) if sample_axis
                         else ref(field, xi[lvl + 1]))
            return field

        backend = dispatch.select_backend()
        pol = self.policy if self.dtype_policy is not None else None
        if pol is not None:
            field = field.astype(pol.storage_dtype)
        n_s = field.shape[0] if sample_axis else 1
        itemsize = jnp.dtype(field.dtype).itemsize
        covered = (self.chart.ndim == 1) or ("Rax" in mats)
        cover = (dispatch.pyramid_cover(
            self.chart, have_axis_mats="Rax" in mats, samples=n_s,
            itemsize=itemsize) if self.use_pyramid and covered else None)
        if cover is not None:
            start, s_b = cover
            geoms = [LevelGeom.for_level(self.chart, l)
                     for l in range(start)]
            pmats = [self._level_axis_mats(mats, l) for l in range(start)]
            if pol is not None:
                pmats = pol.cast_storage(pmats)
            field = pyramid.refine_pyramid(
                field, [xi[l + 1] for l in range(start)], pmats, geoms,
                sample_axis=sample_axis, sample_block=s_b,
                interpret=dispatch.pyramid_interpret(backend),
                accum_dtype=(pol.accum_name if pol is not None
                             else "float32"),
            )

        for lvl in range(start, self.chart.n_levels):
            geom = LevelGeom.for_level(self.chart, lvl)
            axis_mats = None
            if "Rax" in mats:
                axis_mats = (mats["Rax"][lvl], mats["sqrtDax"][lvl])
            r = mats["R"][lvl] if "R" in mats else None
            d = mats["sqrtD"][lvl] if "sqrtD" in mats else None
            field = dispatch.refine(
                field, xi[lvl + 1], r, d, geom, axis_mats=axis_mats,
                sample_axis=sample_axis, policy=pol, backend=backend,
            )
            # keep XLA from fusing one level's phase-plane relayouts into
            # the next level's: at 10^8 points such fusions took minutes to
            # compile for a v5e (the dust slab: 224 s, 18 s with barriers)
            field = jax.lax.optimization_barrier(field)
        return field

    def apply_sqrt(self, mats: dict, xi: Sequence[Array]) -> Array:
        """Apply sqrt(K_ICR) to ξ (paper Alg. 1). Returns the finest field."""
        field = level0_field(mats["sqrt0"], xi[0]).reshape(self.chart.shape0)
        return self._refine_levels(mats, xi, field, sample_axis=False)

    def apply_sqrt_batch(self, mats: dict, xi: Sequence[Array]) -> Array:
        """Apply sqrt(K_ICR) to a whole batch of excitations at once.

        xi: ξ-shaped list with a leading sample dimension S on every level
        (``init_xi(key, batch=S)``). Returns (S, *final_shape).

        This is the batched-serving fast path (DESIGN.md §10): with
        ``use_pallas=True`` the sample dimension is threaded through the
        kernels natively — a whole sample slab per VMEM tile, matrices
        fetched once per tile — instead of being lifted into the launch
        grid the way ``jax.vmap(apply_sqrt)`` would. The reference path
        falls back to a vmap of the per-level jnp apply.
        """
        n_s = xi[0].shape[0]
        field = level0_field(mats["sqrt0"], xi[0])
        field = field.reshape((n_s,) + self.chart.shape0)
        return self._refine_levels(mats, xi, field, sample_axis=True)

    def sample_batch(self, key, n: int, theta=None,
                     dtype=None) -> Array:
        """Draw ``n`` approximate GP samples in one batched application —
        (n, *final_shape). Amortizes every matrix load across the batch."""
        return self.apply_sqrt_batch(
            self.matrices(theta), self.init_xi(key, dtype, batch=n))

    def apply_sqrt_T(self, mats: dict, v: Array) -> List[Array]:
        """Apply sqrt(K_ICR)ᵀ to a field-space vector (paper §3.2, Eq. 3).

        The transpose of the generative map — the second half of one
        inference evaluation ("two applications of the square root and its
        VJP", paper §1) and the workhorse of Wiener-filter-style residual
        diagnostics ``sqrt(K)ᵀ (y − s)``. apply_sqrt is linear in ξ at fixed
        matrices, so the VJP at the origin IS the transpose; with
        ``use_pallas=True`` it runs the hand-written adjoint kernels level
        by level in reverse (kernels/icr_refine.py), never the jnp
        reference.

        v: (*final_shape)  ->  ξ-shaped list (see xi_shapes).

        Jitted (cached per instance) so XLA dead-code-eliminates the
        zero-ξ forward the VJP construction would otherwise execute — an
        eager call pays only the adjoint chain.
        """
        fn = self.__dict__.get("_apply_sqrt_T_jit")
        if fn is None:
            def transpose(mats, v):
                zero = self.zero_xi(dtype=v.dtype)
                _, vjp = jax.vjp(lambda xi: self.apply_sqrt(mats, xi), zero)
                return vjp(v)[0]

            fn = jax.jit(transpose)
            object.__setattr__(self, "_apply_sqrt_T_jit", fn)
        return fn(mats, v)

    def _stationary_level(self, lvl: int) -> bool:
        """True iff level `lvl` refines with a single shared stencil.

        Per-level, not per-chart: a charted axis whose family count is 1 at
        some level is stationary there (kept_T == 1), and a single charted
        axis makes the whole level non-stationary even when every other axis
        is invariant (the old ``all(chart.invariant)`` ignored `lvl` and
        both of these cases).
        """
        geom = LevelGeom.for_level(self.chart, lvl)
        return all(k == 1 for k in geom.kept_T)

    def __call__(self, xi: Sequence[Array],
                 theta: Mapping[str, Array] | None = None) -> Array:
        return self.apply_sqrt(self.matrices(theta), xi)

    def sample(self, key, theta=None, dtype=None) -> Array:
        """Draw one approximate GP sample (paper Alg. 1; dtype defaults to
        the policy's storage dtype)."""
        return self(self.init_xi(key, dtype), theta)

    # -- diagnostics ----------------------------------------------------------
    def implicit_sqrt(self, theta=None, dtype=jnp.float64) -> Array:
        """Dense sqrt(K_ICR) as an (N, n_xi) matrix via one jacobian.

        Only for small N (validation vs. the exact kernel, paper §5.1).
        """
        mats = self.matrices(theta)
        shapes = self.xi_shapes()
        sizes = [int(np.prod(s)) for s in shapes]

        def flat_apply(xi_flat):
            xs, o = [], 0
            for s, n in zip(shapes, sizes):
                xs.append(xi_flat[o : o + n].reshape(s))
                o += n
            return self.apply_sqrt(mats, xs).reshape(-1)

        return jax.jacfwd(flat_apply)(jnp.zeros(sum(sizes), dtype))

    def implicit_cov(self, theta=None, dtype=jnp.float64) -> Array:
        """Dense K_ICR = sqrt(K_ICR) sqrt(K_ICR)ᵀ (paper Fig. 3)."""
        a = self.implicit_sqrt(theta, dtype)
        return a @ a.T
