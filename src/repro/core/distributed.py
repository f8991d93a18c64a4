"""Distributed ICR: spatial sharding with halo exchange (DESIGN.md §6).

The paper's 122-billion-DOF application (§6, ref [24]) needs the refinement
to run across pods. ICR's conditioning is *local* (each family reads n_csz
coarse neighbors), so the natural distribution is a spatial domain
decomposition: every device owns a contiguous block along one chart axis and
each refinement level exchanges a ``b = (n_csz-1)//2`` halo with its ring
neighbors via ``lax.ppermute`` — O(b) elements per device per level,
independent of N. Interior compute is identical to the single-device path,
so ``sharded == unsharded`` exactly (tests/test_distributed_icr.py).

Requirements: ``boundary="reflect"`` (uniform 2x level sizes) and the family
count along the shard axis divisible by the device count from the first
sharded level on (doubling preserves divisibility). Earlier (tiny) levels are
computed replicated on every device — identical math, no communication.

Multi-pod: the shard axis may span several mesh axes (e.g. ("pod", "space"));
the halo ppermute runs over the flattened ring, so cross-pod boundaries are
just two of the 512 ring edges (DCN links), everything else stays on ICI.
"""
from __future__ import annotations

import dataclasses
from typing import Sequence

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.compat import shard_map

from .charts import Chart
from .icr import ICR, level0_field
from .refine import LevelGeom, refine_level

Array = jnp.ndarray


@dataclasses.dataclass(frozen=True)
class DistributedICR:
    """Spatially sharded wrapper around an ICR model.

    Attributes:
      icr: the underlying model; its chart must use boundary="reflect".
      mesh: device mesh.
      axis_names: mesh axis name(s) forming the spatial ring (flattened).
      shard_axis: which chart axis is decomposed (default: the largest).
    """

    icr: ICR
    mesh: Mesh
    axis_names: tuple = ("space",)
    shard_axis: int = 0

    def __post_init__(self):
        if self.icr.chart.boundary != "reflect":
            raise ValueError("DistributedICR requires boundary='reflect'")
        if isinstance(self.axis_names, str):
            object.__setattr__(self, "axis_names", (self.axis_names,))

    # -- partitioning geometry -------------------------------------------------
    @property
    def n_dev(self) -> int:
        return int(np.prod([self.mesh.shape[a] for a in self.axis_names]))

    @property
    def chart(self) -> Chart:
        return self.icr.chart

    def first_sharded_level(self) -> int:
        """First level whose *input* (coarse grid) is sharded.

        Constraints: family count divisible by the ring size, and the
        per-device coarse block must cover the halo + edge reflection
        (block >= b + 1) so halos are single-hop.
        """
        c = self.chart
        for lvl in range(c.n_levels):
            t = c.family_count(lvl, self.shard_axis)
            blk = c.shape(lvl)[self.shard_axis] // self.n_dev
            if t % self.n_dev == 0 and t >= self.n_dev and blk >= c.b + 1:
                return lvl
        raise ValueError(
            f"no refinement level is shardable over {self.n_dev} devices "
            f"along axis {self.shard_axis} (need family count divisible by "
            f"the ring and a coarse block >= {c.b + 1}); grow shape0 or "
            "reduce devices"
        )

    def xi_structure(self):
        """Per-level xi shapes (families kept *shaped*, not flattened):
        level 0: shape0-prod vector; level l>=1: (*T_l, n_fsz^d)."""
        c = self.chart
        nd = c.ndim
        shapes = [(int(np.prod(c.shape0)),)]
        for lvl in range(c.n_levels):
            t = tuple(c.family_count(lvl, a) for a in range(nd))
            shapes.append(t + (c.n_fsz**nd,))
        return shapes

    def xi_specs(self):
        """PartitionSpec per xi leaf: replicated until first sharded level."""
        k = self.first_sharded_level()
        specs = [P()]  # level-0 excitation replicated
        for lvl in range(self.chart.n_levels):
            if lvl < k:
                specs.append(P())
            else:
                spec = [None] * (self.chart.ndim + 1)
                spec[self.shard_axis] = self.axis_names
                specs.append(P(*spec))
        return specs

    def uses_axis_factors(self) -> bool:
        """The kernel path of an N-D chart refines through the per-axis
        Kronecker factors (the fused N-D routes), never the joint
        matrices."""
        return self.icr.use_pallas and self.chart.ndim > 1

    def mat_specs(self):
        """PartitionSpecs for the refinement-matrix pytree: per-family
        matrices along a charted shard axis are sharded with the field
        from the first sharded level on, everything else is replicated."""
        c = self.chart
        k = self.first_sharded_level()
        charted_shard = not c.invariant[self.shard_axis]
        if self.uses_axis_factors():
            rs_specs, ds_specs = [], []
            for lvl in range(c.n_levels):
                specs = [P()] * c.ndim
                if lvl >= k and charted_shard:
                    specs[self.shard_axis] = P(self.axis_names)
                rs_specs.append(list(specs))
                ds_specs.append(list(specs))
            return {"sqrt0": P(), "Rax": rs_specs, "sqrtDax": ds_specs}
        r_specs, d_specs = [], []
        for lvl in range(c.n_levels):
            if lvl >= k and charted_shard:
                spec = [None] * (c.ndim + 2)
                spec[self.shard_axis] = self.axis_names
                r_specs.append(P(*spec))
                d_specs.append(P(*spec))
            else:
                r_specs.append(P())
                d_specs.append(P())
        return {"sqrt0": P(), "R": r_specs, "sqrtD": d_specs}

    def out_spec(self):
        spec = [None] * self.chart.ndim
        spec[self.shard_axis] = self.axis_names
        return P(*spec)

    def shardings(self):
        """NamedShardings for (matrices, xi, out) — feed these to jax.jit."""
        ns = lambda spec: NamedSharding(self.mesh, spec)
        mats = jax.tree.map(ns, self.mat_specs(),
                            is_leaf=lambda x: isinstance(x, P))
        xis = [ns(s) for s in self.xi_specs()]
        return mats, xis, ns(self.out_spec())

    def _refine(self, field: Array, xl: Array, mats: dict, lvl: int,
                geom: LevelGeom) -> Array:
        """Interior compute of one level — per-device, identical math to the
        single-device path. With ``icr.use_pallas`` it goes through
        ``dispatch.refine`` (the fused kernels on every device, honoring the
        dtype policy; N-D levels get their per-axis factors), else straight
        to ``refine_level``."""
        from repro.kernels import dispatch

        if not self.icr.use_pallas:
            dispatch.require_reference_allowed()
            return refine_level(field, xl, mats["R"][lvl],
                                mats["sqrtD"][lvl], geom)
        pol = (self.icr.policy if self.icr.dtype_policy is not None
               else None)
        if "Rax" in mats:
            return dispatch.refine(
                field, xl, None, None, geom, policy=pol,
                axis_mats=(mats["Rax"][lvl], mats["sqrtDax"][lvl]))
        return dispatch.refine(field, xl, mats["R"][lvl],
                               mats["sqrtD"][lvl], geom, policy=pol)

    # -- the sharded program ----------------------------------------------------
    def _halo_exchange(self, local: Array, b: int) -> Array:
        """Append ring halos of width b along shard_axis; global edges use
        local reflection (= the chart's reflect boundary)."""
        ax, names = self.shard_axis, self.axis_names
        n = self.n_dev
        idx = lax.axis_index(names)

        def take(arr, sl):
            ind = [slice(None)] * arr.ndim
            ind[ax] = sl
            return arr[tuple(ind)]

        def rev(arr):
            ind = [slice(None)] * arr.ndim
            ind[ax] = slice(None, None, -1)
            return arr[tuple(ind)]

        fwd = [(i, i + 1) for i in range(n - 1)]
        bwd = [(i + 1, i) for i in range(n - 1)]
        from_left = lax.ppermute(take(local, slice(-b, None)), names, fwd)
        from_right = lax.ppermute(take(local, slice(None, b)), names, bwd)
        # reflect at the global edges (chart reflect boundary condition)
        left = jnp.where(idx == 0, rev(take(local, slice(1, b + 1))),
                         from_left)
        right = jnp.where(idx == n - 1,
                          rev(take(local, slice(-b - 1, -1))), from_right)
        return jnp.concatenate([left, local, right], axis=ax)

    def _local_geom(self, lvl: int, sharded: bool) -> LevelGeom:
        """Geometry of the per-device refine: the local block is pre-padded
        on every axis, so window extraction is plain 'shrink' indexing."""
        c = self.chart
        nd = c.ndim
        t = [c.family_count(lvl, a) for a in range(nd)]
        kept = tuple(
            1 if c.invariant[a] else t[a] for a in range(nd)
        )
        coarse = list(c.shape(lvl))
        fine = list(c.shape(lvl + 1))
        if sharded:
            t[self.shard_axis] //= self.n_dev
            coarse[self.shard_axis] //= self.n_dev
            fine[self.shard_axis] //= self.n_dev
            if not c.invariant[self.shard_axis]:
                kept = tuple(
                    t[a] if a == self.shard_axis else kept[a]
                    for a in range(nd)
                )
        padded = tuple(coarse[a] + 2 * c.b for a in range(nd))
        return LevelGeom(
            coarse_shape=padded, fine_shape=tuple(fine), T=tuple(t),
            kept_T=kept, n_csz=c.n_csz, n_fsz=c.n_fsz, stride=c.stride,
            b=c.b, boundary="shrink",
        )

    def _pad_unsharded_axes(self, local: Array) -> Array:
        c = self.chart
        pads = [(c.b, c.b)] * c.ndim
        pads[self.shard_axis] = (0, 0)
        return jnp.pad(local, pads, mode="reflect")

    def _sharded_body(self, mats: dict, xi: Sequence[Array]) -> Array:
        c = self.chart
        nd = c.ndim
        k = self.first_sharded_level()
        fsz = c.n_fsz**nd

        # replicated prologue (levels < k): identical on every device
        field = level0_field(mats["sqrt0"], xi[0]).reshape(c.shape0)
        for lvl in range(k):
            geom = LevelGeom.for_level(c, lvl)
            xl = xi[lvl + 1].reshape(-1, fsz)
            field = self._refine(field, xl, mats, lvl, geom)

        # transition: slice my block along shard_axis
        blk = c.shape(k)[self.shard_axis] // self.n_dev
        idx = lax.axis_index(self.axis_names)
        field = lax.dynamic_slice_in_dim(field, idx * blk, blk,
                                         axis=self.shard_axis)

        # sharded levels with halo exchange
        for lvl in range(k, c.n_levels):
            padded = self._halo_exchange(field, c.b)
            padded = self._pad_unsharded_axes(padded)
            geom = self._local_geom(lvl, sharded=True)
            xl = xi[lvl + 1].reshape(-1, fsz)
            field = self._refine(padded, xl, mats, lvl, geom)
        return field

    def apply_sqrt(self, mats: dict, xi: Sequence[Array]) -> Array:
        """shard_map'd sqrt(K_ICR) application. xi leaves must be laid out per
        ``xi_structure()``; use ``shardings()`` to place them."""
        mat_specs = self.mat_specs()
        xi_specs = self.xi_specs()

        # inside shard_map, sharded xi arrive as local blocks along shard_axis
        fn = shard_map(
            self._sharded_body,
            mesh=self.mesh,
            in_specs=(mat_specs, tuple(xi_specs)),
            out_specs=self.out_spec(),
            check_vma=False,
        )
        return fn(mats, tuple(xi))

    def init_xi(self, key, dtype=None):
        dtype = self.icr.policy.storage_dtype if dtype is None else dtype
        shapes = self.xi_structure()
        keys = jax.random.split(key, len(shapes))
        _, xi_sh, _ = self.shardings()
        return [
            jax.device_put(jax.random.normal(k, s, dtype), sh)
            for k, s, sh in zip(keys, shapes, xi_sh)
        ]

    def matrices(self, theta=None):
        # the kernel path of an N-D chart needs only the per-axis factors;
        # the reference path (use_pallas=False) the joint matrices
        axes = self.uses_axis_factors()
        mats = self.icr.matrices(theta, joint=not axes, axes=axes)
        mat_sh, _, _ = self.shardings()
        return jax.tree.map(jax.device_put, mats, mat_sh)

    def sample(self, key, theta=None, dtype=jnp.float32) -> Array:
        return self.apply_sqrt(self.matrices(theta), self.init_xi(key, dtype))
