"""Coordinate charts and the regular refinement grid ladder (paper §4.2–4.3).

ICR refines a ladder of *regular Euclidean grids* (the chart codomain). The
user-provided chart ``phi_inv`` maps chart coordinates to the modeled space
``D``; the kernel is always evaluated at charted positions
``k(phi_inv(x), phi_inv(x'))`` (paper §4.3).

Geometry convention (paper §4.1, §4.4 and Fig. 1/2):

* level-l grid: per-axis size ``N_l``, spacing ``Δ_l``, origin ``o_l``.
* one refinement *family* sits on a central coarse pixel ``i`` and conditions
  ``n_fsz`` fine pixels on the ``n_csz`` nearest coarse pixels
  (``i-b … i+b`` with ``b = (n_csz-1)//2``).
* fine pixels have **half the coarse pixel volume** (paper §5.1): fine spacing
  is ``Δ_l / 2`` always; a family's children sit at
  ``c_i + (k - (n_fsz-1)/2) · Δ_l/2``. Consecutive families therefore stride
  ``n_fsz//2`` coarse pixels, which keeps the fine level a *regular* grid of
  spacing ``Δ_l/2`` (for (3,2) this reduces exactly to paper Eq. 11–13:
  ``N_{l+1} = 2 (N_l - 2)``).

Boundary handling:

* ``"shrink"`` — paper-faithful: border pixels without a full neighborhood are
  not refined, the grid loses ``n_csz - 1`` pixels per level (paper §4.2).
* ``"reflect"`` — production/sharded path: *every* stride-th pixel anchors a
  family; edge neighborhoods reflect out-of-range indices. The interior math
  is identical to "shrink"; only O(b) border families per level differ. This
  makes every refinement level an exact 2x of its parent, so spatial sharding
  is uniform across devices (see core/distributed.py).
"""
from __future__ import annotations

import dataclasses
import math
from typing import Callable

import numpy as np
import jax.numpy as jnp


def _as_tuple(x, ndim, name):
    if x is None:
        return None
    if np.isscalar(x):
        return (x,) * ndim
    t = tuple(x)
    if len(t) != ndim:
        raise ValueError(f"{name} must have length {ndim}, got {t}")
    return t


@dataclasses.dataclass(frozen=True)
class Chart:
    """Refinement grid ladder + coordinate chart.

    Attributes:
      shape0: per-axis level-0 grid size.
      n_levels: number of refinement steps (level 0 is the exact coarse grid).
      n_csz: coarse neighborhood size per axis (odd, >= 3).
      n_fsz: fine family size per axis (even, >= 2).
      delta0: level-0 spacing per axis in chart units.
      origin0: chart coordinate of pixel 0 per axis.
      boundary: "shrink" (paper) or "reflect" (uniform 2x, shardable).
      phi_inv: chart map, ``(..., ndim) -> (..., dim_D)``; ``None`` = identity.
      invariant: per-axis flags; True means the chart/kernel are translation
        invariant along that axis so refinement matrices are computed once and
        broadcast (paper §4.3 symmetry optimization).
    """

    shape0: tuple
    n_levels: int
    n_csz: int = 3
    n_fsz: int = 2
    delta0: tuple = None
    origin0: tuple = None
    boundary: str = "shrink"
    phi_inv: Callable = None
    invariant: tuple = None

    def __post_init__(self):
        shape0 = (self.shape0,) if np.isscalar(self.shape0) else tuple(self.shape0)
        object.__setattr__(self, "shape0", shape0)
        nd = len(shape0)
        object.__setattr__(
            self, "delta0", _as_tuple(self.delta0, nd, "delta0") or (1.0,) * nd
        )
        object.__setattr__(
            self, "origin0", _as_tuple(self.origin0, nd, "origin0") or (0.0,) * nd
        )
        inv = self.invariant
        if inv is None:
            # identity chart => fully invariant; custom chart => not invariant
            inv = (self.phi_inv is None,) * nd
        object.__setattr__(self, "invariant", _as_tuple(inv, nd, "invariant"))
        if self.n_csz % 2 != 1 or self.n_csz < 3:
            raise ValueError("n_csz must be odd and >= 3")
        if self.n_fsz % 2 != 0 or self.n_fsz < 2:
            raise ValueError("n_fsz must be even and >= 2")
        if self.boundary not in ("shrink", "reflect"):
            raise ValueError(f"unknown boundary {self.boundary!r}")
        for lvl in range(self.n_levels):
            for n in self.shape(lvl):
                if n < self.n_csz:
                    raise ValueError(
                        f"level {lvl} has size {n} < n_csz={self.n_csz}; "
                        "increase shape0 or reduce n_levels"
                    )

    # -- static geometry ----------------------------------------------------
    @property
    def ndim(self) -> int:
        return len(self.shape0)

    @property
    def b(self) -> int:
        return (self.n_csz - 1) // 2

    @property
    def stride(self) -> int:
        return self.n_fsz // 2

    def family_count(self, level: int, axis: int) -> int:
        """Number of refinement families along `axis` refining level `level`."""
        n = self.shape(level)[axis]
        if self.boundary == "shrink":
            return (n - 2 * self.b - 1) // self.stride + 1
        if n % self.stride != 0:
            raise ValueError(
                f"reflect boundary requires size % (n_fsz//2) == 0, got {n}"
            )
        return n // self.stride

    def shape(self, level: int) -> tuple:
        """Per-axis grid size at `level` (0 = coarsest)."""
        s = self.shape0
        for lvl in range(level):
            s = tuple(
                self.n_fsz * self._family_count_for(n)
                for n in s
            )
        return s

    def _family_count_for(self, n: int) -> int:
        if self.boundary == "shrink":
            return (n - 2 * self.b - 1) // self.stride + 1
        return n // self.stride

    def delta(self, level: int) -> tuple:
        return tuple(d / (2.0**level) for d in self.delta0)

    def origin(self, level: int) -> tuple:
        o = list(self.origin0)
        for lvl in range(level):
            for a in range(self.ndim):
                da = self.delta0[a] / (2.0**lvl)
                anchor0 = self.b if self.boundary == "shrink" else 0
                o[a] = o[a] + anchor0 * da - (self.n_fsz - 1) * da / 4.0
        return tuple(o)

    @property
    def final_shape(self) -> tuple:
        return self.shape(self.n_levels)

    @property
    def size(self) -> int:
        return int(np.prod(self.final_shape))

    # -- chart coordinates ---------------------------------------------------
    def axis_coords(self, level: int, axis: int) -> np.ndarray:
        """Chart coordinates of all pixels along `axis` at `level`."""
        n = self.shape(level)[axis]
        return self.origin(level)[axis] + np.arange(n) * self.delta(level)[axis]

    def _family_centers_idx(self, level: int, axis: int,
                            families=None) -> np.ndarray:
        t = (np.arange(self.family_count(level, axis)) if families is None
             else np.asarray(families))
        anchor0 = self.b if self.boundary == "shrink" else 0
        return anchor0 + t * self.stride

    def axis_coarse_windows(self, level: int, axis: int,
                            families=None) -> np.ndarray:
        """(T_a, n_csz) chart coords of each family's coarse neighbors
        (only of the family indices ``families``, when given)."""
        n = self.shape(level)[axis]
        centers = self._family_centers_idx(level, axis, families)
        idx = centers[:, None] + np.arange(-self.b, self.b + 1)[None, :]
        if self.boundary == "reflect":
            idx = np.abs(idx)
            idx = np.minimum(idx, 2 * (n - 1) - idx)
        else:
            assert (idx >= 0).all() and (idx < n).all()
        return self.origin(level)[axis] + idx * self.delta(level)[axis]

    def axis_fine_windows(self, level: int, axis: int,
                          families=None) -> np.ndarray:
        """(T_a, n_fsz) chart coords of each family's fine children (only
        of the family indices ``families``, when given)."""
        centers = self._family_centers_idx(level, axis, families)
        d = self.delta(level)[axis]
        c = self.origin(level)[axis] + centers * d
        off = (np.arange(self.n_fsz) - (self.n_fsz - 1) / 2.0) * d / 2.0
        return c[:, None] + off[None, :]

    def grid_positions(self, level: int) -> jnp.ndarray:
        """All charted positions at `level`, shape (prod(shape_l), dim_D).

        Only call on small levels (tests, level-0 exact sqrt).
        """
        axes = [self.axis_coords(level, a) for a in range(self.ndim)]
        mesh = np.stack(np.meshgrid(*axes, indexing="ij"), axis=-1)
        pts = mesh.reshape(-1, self.ndim)
        return self.map_to_D(jnp.asarray(pts))

    def map_to_D(self, chart_pts: jnp.ndarray) -> jnp.ndarray:
        """Map chart coordinates (..., ndim) to the modeled space D."""
        if self.phi_inv is None:
            return chart_pts
        out = self.phi_inv(chart_pts)
        if out.ndim == chart_pts.ndim - 1:  # scalar-valued map
            out = out[..., None]
        return out


# -- common chart constructors ------------------------------------------------
def regular_chart(shape0, n_levels, *, n_csz=3, n_fsz=2, delta0=1.0,
                  boundary="shrink") -> Chart:
    """Identity chart: regularly spaced modeled points (paper §4.1–4.2)."""
    return Chart(shape0=shape0, n_levels=n_levels, n_csz=n_csz, n_fsz=n_fsz,
                 delta0=delta0, boundary=boundary, phi_inv=None)


def log_chart(shape0, n_levels, *, n_csz=3, n_fsz=2, delta0=1.0, origin0=0.0,
              base_scale=1.0, boundary="shrink") -> Chart:
    """1-D logarithmic chart: ``phi_inv(x) = base_scale * exp(x)``.

    This is the paper's §5 experimental setup — nearest-neighbor distances of
    the modeled points vary exponentially along the grid.
    """

    def phi_inv(x):
        return base_scale * jnp.exp(x)

    return Chart(shape0=shape0, n_levels=n_levels, n_csz=n_csz, n_fsz=n_fsz,
                 delta0=delta0, origin0=origin0, boundary=boundary,
                 phi_inv=phi_inv, invariant=(False,))


def log_polar_chart(shape0, n_levels, *, n_csz=3, n_fsz=2, delta_logr=0.05,
                    origin_logr=0.0, boundary="reflect") -> Chart:
    """2-D chart (log-r, azimuth) -> R^2; azimuth axis is *rotation* invariant
    only at fixed r, so neither axis is globally invariant; we still mark the
    angular axis non-invariant and rely on per-pixel matrices. Used in tests.
    """

    def phi_inv(x):
        r = jnp.exp(x[..., 0])
        phi = x[..., 1]
        return jnp.stack([r * jnp.cos(phi), r * jnp.sin(phi)], axis=-1)

    n_phi = shape0[1] if not np.isscalar(shape0) else shape0
    return Chart(shape0=shape0, n_levels=n_levels, n_csz=n_csz, n_fsz=n_fsz,
                 delta0=(delta_logr, 2 * math.pi / n_phi),
                 origin0=(origin_logr, 0.0), boundary=boundary,
                 phi_inv=phi_inv, invariant=(False, False))


def galactic_dust_chart(shape0, n_levels, *, n_csz=5, n_fsz=4,
                        delta_logr=0.02, origin_logr=0.0,
                        angular_extent=1.0, boundary="reflect") -> Chart:
    """3-D (log-r, u, v) chart used for the Galactic dust application
    (paper §6, ref [24]): logarithmic radial axis, locally-flat angular axes.

    The angular axes are treated as translation invariant (flat-sky
    approximation at each radial shell scaled into the chart), so refinement
    matrices are computed per-radial-pixel only and broadcast over angles —
    the §4.3 symmetry optimization that made the 122-billion-DOF run feasible.
    """

    def phi_inv(x):
        # Radial distance enters the kernel in log-space-scaled Euclidean
        # coordinates: locally the metric is ~ (dr, r*du, r*dv); we absorb the
        # r factor into the invariant-axis approximation and use chart-space
        # distances scaled by base radius. Distances along (u, v) are chart
        # distances (flat patch); along log-r we map to true radii.
        r = jnp.exp(x[..., 0])
        return jnp.stack([r, x[..., 1], x[..., 2]], axis=-1)

    d_ang = angular_extent / (shape0[1] if not np.isscalar(shape0) else shape0)
    return Chart(shape0=shape0, n_levels=n_levels, n_csz=n_csz, n_fsz=n_fsz,
                 delta0=(delta_logr, d_ang, d_ang),
                 origin0=(origin_logr, 0.0, 0.0), boundary=boundary,
                 phi_inv=phi_inv, invariant=(False, True, True))
