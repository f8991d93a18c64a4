"""The plain float32 reference the benchmark holds the program to.

It imports nothing of the program. Chart geometry, the kernel, the
refinement matrices (paper Eq. 7/8), the level-0 square root, one
refinement application (Eq. 9) and its transpose are written here from the
paper's definitions; the matrix construction keeps the program's order of
operations (eager level-0 kernel matrix, host eigh, one jitted vmap per
level and axis), because the square roots of near-singular matrices are
only fixed up to rounding: a different order gives a different but equally
valid square root, and samples of the same excitation would then differ
by far more than rounding.

Refinement is applied with strided slices and elementwise products, so
its only rounding is float32 accumulation; every matrix product of the
reference goes through ``dot``, which runs at ``highest`` and, for the
control, at ``high``: three bfloat16 passes, written out so that the
control reads the same on any backend.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, List

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax

HIGHEST = "highest"
HIGH = "high"


# -- precision ------------------------------------------------------------------
def _split_bf16(x):
    hi = x.astype(jnp.bfloat16).astype(jnp.float32)
    lo = (x - hi).astype(jnp.bfloat16).astype(jnp.float32)
    return hi, lo


def dot(a, b, precision: str = HIGHEST):
    """``a @ b`` at ``highest``, or at ``high``: the three bfloat16 passes
    hi·hi + hi·lo + lo·hi that a TPU runs for that precision."""
    if precision == HIGHEST:
        return a @ b
    if precision != HIGH:
        raise ValueError(f"unknown precision {precision!r}")
    ah, al = _split_bf16(a)
    bh, bl = _split_bf16(b)
    hp = lax.Precision.HIGHEST
    return (jnp.matmul(ah, bh, precision=hp) + jnp.matmul(ah, bl, precision=hp)
            + jnp.matmul(al, bh, precision=hp))


# -- charts -----------------------------------------------------------------------
def _phi_identity(x):
    return x


def _phi_log(x):
    return 1.0 * jnp.exp(x)


def _phi_dust(x):
    r = jnp.exp(x[..., 0])
    return jnp.stack([r, x[..., 1], x[..., 2]], axis=-1)


@dataclasses.dataclass(frozen=True)
class Geometry:
    """A refinement grid ladder and its chart (paper §4.2-4.3)."""

    shape0: tuple
    n_levels: int
    n_csz: int
    n_fsz: int
    delta0: tuple
    origin0: tuple
    boundary: str
    invariant: tuple
    phi: Callable

    @property
    def ndim(self) -> int:
        return len(self.shape0)

    @property
    def b(self) -> int:
        return (self.n_csz - 1) // 2

    @property
    def stride(self) -> int:
        return self.n_fsz // 2

    def _families(self, n: int) -> int:
        if self.boundary == "shrink":
            return (n - 2 * self.b - 1) // self.stride + 1
        if n % self.stride:
            raise ValueError(f"reflect needs size % stride == 0, got {n}")
        return n // self.stride

    def shape(self, level: int) -> tuple:
        s = self.shape0
        for _ in range(level):
            s = tuple(self.n_fsz * self._families(n) for n in s)
        return s

    def family_count(self, level: int, axis: int) -> int:
        return self._families(self.shape(level)[axis])

    def families(self, level: int) -> tuple:
        return tuple(self.family_count(level, a) for a in range(self.ndim))

    @property
    def final_shape(self) -> tuple:
        return self.shape(self.n_levels)

    @property
    def size(self) -> int:
        return int(np.prod(self.final_shape))

    def xi_shapes(self) -> List[tuple]:
        out = [(int(np.prod(self.shape0)),)]
        for lvl in range(self.n_levels):
            out.append((int(np.prod(self.families(lvl))),
                        self.n_fsz ** self.ndim))
        return out

    def delta(self, level: int) -> tuple:
        return tuple(d / (2.0 ** level) for d in self.delta0)

    def origin(self, level: int) -> tuple:
        o = list(self.origin0)
        anchor0 = self.b if self.boundary == "shrink" else 0
        for lvl in range(level):
            for a in range(self.ndim):
                da = self.delta0[a] / (2.0 ** lvl)
                o[a] = o[a] + anchor0 * da - (self.n_fsz - 1) * da / 4.0
        return tuple(o)

    def axis_coords(self, level: int, axis: int) -> np.ndarray:
        n = self.shape(level)[axis]
        return self.origin(level)[axis] + np.arange(n) * self.delta(level)[axis]

    def _centers(self, level, axis, fams):
        t = (np.arange(self.family_count(level, axis)) if fams is None
             else np.asarray(fams))
        return (self.b if self.boundary == "shrink" else 0) + t * self.stride

    def coarse_windows(self, level, axis, fams=None) -> np.ndarray:
        n = self.shape(level)[axis]
        idx = (self._centers(level, axis, fams)[:, None]
               + np.arange(-self.b, self.b + 1)[None, :])
        if self.boundary == "reflect":
            idx = np.abs(idx)
            idx = np.minimum(idx, 2 * (n - 1) - idx)
        return self.origin(level)[axis] + idx * self.delta(level)[axis]

    def fine_windows(self, level, axis, fams=None) -> np.ndarray:
        d = self.delta(level)[axis]
        c = self.origin(level)[axis] + self._centers(level, axis, fams) * d
        off = (np.arange(self.n_fsz) - (self.n_fsz - 1) / 2.0) * d / 2.0
        return c[:, None] + off[None, :]

    def map_to_d(self, pts):
        out = self.phi(pts)
        if out.ndim == pts.ndim - 1:
            out = out[..., None]
        return out


CHARTS = ("regular_chart", "log_chart", "galactic_dust_chart")


def geometry(cfg: dict) -> Geometry:
    """The geometry a configuration file names (``chart`` and ``args``)."""
    kind, a = cfg["chart"], dict(cfg["args"])
    shape0 = tuple(a["shape0"]) if isinstance(a["shape0"], list) \
        else (a["shape0"],)
    nd = len(shape0)
    common = dict(shape0=shape0, n_levels=a["n_levels"], n_csz=a["n_csz"],
                  n_fsz=a["n_fsz"], boundary=a["boundary"])
    if kind == "regular_chart":
        return Geometry(delta0=(float(a["delta0"]),) * nd,
                        origin0=(0.0,) * nd, invariant=(True,) * nd,
                        phi=_phi_identity, **common)
    if kind == "log_chart":
        if nd != 1 or a.get("base_scale", 1.0) != 1.0:
            raise ValueError("log_chart: 1-D with base_scale 1.0 only")
        return Geometry(delta0=(float(a["delta0"]),),
                        origin0=(float(a["origin0"]),), invariant=(False,),
                        phi=_phi_log, **common)
    if kind == "galactic_dust_chart":
        d_ang = a["angular_extent"] / shape0[1]
        return Geometry(delta0=(a["delta_logr"], d_ang, d_ang),
                        origin0=(a["origin_logr"], 0.0, 0.0),
                        invariant=(False, True, True), phi=_phi_dust,
                        **common)
    raise ValueError(f"unknown chart {kind!r} (known: {CHARTS})")


# -- kernel -------------------------------------------------------------------------
def matern32(rho: float, sigma: float = 1.0):
    """Matérn-3/2 of distance (paper Eq. 14)."""

    def k(d):
        z = jnp.sqrt(3.0) * d / rho
        return sigma ** 2 * (1.0 + z) * jnp.exp(-z)

    return k


def kernel_fn(cfg: dict):
    if cfg["kernel"] != "matern32":
        raise ValueError(f"kernel {cfg['kernel']!r}: only matern32")
    return matern32(float(cfg["rho"]), float(cfg.get("sigma", 1.0)))


def kernel_matrix(k, x, y=None):
    y = x if y is None else y
    d = jnp.linalg.norm(x[:, None, :] - y[None, :, :], axis=-1)
    return k(d)


# -- matrices -----------------------------------------------------------------------
def _psd_sqrt(mat, eps):
    evals, evecs = jnp.linalg.eigh(mat)
    evals = jnp.maximum(evals, eps)
    return evecs * jnp.sqrt(evals)[..., None, :]


def _family_mats(k, cpos, fpos, jitter, precision, k0=None):
    k_cc = kernel_matrix(k, cpos)
    k_fc = kernel_matrix(k, fpos, cpos)
    k_ff = kernel_matrix(k, fpos)
    csz = k_cc.shape[0]
    eps = jitter * jnp.mean(jnp.diag(k_cc))
    k_cc = k_cc + eps * jnp.eye(csz, dtype=k_cc.dtype)
    r = jnp.linalg.solve(k_cc, k_fc.T).T
    d = k_ff - dot(r, k_fc.T, precision)
    d = 0.5 * (d + d.T)
    if k0 is not None:  # the variance enters the Kronecker product once
        d = d / k0
        k_ff = k_ff / k0
    return r, _psd_sqrt(d, jitter * jnp.mean(jnp.diag(k_ff)))


def level0_sqrt(geom: Geometry, k, jitter: float):
    """Square root of the dense level-0 kernel matrix, eigh on the host."""
    axes = [geom.axis_coords(0, a) for a in range(geom.ndim)]
    pts = np.stack(np.meshgrid(*axes, indexing="ij"), -1).reshape(-1,
                                                                 geom.ndim)
    kmat = kernel_matrix(k, geom.map_to_d(jnp.asarray(pts)))
    kmat, eps = 0.5 * (kmat + kmat.T), jitter * jnp.mean(jnp.diag(kmat))
    if jax.default_backend() == "cpu":
        return _psd_sqrt(kmat, eps)
    cpu = jax.devices("cpu")[0]
    dev = next(iter(kmat.devices()))
    return jax.device_put(_psd_sqrt(jax.device_put(kmat, cpu),
                                    jax.device_put(eps, cpu)), dev)


FAMILY_CHUNK = 1 << 18


def joint_mats_1d(geom: Geometry, k, level: int, jitter: float,
                  precision: str):
    """(R, sqrtD) of every family of a 1-D level: (T', f, c), (T', f, f)."""
    fams = [min(geom.family_count(level, 0) - 1, geom.b)] \
        if geom.invariant[0] else None
    cws = jnp.asarray(geom.coarse_windows(level, 0, fams))
    fws = jnp.asarray(geom.fine_windows(level, 0, fams))

    def one(cw, fw):
        cpos = geom.map_to_d(jnp.stack([cw], axis=-1))
        fpos = geom.map_to_d(jnp.stack([fw], axis=-1))
        return _family_mats(k, cpos, fpos, jitter, precision)

    fn = jax.jit(jax.vmap(one))
    n = cws.shape[0]
    if n <= FAMILY_CHUNK:
        return fn(cws, fws)
    parts = [fn(cws[i:i + FAMILY_CHUNK], fws[i:i + FAMILY_CHUNK])
             for i in range(0, n, FAMILY_CHUNK)]
    return (jnp.concatenate([p[0] for p in parts]),
            jnp.concatenate([p[1] for p in parts]))


def axis_mats(geom: Geometry, k, level: int, jitter: float, precision: str):
    """Per-axis factors of an N-D level: the Kronecker-factored refinement,
    each axis' 1-D matrices with the other coordinates at the grid middle.
    Returns ([R_a], [sqrtD_a]), each (T_a, f, c) / (T_a, f, f), T_a = 1 on
    invariant axes."""
    nd = geom.ndim
    k0 = kernel_matrix(k, jnp.zeros((1, max(1, nd))))[0, 0]
    rep = [geom.axis_coords(level, o)[geom.shape(level)[o] // 2]
           for o in range(nd)]
    rs, ds = [], []
    for a in range(nd):
        fams = ([min(geom.family_count(level, a) - 1, geom.b)]
                if geom.invariant[a] else None)
        cw = jnp.asarray(geom.coarse_windows(level, a, fams))
        fw = jnp.asarray(geom.fine_windows(level, a, fams))

        def one(cw_t, fw_t, axis=a):
            def pts(w):
                cols = [w if o == axis else jnp.full(w.shape, rep[o], w.dtype)
                        for o in range(nd)]
                return geom.map_to_d(jnp.stack(cols, axis=-1))

            return _family_mats(k, pts(cw_t), pts(fw_t), jitter, precision,
                                k0=k0 if axis > 0 else None)

        r, d = jax.jit(jax.vmap(one))(cw, fw)
        rs.append(r)
        ds.append(d)
    return rs, ds


def matrices(cfg: dict, precision: str = HIGHEST) -> dict:
    """Everything the reference applies: sqrt0 and per-level, per-axis
    (R, sqrtD). A 1-D chart's joint matrices are its only axis factors."""
    geom, k = geometry(cfg), kernel_fn(cfg)
    jitter = float(cfg.get("jitter", 1e-6))
    with jax.default_matmul_precision(precision if precision == HIGHEST
                                      else "high"):
        out = {"sqrt0": level0_sqrt(geom, k, jitter), "R": [], "D": []}
        for lvl in range(geom.n_levels):
            if geom.ndim == 1:
                r, d = joint_mats_1d(geom, k, lvl, jitter, precision)
                rs, ds = [r], [d]
            else:
                rs, ds = axis_mats(geom, k, lvl, jitter, precision)
            out["R"].append(rs)
            out["D"].append(ds)
    return out


# -- one refinement application -----------------------------------------------------
def _axis_pass(x, axis, r, d, noise, geom: Geometry, level: int):
    """Refine ``x`` along ``axis``: child f of family t is
    sum_k R[t,f,k] c[t*s+k] (+ sum_j D[t,f,j] noise[t,j]) on the
    boundary-padded coarse axis. ``noise`` is (T, f, *rest) or None."""
    t = geom.family_count(level, axis)
    s, csz, fsz, b = geom.stride, geom.n_csz, geom.n_fsz, geom.b
    x = jnp.moveaxis(x, axis, 0)
    if geom.boundary == "reflect":
        x = jnp.pad(x, [(b, b)] + [(0, 0)] * (x.ndim - 1), mode="reflect")
    lim = s * (t - 1) + 1
    win = [lax.slice_in_dim(x, kk, kk + lim, stride=s, axis=0)
           for kk in range(csz)]
    bshape = (r.shape[0],) + (1,) * (x.ndim - 1)
    kids = []
    for f in range(fsz):
        acc = sum(r[:, f, kk].reshape(bshape) * win[kk] for kk in range(csz))
        if noise is not None:
            acc = acc + sum(d[:, f, j].reshape(bshape) * noise[:, j]
                            for j in range(fsz))
        kids.append(acc)
    out = jnp.stack(kids, axis=1)  # (T, f, *rest)
    out = out.reshape((t * fsz,) + out.shape[2:])
    return jnp.moveaxis(out, 0, axis)


def _noise_nd(xi, ds, geom: Geometry, level: int, precision: str):
    """ξ of an N-D level as the axis-0 pass takes it: the trailing axes'
    noise factors contracted in, (T0, f0, F1, .., F_{d-1})."""
    nd, fsz = geom.ndim, geom.n_fsz
    tt = geom.families(level)
    x = xi.reshape(tt + (fsz,) * nd)
    for a in range(1, nd):
        x = jnp.moveaxis(x, (a, nd + a), (-2, -1))  # (..., T_a, f_a)
        dm = ds[a]
        if dm.shape[0] == 1:
            x = dot(x, dm[0].T, precision)
        else:  # per-family factor: (..., T, j) x (T, f, j)
            x = jnp.sum(x[..., None, :] * dm, axis=-1)
        x = jnp.moveaxis(x, (-2, -1), (a, nd + a))
    perm = [0, nd]
    for a in range(1, nd):
        perm += [a, nd + a]
    x = x.transpose(perm)
    return x.reshape((tt[0], fsz) + tuple(tt[a] * fsz for a in range(1, nd)))


def refine(field, xi, rs, ds, geom: Geometry, level: int,
           precision: str = HIGHEST):
    """One level (paper Eq. 9), Kronecker-factored on an N-D chart."""
    nd = geom.ndim
    if nd == 1:
        noise = xi.reshape(geom.families(level) + (geom.n_fsz,))
        return _axis_pass(field, 0, rs[0], ds[0], noise, geom, level)
    for a in range(nd - 1, 0, -1):
        field = _axis_pass(field, a, rs[a], None, None, geom, level)
    noise = _noise_nd(xi, ds, geom, level, precision)
    return _axis_pass(field, 0, rs[0], ds[0], noise, geom, level)


def forward_fn(geom: Geometry, precision: str = HIGHEST):
    """``forward`` jitted per level, so that a 10^8-point field is never
    one program: (mats, ξ) -> field."""
    hp = lax.Precision.HIGHEST

    @jax.jit
    def level0(sqrt0, xi0):
        if precision == HIGHEST:
            f = jnp.matmul(sqrt0, xi0, precision=hp)
        else:
            f = dot(sqrt0, xi0[:, None], precision)[:, 0]
        return f.reshape(geom.shape0)

    steps = [jax.jit(lambda f, x, r, d, lvl=lvl:
                     refine(f, x, r, d, geom, lvl, precision))
             for lvl in range(geom.n_levels)]

    def run(mats, xi):
        field = level0(mats["sqrt0"], xi[0])
        for lvl, step in enumerate(steps):
            field = step(field, xi[lvl + 1], mats["R"][lvl], mats["D"][lvl])
        return field

    return run


# -- the server's draw and its moments ------------------------------------------------
def row_xi(mean, std, seed: int, row: int):
    """The excitation of row ``row`` of a request with seed ``seed``:
    mean + std · N(0, 1) under ``fold_in(PRNGKey(seed), row)``, one key
    per level."""
    k = jax.random.fold_in(jax.random.PRNGKey(seed), row)
    ks = jax.random.split(k, len(mean))
    return [m + s * jax.random.normal(kk, m.shape, m.dtype)
            for kk, m, s in zip(ks, mean, std)]


def rel_l2(a, b) -> float:
    """||a - b|| / ||b|| in float32 on the device (the difference is exact)."""
    a = jnp.asarray(a, jnp.float32)
    b = jnp.asarray(b, jnp.float32)
    return float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))
