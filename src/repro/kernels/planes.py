"""Phase-plane tiles: the layout every refinement kernel body works in.

Mosaic (the TPU kernel compiler) tiles the last two dimensions of every
value by (8 sublanes, 128 lanes). A refinement family strides ``s =
n_fsz // 2`` coarse pixels and emits ``n_fsz`` fine pixels, so building its
window from the natural layout needs a stride-``s`` lane gather and writing
its children needs a stride-``n_fsz`` lane scatter — neither of which Mosaic
lowers, and a reshape that puts ``s`` or ``n_fsz`` on the lanes pads every
vector register 32- to 64-fold. The kernels therefore never see a field in
its natural layout. Along every refined axis ``a`` the field is split into
**phase planes**: element ``n = u·P + p`` of the axis lives at phase ``p``
(a leading, untiled dimension) and position ``u``. A tile is

    [p_0, ..., p_{d-1}, batch, u_{layout[0]}, ..., u_{layout[d-1]}]

— one phase dimension per axis, a batch dimension, then the positions in
``layout`` order (the last two are the tiled sublane/lane dimensions).

With ``P = Q·s`` phases, family ``t = v·Q + w`` reads coarse element
``t·s + k - off = v·P + (w·s + k - off)``: phase ``(w·s + k - off) mod P``
at position ``v`` shifted by ``⌊(w·s + k - off) / P⌋``. So the window
column ``k`` of all families is a phase selection (a reshape of a leading
dimension) plus a static position shift — never a gather — and the child
``f`` of family ``(v, w)`` is fine element ``v·2P + (w·n_fsz + f)``: phase
``w·n_fsz + f`` of a tile with ``2P`` phases at the same position. The
contraction is a multiply-accumulate of whole planes on the VPU in
float32, with the refinement matrices as per-family coefficient planes
(charted axes) or broadcast scalars (stationary axes).

Two uses:

* per-level launches (``icr_refine``, ``nd_fused``) split the halo-padded
  coarse field into ``P = s`` phases outside the kernel; the window
  overhang then only ever shifts positions forward, into the next block's
  halo view;
* the pyramid (``pyramid``) keeps a whole multi-level chain in VMEM: the
  phase count doubles each level while positions stay fixed, and window
  positions that fall off either end are filled from the reflected
  boundary (``fill="reflect"``) or with zeros (``"zero"``: the ``shrink``
  boundary, whose families never read there). On three or more axes the
  pyramid merges its two innermost position dims into one (``window``'s
  ``unit``/``period``), so short planes do not pad to (8, 128).
"""
from __future__ import annotations

import jax.numpy as jnp
from jax import lax

Array = jnp.ndarray


def _slice(x, lo: int, hi: int, axis: int):
    return lax.slice_in_dim(x, lo, hi, axis=axis)


def _reflect_index(u: int, p: int, *, P: int, lin: int):
    """(phase, position) of the in-axis element that the out-of-range
    natural index ``n = u·P + p`` reflects onto."""
    n_tot = P * lin
    n = u * P + p
    n = -n if n < 0 else 2 * n_tot - 2 - n
    return n % P, n // P


def _reflect_fill(x, *, pd: int, ud: int, P: int, lin: int, rows, us,
                  unit: int = 1):
    """Reflected-boundary window entries: for each (phase, position)
    ``(p, u)`` with ``u`` outside ``[0, lin)`` the natural index
    ``n = u·P + p`` reflects into the axis and is read back from ``x``.
    ``rows`` lists the source phase of each output row, ``us`` the
    out-of-range positions; returns rows × len(us) along (pd, ud), a
    position being ``unit`` consecutive entries of ``ud``."""
    out_rows = []
    for p in rows:
        cols = []
        for u in us:
            pp, uu = _reflect_index(u, p, P=P, lin=lin)
            cols.append(_slice(_slice(x, pp, pp + 1, pd), uu * unit,
                               (uu + 1) * unit, ud))
        out_rows.append(jnp.concatenate(cols, axis=ud)
                        if len(cols) > 1 else cols[0])
    return jnp.concatenate(out_rows, axis=pd) if len(out_rows) > 1 \
        else out_rows[0]


def _shifted(x, src, *, pd: int, ud: int, P: int, sigma: int, rows,
             lout: int, fill: str, unit: int = 1):
    """``out[u] = src[u + sigma]`` for ``u < lout`` along ``ud`` (a
    position being ``unit`` consecutive entries); entries past either
    end of ``src`` come from ``fill``."""
    lin = src.shape[ud] // unit
    lo = min(lout, max(0, -sigma))
    hi = max(lo, min(lout, lin - sigma))
    parts = []
    for a, b in ((0, lo), (hi, lout)):
        if b <= a:
            continue
        if fill == "none":
            raise ValueError(
                f"window shift {sigma} leaves the tile ({lin} positions, "
                f"{lout} outputs) with no fill rule")
        if fill == "zero":
            shape = list(src.shape)
            shape[ud] = (b - a) * unit
            parts.append((a, jnp.zeros(shape, src.dtype)))
        else:
            parts.append((a, _reflect_fill(
                x, pd=pd, ud=ud, P=P, lin=lin, rows=rows,
                us=[u + sigma for u in range(a, b)], unit=unit)))
    if hi > lo:
        parts.append((lo, _slice(src, (lo + sigma) * unit,
                                 (hi + sigma) * unit, ud)))
    parts.sort(key=lambda t: t[0])
    arrs = [p for _, p in parts]
    return jnp.concatenate(arrs, axis=ud) if len(arrs) > 1 else arrs[0]


def _lane_shift(v, shift: int, ud: int):
    """``out[f] = v[f + shift]`` along ``ud``, zero past either end."""
    n = v.shape[ud]
    if shift == 0:
        return v
    zshape = list(v.shape)
    zshape[ud] = min(abs(shift), n)
    zeros = jnp.zeros(zshape, v.dtype)
    if abs(shift) >= n:
        return zeros
    if shift > 0:
        return jnp.concatenate([_slice(v, shift, n, ud), zeros], axis=ud)
    return jnp.concatenate([zeros, _slice(v, 0, n + shift, ud)], axis=ud)


def _shifted_inner(x, src, *, pd: int, ud: int, P: int, sigma: int, rows,
                   period: int, fill: str):
    """``_shifted`` for the inner axis of a merged position dimension:
    position ``u`` of row ``r`` is entry ``r·period + u`` of ``ud``. The
    whole dimension shifts by ``sigma``; the ``|sigma|`` entries per row
    that crossed a row end are replaced (selected by the entry index mod
    ``period``) by their ``fill`` values."""
    body = _lane_shift(src, sigma, ud)
    bad = [j for j in range(period) if not 0 <= j + sigma < period]
    if not bad:
        return body
    if fill == "none":
        raise ValueError(
            f"window shift {sigma} leaves the tile ({period} positions) "
            "with no fill rule")
    inner = lax.broadcasted_iota(jnp.int32, body.shape, ud) % period
    for j in bad:
        if fill == "zero":
            val = jnp.zeros_like(body)
        else:
            vals = []
            for p in rows:
                pp, uu = _reflect_index(j + sigma, p, P=P, lin=period)
                vals.append(_lane_shift(_slice(x, pp, pp + 1, pd), uu - j,
                                        ud))
            val = jnp.concatenate(vals, axis=pd) if len(vals) > 1 \
                else vals[0]
        body = jnp.where(inner == j, val, body)
    return body


def window(x, k: int, *, pd: int, ud: int, s: int, off: int, lout: int,
           fill: str = "none", unit: int = 1, period: int | None = None):
    """Window column ``k`` of every family along one axis.

    ``x`` has ``P`` phases on dim ``pd`` and its positions on dim ``ud``;
    the result has ``Q = P // s`` family rows on ``pd`` and ``lout``
    positions on ``ud``. ``off`` is the window origin relative to the
    family anchor (``b`` for an unpadded reflect axis, 0 when the field
    is already halo-padded or the boundary is ``shrink``).

    Two axes may share ``ud`` (a merged position dimension, row-major):
    the outer one's positions are ``unit`` entries wide, the inner one's
    repeat with ``period`` (``lout`` must then equal ``period``).
    """
    P = x.shape[pd]
    Q = P // s
    qq, rr = divmod(k - off, s)
    xr = x.reshape(x.shape[:pd] + (Q, s) + x.shape[pd + 1:])
    src = lax.index_in_dim(xr, rr, axis=pd + 1, keepdims=False)
    pieces, w = [], 0
    while w < Q:
        sigma = (w + qq) // Q
        w_end = min(Q, (sigma + 1) * Q - qq)
        r0 = w + qq - sigma * Q
        rows = _slice(src, r0, r0 + (w_end - w), pd)
        phases = [(r0 + i) * s + rr for i in range(w_end - w)]
        if period is not None:
            pieces.append(_shifted_inner(x, rows, pd=pd, ud=ud, P=P,
                                         sigma=sigma, rows=phases,
                                         period=period, fill=fill))
        else:
            pieces.append(_shifted(x, rows, pd=pd, ud=ud, P=P, sigma=sigma,
                                   rows=phases, lout=lout, fill=fill,
                                   unit=unit))
        w = w_end
    return jnp.concatenate(pieces, axis=pd) if len(pieces) > 1 \
        else pieces[0]


def axis_step(x, *, pd: int, ud: int, s: int, csz: int, fsz: int,
              off: int, lout: int, coef_r, coef_d=None, xi=None,
              fill: str = "none", unit: int = 1, period: int | None = None):
    """Refine one axis of a phase-plane tile (float32 in, float32 out).

    ``coef_r(f, k)`` / ``coef_d(f, j)`` return the refinement-matrix
    entries as arrays that broadcast against a window (per-family planes
    on charted axes, ``(1, ..., 1)`` scalars on stationary ones). ``xi``
    (given iff this axis injects the excitation) is laid out like the
    output. The result has ``Q·fsz`` phases on ``pd`` and ``lout``
    positions on ``ud`` (``unit``/``period``: see ``window``).
    """
    # one window at a time: the live set is the tile, one window and the
    # n_fsz accumulators (the VMEM models in dispatch count exactly that)
    accs = [None] * fsz
    for k in range(csz):
        w = window(x, k, pd=pd, ud=ud, s=s, off=off, lout=lout, fill=fill,
                   unit=unit, period=period)
        for f in range(fsz):
            term = coef_r(f, k) * w
            accs[f] = term if accs[f] is None else accs[f] + term
    if xi is not None:
        Q = x.shape[pd] // s
        xr = xi.reshape(xi.shape[:pd] + (Q, fsz) + xi.shape[pd + 1:])
        xis = [lax.index_in_dim(xr, j, axis=pd + 1, keepdims=False)
               for j in range(fsz)]
        for f in range(fsz):
            noise = coef_d(f, 0) * xis[0]
            for j in range(1, fsz):
                noise = noise + coef_d(f, j) * xis[j]
            accs[f] = accs[f] + noise
    outs = accs
    y = jnp.stack(outs, axis=pd + 1)
    return y.reshape(y.shape[:pd] + (y.shape[pd] * fsz,) + y.shape[pd + 2:])


# -- natural <-> phase-plane relayouts (XLA, outside the kernels) -------------
def to_planes(x, axes_len, *, s: int, lead: int, layout):
    """Natural ``[*lead_dims, n_0, ..., n_{d-1}]`` (with ``lead`` leading
    batch dims merged into one) -> ``[s]*d + [batch] + [u in layout]``
    with ``u_a = axes_len[a]`` positions (zero-padded or cropped)."""
    nd = len(axes_len)
    batch = 1
    for n in x.shape[:lead]:
        batch *= n
    x = x.reshape((batch,) + x.shape[lead:])
    for a in range(nd):
        want = axes_len[a] * s
        have = x.shape[1 + a]
        if have > want:
            x = _slice(x, 0, want, 1 + a)
        elif have < want:
            pads = [(0, 0)] * x.ndim
            pads[1 + a] = (0, want - have)
            x = jnp.pad(x, pads)
    shape = [batch]
    for a in range(nd):
        shape += [axes_len[a], s]
    x = x.reshape(shape)
    perm = [2 + 2 * a for a in range(nd)] + [0] \
        + [1 + 2 * a for a in layout]
    return x.transpose(perm)


def from_planes(y, *, layout):
    """Inverse relayout of a kernel output ``[P_a]*d + [batch] + [u in
    layout]`` -> natural ``[batch, u_0·P_0, ..., u_{d-1}·P_{d-1}]``."""
    nd = len(layout)
    # dims: phases 0..nd-1, batch nd, positions nd+1+i for axis layout[i]
    perm = [nd]
    for a in range(nd):
        perm += [nd + 1 + list(layout).index(a), a]
    z = y.transpose(perm)
    shape = [z.shape[0]] + [z.shape[1 + 2 * a] * z.shape[2 + 2 * a]
                            for a in range(nd)]
    return z.reshape(shape)


def fine_to_planes(x, T, *, fsz: int, layout):
    """Natural fine-resolution ``[batch, T_0, f_0, ..., T_{d-1}, f_{d-1}]``
    (e.g. ξ, or a cotangent) -> the kernel output layout
    ``[fsz]*d + [batch] + [T in layout]``."""
    nd = len(T)
    x = x.reshape((x.shape[0],) + sum(((t, fsz) for t in T), ()))
    perm = [2 + 2 * a for a in range(nd)] + [0] \
        + [1 + 2 * a for a in layout]
    return x.transpose(perm)
