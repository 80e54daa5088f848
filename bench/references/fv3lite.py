"""Plain reference of the FV3-lite physics step, in straightforward jnp.

It imports nothing of the program under test.  State is a dict of padded
``(..., 6, nk, N+2h, N+2h)`` arrays (any leading member axes ride along).
Every stencil is written out on whole padded planes with ``jnp.roll``
shifts; only the tile interiors of what a step produces are meaningful,
and the rules for the ghost cells follow the program's documented
semantics:

* a field a program produces anew (``delpc``, ``delp_out``, ``pt_out``,
  a tracer's or a remapped field's output) holds zeros in its ghost cells;
* a field a program updates in place (``u`` and ``v`` in d_sw, ``w`` in
  c_sw) keeps the ghost cells it came in with;
* the halo exchange fills ghost cells in two passes: first the west and
  east ghost columns of the interior rows, then the south and north ghost
  rows over the whole padded width, each read from the neighbouring tile
  as it stands after the first pass (so a corner ghost may read a
  neighbour's ghost cell, filled by the first pass or left from before).

The cube's connectivity and the vector rotation across each edge are
derived here from the face frames by folding a ghost cell over the edge
it lies beyond.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

#: face frames (normal, ex, ey) of the six cube faces, ex x ey = normal
FACES = (
    ((1, 0, 0), (0, 1, 0), (0, 0, 1)),
    ((0, 1, 0), (-1, 0, 0), (0, 0, 1)),
    ((-1, 0, 0), (0, -1, 0), (0, 0, 1)),
    ((0, -1, 0), (1, 0, 0), (0, 0, 1)),
    ((0, 0, 1), (0, 1, 0), (-1, 0, 0)),
    ((0, 0, -1), (0, 1, 0), (1, 0, 0)),
)
STATE = ("delp", "pt", "w", "u", "v")
REMAPPED = ("pt", "w", "u", "v")


# ---------------------------------------------------------------------------
# cube geometry and the two-pass halo exchange
# ---------------------------------------------------------------------------

def _frame(f):
    return tuple(np.array(x, float) for x in FACES[f])


def _fold(f, a, b, edge):
    """Fold the cube-surface point with face-``f`` coordinates (a, b)
    (a, b in [-0.5, 0.5] on the face) across ``edge`` onto the
    neighbouring face.  Returns (g, a', b', M) where M maps the
    neighbour's (u, v) components into face f's frame."""
    n, ex, ey = _frame(f)
    out = {"W": -ex, "E": ex, "S": -ey, "N": ey}[edge]
    along = ey if edge in ("W", "E") else ex
    t = b if edge in ("W", "E") else a
    depth = (abs(a) if edge in ("W", "E") else abs(b)) - 0.5
    g = next(k for k in range(6) if np.allclose(_frame(k)[0], out))
    p = 0.5 * out + (0.5 - depth) * n + t * along
    _, exg, eyg = _frame(g)
    inward = -n            # direction into g from the shared edge
    m = np.zeros((2, 2))
    for col, w in enumerate((exg, eyg)):
        unf = w - (w @ inward) * inward + (w @ inward) * out
        m[0, col], m[1, col] = unf @ ex, unf @ ey
    return g, p @ exg, p @ eyg, np.round(m)


@functools.lru_cache(maxsize=4)
def exchange_plan(N: int, h: int):
    """Flat (tile, j, i) index arrays of both passes, with the 2x2 vector
    maps per ghost cell: ((dst, src, m00, m01, m10, m11), ...)."""
    P = N + 2 * h
    coord = lambda x: (x + 0.5) / N - 0.5           # padded -> face coord
    index = lambda c: int(round((c + 0.5) * N - 0.5)) + h

    def build(cells):
        dst, src, mats = [], [], []
        for f, j, i, edge in cells:
            g, a2, b2, m = _fold(f, coord(i - h), coord(j - h), edge)
            dst.append((f * P + j) * P + i)
            src.append((g * P + index(b2)) * P + index(a2))
            mats.append(m.ravel())
        mats = np.array(mats, np.float32)
        return (np.array(dst), np.array(src),
                *(mats[:, c] for c in range(4)))

    inner = range(h, h + N)
    ghosts = [*range(h), *range(h + N, P)]
    pass1 = [(f, j, i, "W" if i < h else "E")
             for f in range(6) for j in inner for i in ghosts]
    pass2 = [(f, j, i, "S" if j < h else "N")
             for f in range(6) for j in ghosts for i in range(P)]
    return build(pass1), build(pass2)


def exchange(fields: dict, halo: int, vector=("u", "v")) -> dict:
    """Fill the ghost cells of ``fields`` (tile axis at -4).  The pair
    ``vector`` is rotated into each receiving tile's frame when both of
    its components are exchanged."""
    some = next(iter(fields.values()))
    P = some.shape[-1]
    N = P - 2 * halo
    flat = {k: jnp.moveaxis(v, -4, -3).reshape(
        v.shape[:-4] + (v.shape[-3], 6 * P * P)) for k, v in fields.items()}
    vec = all(c in flat for c in vector)
    for dst, src, m00, m01, m10, m11 in exchange_plan(N, halo):
        new = {}
        for k, v in flat.items():
            if vec and k in vector:
                u, w = flat[vector[0]][..., src], flat[vector[1]][..., src]
                val = (m00 * u + m01 * w) if k == vector[0] \
                    else (m10 * u + m11 * w)
            else:
                val = v[..., src]
            new[k] = v.at[..., dst].set(val.astype(v.dtype))
        flat = new
    return {k: jnp.moveaxis(v.reshape(v.shape[:-1] + (6, P, P)), -3, -4)
            for k, v in flat.items()}


# ---------------------------------------------------------------------------
# stencils on whole padded planes
# ---------------------------------------------------------------------------

def sh(a, di=0, dj=0):
    """a[..., j + dj, i + di] (values wrap at the array border; only the
    interior, at most ``halo`` cells from any ghost read, is used)."""
    return jnp.roll(a, (-dj, -di), axis=(-2, -1))


def interior_mask(shape, halo):
    """True on the tile interiors of a padded plane."""
    P = shape[-1]
    m = np.zeros((P, P), bool)
    m[halo:P - halo, halo:P - halo] = True
    return jnp.asarray(m)


def fresh(x, mask):
    return jnp.where(mask, x, jnp.zeros_like(x))


def in_place(new, old, mask):
    return jnp.where(mask, new, old)


def al(q, d):
    return (7.0 / 12.0) * (sh(q, *_m(-1, d)) + q) \
        - (1.0 / 12.0) * (sh(q, *_m(-2, d)) + sh(q, *_m(1, d)))


def _m(s, d):
    return (s, 0) if d == "x" else (0, s)


def ppm_flux(q, a, c, d):
    bl = a - q
    br = sh(a, *_m(1, d)) - q
    b0 = bl + br
    qm = sh(q, *_m(-1, d))
    cand = jnp.where(c > 0.0,
                     qm + (1.0 - c) * (sh(br, *_m(-1, d)) - c * sh(b0, *_m(-1, d))),
                     q - (1.0 + c) * (bl + c * b0))
    lo, hi = jnp.minimum(qm, q), jnp.maximum(qm, q)
    return c * jnp.minimum(jnp.maximum(cand, lo), hi)


def transport(q, cx, cy):
    """Lin-Rood 2D transport of q: PPM x and y inner updates, then the
    conservative flux divergence of the cross-directional fluxes."""
    fxi = ppm_flux(q, al(q, "x"), cx, "x")
    qx = q + 0.5 * (fxi - sh(fxi, 1, 0))
    fyf = ppm_flux(qx, al(qx, "y"), cy, "y")
    fyi = ppm_flux(q, al(q, "y"), cy, "y")
    qy = q + 0.5 * (fyi - sh(fyi, 0, 1))
    fxf = ppm_flux(qy, al(qy, "x"), cx, "x")
    return q + (fxf - sh(fxf, 1, 0)) + (fyf - sh(fyf, 0, 1))


def courant(u, v, dtdx, dtdy):
    return 0.5 * (sh(u, -1, 0) + u) * dtdx, 0.5 * (sh(v, 0, -1) + v) * dtdy


def kshift(a, s):
    """a[k + s] along the level axis (-3), edge levels repeated."""
    nk = a.shape[-3]
    idx = np.clip(np.arange(nk) + s, 0, nk - 1)
    return jnp.take(a, idx, axis=-3)


def top_down_sum(delp, top):
    """Interface values: ``top``, then ``top`` plus delp's running sum
    level by level from the top (nk + 1 levels)."""
    def add(acc, d):
        acc = acc + d
        return acc, acc

    first = jnp.full_like(delp[..., 0, :, :], top)
    _, rest = _scan_k(add, first, delp)
    return jnp.concatenate([first[..., None, :, :], rest], axis=-3)


def _scan_k(fn, init, xs):
    """lax.scan along the level axis (-3) of each array in xs."""
    xs = jax.tree.map(lambda a: jnp.moveaxis(a, -3, 0), xs)
    carry, ys = jax.lax.scan(fn, init, xs)
    return carry, jax.tree.map(lambda a: jnp.moveaxis(a, 0, -3), ys)


def riemann_w(w, delpc, ptc, beta, dt):
    """Tridiagonal implicit solve for the pressure perturbation (Thomas
    algorithm, top to bottom then back) and the w update from it."""
    nk = w.shape[-3]
    k = jnp.arange(nk).reshape((nk, 1, 1))
    top, bot = k == 0, k == nk - 1
    ptm, dpm, dpp = kshift(ptc, -1), kshift(delpc, -1), kshift(delpc, 1)
    aa = jnp.where(top, 0.0, jnp.where(bot, -ptm / delpc,
                                       -ptm / (0.5 * (dpm + delpc))))
    cc = jnp.where(bot, 0.0, jnp.where(top, -ptc / delpc,
                                       -ptc / (0.5 * (delpc + dpp))))
    bb = beta - (aa + cc)
    rhs = w * delpc

    def fwd(carry, x):
        c_prev, r_prev, first = carry
        a, b, c, r = x
        den = jnp.where(first, b, b - a * c_prev)
        c2 = c / den
        r2 = jnp.where(first, r / b, (r - a * r_prev) / den)
        return (c2, r2, False), (c2, r2)

    zero = jnp.zeros_like(w[..., 0, :, :])
    _, (cp, rp) = _scan_k(fwd, (zero, zero, True), (aa, bb, cc, rhs))

    def bwd(p_next, x):
        c, r, last = x
        p = jnp.where(last, r, r - c * p_next)
        return p, p

    lastk = jnp.broadcast_to(bot, cp.shape)
    rev = lambda a: jnp.flip(a, axis=-3)
    _, pp = _scan_k(bwd, zero, (rev(cp), rev(rp), rev(lastk)))
    pp = rev(pp)
    return jnp.where(bot, w - dt * pp / delpc,
                     w + dt * (kshift(pp, 1) - pp) / delpc)


def c_sw(st, p, mask):
    u, v, delp, pt, w = (st[k] for k in ("u", "v", "delp", "pt", "w"))
    div = (0.5 * (sh(u, 1, 0) - sh(u, -1, 0))) * p["rdx"] \
        + (0.5 * (sh(v, 0, 1) - sh(v, 0, -1))) * p["rdy"]
    delpc = delp * (1.0 - p["dt2"] * div)
    ptc = pt * (1.0 - p["dt2"] * div)
    w2 = riemann_w(w, delpc, ptc, p["beta"], p["dt2"])
    return in_place(w2, w, mask), fresh(delpc, mask)


def d_sw(st, delpc, p, mask):
    u, v, delp, pt = (st[k] for k in ("u", "v", "delp", "pt"))
    vort = (0.5 * (sh(v, 1, 0) - sh(v, -1, 0))) * p["rdx"] \
        - (0.5 * (sh(u, 0, 1) - sh(u, 0, -1))) * p["rdy"]
    ke = 0.5 * (u * u + v * v)
    damp = p["smag_dt"] * (delpc ** 2.0 + vort ** 2.0) ** 0.5
    pe = top_down_sum(delp, p["ptop"])[..., :-1, :, :]
    cx, cy = courant(u, v, p["dtdx"], p["dtdy"])
    gx = 0.5 * (sh(ke, 1, 0) - sh(ke, -1, 0) + sh(pe, 1, 0) - sh(pe, -1, 0)) \
        * p["rdx"]
    gy = 0.5 * (sh(ke, 0, 1) - sh(ke, 0, -1) + sh(pe, 0, 1) - sh(pe, 0, -1)) \
        * p["rdy"]
    lap = lambda a: sh(a, 1, 0) + sh(a, -1, 0) + sh(a, 0, 1) + sh(a, 0, -1) \
        - 4.0 * a
    u2 = u + p["dt"] * (vort * v - gx) + damp * lap(u)
    # the v update reads the u just written at the same point
    v2 = v - p["dt"] * (vort * u2 + gy) + damp * lap(v)
    return {"u": in_place(u2, u, mask), "v": in_place(v2, v, mask),
            "delp": fresh(transport(delp, cx, cy), mask),
            "pt": fresh(transport(pt, cx, cy), mask)}


def remap(st, names, p, mask):
    """Conservative remap of each column from the Lagrangian interfaces
    (top-down sums of delp) to uniform slices of the column's mass."""
    delp = st["delp"]
    nk = delp.shape[-3]
    pe = top_down_sum(delp, p["ptop"])
    total = top_down_sum(delp, 0.0)[..., -1:, :, :]
    # the reference interfaces accumulate total / nk level by level
    steps = jnp.broadcast_to(total * p["rk"], delp.shape)
    pe_ref = top_down_sum(steps, p["ptop"])
    dref = pe_ref[..., 1:, :, :] - pe_ref[..., :-1, :, :]
    # layer s bracketing each reference interface: the largest s in
    # [0, nk-1] with s == 0 or pe[s] <= pe_ref
    inner = pe[..., 1:nk, :, :]
    s = jnp.zeros(pe_ref.shape, jnp.int32)
    for k in range(nk - 1):
        s = s + (inner[..., k:k + 1, :, :] <= pe_ref).astype(jnp.int32)
    fms = {q: top_down_sum(st[q] * delp, 0.0) for q in names}

    def layer(j, fi):
        """Interpolate within Lagrangian layer j where it was selected."""
        at = lambda a, o: jax.lax.dynamic_index_in_dim(a, j + o, a.ndim - 3)
        pe0, pe1 = at(pe, 0), at(pe, 1)
        width = jnp.maximum(pe1 - pe0, 1e-30)
        chosen = s == j
        out = {}
        for q, fm in fms.items():
            f0, f1 = at(fm, 0), at(fm, 1)
            out[q] = jnp.where(chosen, f0 + (pe_ref - pe0) * (f1 - f0) / width,
                               fi[q])
        return out

    fis = jax.lax.fori_loop(0, nk, layer,
                            {q: jnp.zeros_like(pe_ref) for q in names})
    out = {"delp": fresh(dref, mask)}
    for q, fi in fis.items():
        out[q] = fresh((fi[..., 1:, :, :] - fi[..., :-1, :, :]) / dref, mask)
    return out


def params(cfg: dict) -> dict:
    dt = cfg["dt"]
    return {"dt": dt, "dt2": 0.5 * dt, "smag_dt": cfg["smag_coeff"] * dt,
            "dtdx": dt, "dtdy": dt, "rdx": 1.0, "rdy": 1.0,
            "ptop": cfg["ptop"], "beta": cfg["beta"], "rk": 1.0 / cfg["nk"]}


def make_step(cfg: dict, namelist: dict, tracers: tuple):
    """One physics step: k_split x (n_split acoustic substeps, tracer
    transport, vertical remap).  Returns ``step(state) -> state``."""
    h = cfg["halo"]
    p = params(cfg)

    def acoustic(st, mask):
        st = {**st, **exchange({k: st[k] for k in STATE}, h)}
        w, delpc = c_sw(st, p, mask)
        st["w"] = w
        delpc = exchange({"delpc": delpc}, h)["delpc"]
        return {**st, **d_sw(st, delpc, p, mask)}

    def remap_iteration(st, mask):
        st = jax.lax.fori_loop(0, namelist["n_split"],
                               lambda _, s: acoustic(s, mask), st)
        st = {**st, **exchange({k: st[k] for k in ("u", "v", *tracers)}, h)}
        cx, cy = courant(st["u"], st["v"], p["dtdx"], p["dtdy"])
        for q in tracers:
            st[q] = fresh(transport(st[q], cx, cy), mask)
        return {**st, **remap(st, (*REMAPPED, *tracers), p, mask)}

    def step(st):
        mask = interior_mask(st["delp"].shape, h)
        return jax.lax.fori_loop(0, namelist["k_split"],
                                 lambda _, s: remap_iteration(s, mask),
                                 dict(st))

    return step
