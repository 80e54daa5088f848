"""Pallas TPU lowering of Stencil IR.

Schedules map onto Pallas as follows (paper §V-A ↔ TPU):

 * horizontal (PARALLEL) stencils: grid over K slabs; each invocation holds a
   ``(block_k, NJ+2h, NI+2h)`` VMEM block per field.  Horizontal offsets are
   in-block static slices (VREG shifts); K is the parallel ("map") dimension —
   the paper's ``[Interval, Operation, K, J, I]`` order with I on lanes.
   Where ``block_k`` does not divide nk the last slab is a partial (edge)
   block: its rows past nk are padding, masked by level and never stored.
 * vertical (FORWARD/BACKWARD) solvers: one full-column block; an in-kernel
   ``fori_loop`` walks K.  With ``carry_storage='vreg'`` loop-carried values
   live in registers across iterations (paper §VI-A.2 transform 3); with
   ``'vmem'`` each level re-reads the previously written VMEM row (the
   untransformed schedule, for A/B comparison).
 * horizontal regions: ``'predicated'`` masks statements on index grids inside
   the full-domain kernel; ``'split'`` emits a separate kernel writing only
   the region's bounding box (paper Table III: "Split regions to multiple
   kernels").
 * ensemble members (``n_members=M``): the member axis becomes the
   *outermost sequential grid axis* — every BlockSpec gains a squeezed
   (``None``) leading member dimension whose index map passes the member
   grid index through, so each invocation still sees exactly the blocks it
   would see at M=1.  Schedules, legality and per-invocation VMEM footprint
   are unchanged per member; one ``pl.pallas_call`` serves all M members
   (launch overhead amortized — the cost model prices this).
 * chunked members (``member_chunk=C``, the ``batch="vmap:C,grid"``
   hybrid): the outermost grid axis walks ceil(M/C) *chunks* instead of
   single members, each block carries a non-squeezed leading member
   dimension of extent C, and kernel bodies batch the chunk through every
   statement (trailing-axis windows; explicit leading slices at traced-K
   levels).  The K-blocked marching carry gains a leading C dim in scratch
   and still resets at each chunk's first block — per-chunk carry reset,
   no leaks between chunks.  Per-invocation VMEM scales by C, which is
   exactly what ``vmem_footprint(member_chunk=C)`` prices for the tuner.

Kernels are validated in interpret mode on CPU against the jnp oracle; on
real TPUs the same ``pl.pallas_call`` lowers to Mosaic.  The mode comes from
the platform (:func:`~repro.core.backend.compile.pallas_interpret`).
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Mapping

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..stencil.domain import DomainSpec
from . import compile as _compile
from ..stencil.ir import (
    Assign,
    BinOp,
    Computation,
    Const,
    Direction,
    Expr,
    FieldAccess,
    FoundLevel,
    Interval,
    LevelSearch,
    Max,
    Min,
    ParamRef,
    Pow,
    Region,
    Stencil,
    UnaryOp,
    Where,
    expr_contains_level_search,
)
from ..stencil.schedule import (Schedule, default_schedule, j_tile, k_block,
                                k_blocks, kblocked_applies,
                                solver_carried_fields)

_UNARY = {
    "neg": lambda x: -x,
    "sqrt": jnp.sqrt,
    "abs": jnp.abs,
    "exp": jnp.exp,
    "log": jnp.log,
    "sign": jnp.sign,
    "floor": jnp.floor,
}
_BIN = {
    "+": lambda a, b: a + b,
    "-": lambda a, b: a - b,
    "*": lambda a, b: a * b,
    "/": lambda a, b: a / b,
    "<": lambda a, b: a < b,
    "<=": lambda a, b: a <= b,
    ">": lambda a, b: a > b,
    ">=": lambda a, b: a >= b,
    "==": lambda a, b: a == b,
    "!=": lambda a, b: a != b,
}


class _Column:
    """A field's whole K column over one horizontal window, read a level at
    a time: ``row(s)`` is level ``s`` (a traced index is fine).  Ref-backed
    columns index the ref at the level — Mosaic has no dynamic slice of a
    loaded value; only kernel-local values fall back to one.  ``whole()``
    loads every level at once."""

    def __init__(self, shape, dtype, row, whole):
        self.shape = tuple(shape)
        self.ndim = len(self.shape)
        self.dtype = dtype
        self.row = row
        self.whole = whole

    @classmethod
    def of_ref(cls, ref, jsl, isl):
        lead = ref.shape[:-3]
        shape = lead + (ref.shape[-3],) + _window_shape(ref, jsl, isl)

        def whole():
            return ref[..., jsl, isl]
        if lead:
            return cls(shape, ref.dtype, lambda s: ref[:, s, jsl, isl], whole)
        return cls(shape, ref.dtype, lambda s: ref[s, jsl, isl], whole)

    @classmethod
    def of_value(cls, col):
        # K sits at axis -3 so leading member-chunk dims ride through
        return cls(col.shape, col.dtype,
                   lambda s: jax.lax.dynamic_index_in_dim(
                       col, s, col.ndim - 3, keepdims=False),
                   lambda: col)


def _window_shape(ref, jsl: slice, isl: slice):
    return (len(range(*jsl.indices(ref.shape[-2]))),
            len(range(*isl.indices(ref.shape[-1]))))


#: vector registers one row block's found-level accumulators may hold: a
#: quarter of the 64, so the block's targets, the level's fresh values and
#: the compare masks fit beside them (on a v5e, twice as many ran the remap
#: 30% slower)
_BAND_VREGS = 16


def band_rows(row_shape, n_found: int) -> int:
    """Target rows per block of the band-limited search: as many as keep
    the ``n_found`` accumulators of one block within :data:`_BAND_VREGS`
    vector registers, given the shape of one target row (leading member
    dims, then the (J, I) window; 32-bit values, (8, 128) per register)."""
    *lead, nj, ni = row_shape
    vregs = math.prod(lead) * -(-nj // 8) * -(-ni // 128)
    return max(1, _BAND_VREGS // (vregs * max(1, n_found)))


def _level_reduce(x, red):
    """``red`` over every axis but the level axis (-3): a (K, 1) column."""
    x = red(red(x, axis=-1), axis=-1, keepdims=True)
    return red(x, axis=tuple(range(x.ndim - 2))) if x.ndim > 2 else x


def _live_fill(x, live, fill):
    """``x`` with the cells outside the live window set to ``fill``."""
    if live is None:
        return x
    return jnp.where(live, x, jnp.asarray(fill, x.dtype))


def _band(cmax, cmin, tmin, tmax, lo: int):
    """The source layers ``[s_lo, s_hi]`` that can bracket a block of
    targets spanning ``[tmin, tmax]`` over the window, from the per-level
    max and min of the compared levels ``lo+1 .. hi-1``.  On non-decreasing
    columns every layer up to ``s_lo`` lies at or below every target, and
    every layer past ``s_hi`` above every target, so a march that starts at
    ``s_lo`` and stops at ``s_hi`` selects what the march over all of
    ``[lo, hi)`` selects."""
    return (lo + jnp.sum(cmax <= tmin, dtype=jnp.int32),
            lo + jnp.sum(cmin <= tmax, dtype=jnp.int32))


def _block_bands(col, target_rows, live, lo: int, hi: int, rows: int):
    """``[(r0, r1, s_lo, s_hi)]`` for the blocks of ``rows`` target rows
    (row axis -3 of ``target_rows``) against the coordinate column ``col``,
    over the live cells of the window: the one bound code the kernel and
    :func:`search_band_share` share."""
    c = col[..., lo + 1:hi, :, :]
    # the contract the band rests on: every live column non-decreasing
    # (and NaN-free) over the compared levels.  A window that breaks it
    # takes extremes that band every block to all of [lo, hi-1]: a NaN
    # maximum is at or below no target, -inf at or below every one.
    ok = (c[..., 1:, :, :] >= c[..., :-1, :, :]) if c.shape[-3] > 1 \
        else c == c
    if live is not None:
        ok = ok | ~live
    mono = jnp.sum(~ok, dtype=jnp.int32) == 0
    cmax = jnp.where(mono, _level_reduce(_live_fill(c, live, -jnp.inf),
                                         jnp.max), jnp.nan)
    cmin = jnp.where(mono, _level_reduce(_live_fill(c, live, jnp.inf),
                                         jnp.min), -jnp.inf)
    # a NaN target widens its block's band to the whole column
    t = target_rows
    nan = t != t
    tlo = _level_reduce(
        _live_fill(jnp.where(nan, -jnp.inf, t), live, jnp.inf), jnp.min)
    thi = _level_reduce(
        _live_fill(jnp.where(nan, jnp.inf, t), live, -jnp.inf), jnp.max)
    out = []
    for r0 in range(0, t.shape[-3], rows):
        r1 = min(r0 + rows, t.shape[-3])
        tmin = jnp.min(tlo[r0:r1], axis=0, keepdims=True)
        tmax = jnp.max(thi[r0:r1], axis=0, keepdims=True)
        out.append((r0, r1) + _band(cmax, cmin, tmin, tmax, lo))
    return out


@functools.partial(jax.jit, static_argnums=(2, 3, 4))
def _marched_pairs(coord, target, lo: int, hi: int, rows: int):
    """Pairs the band-limited march walks in each window of a stack."""
    def one(c, t):
        return sum((r1 - r0) * (s_hi - s_lo) for r0, r1, s_lo, s_hi
                   in _block_bands(c, t, None, lo, hi, rows))
    return jax.vmap(one)(coord, target)


def search_band_share(coord, target, window, *, lo: int, hi: int,
                      n_found: int):
    """Share of the level search's marched (target row x source layer)
    pairs that the band-limited march walks, over the full march's
    ``rows * (hi - lo - 1)``: plain jnp on the arrays a search reads, with
    the kernel's own bound code.

    ``coord`` and ``target`` are ``(..., K, J, I)`` (leading axes: tiles or
    members, each its own window); ``window = (bj, bi)`` tiles the last two
    axes as the kernel's blocks do (ragged tiles at the far ends);
    ``n_found`` distinct FoundLevel accesses set the rows per block."""
    coord, target = jnp.asarray(coord), jnp.asarray(target)
    target = jnp.broadcast_to(target, coord.shape[:-3]
                              + target.shape[-3:])
    nrows = target.shape[-3]
    bj, bi = window
    rows = band_rows((bj, bi), n_found)
    marched = 0
    for j0 in range(0, coord.shape[-2], bj):
        for i0 in range(0, coord.shape[-1], bi):
            win = (Ellipsis, slice(j0, j0 + bj), slice(i0, i0 + bi))
            c, t = coord[win], target[win]
            c = c.reshape((-1,) + c.shape[-3:])
            t = t.reshape((-1,) + t.shape[-3:])
            marched += int(jnp.sum(_marched_pairs(c, t, lo, hi, rows)))
    n_win = math.prod(coord.shape[:-3]) * -(-coord.shape[-2] // bj) \
        * -(-coord.shape[-1] // bi)
    return marched / (n_win * nrows * (hi - lo - 1))


def _march_search(e: LevelSearch, read, params, read_col, nk: int,
                  live=None):
    """Lower a LevelSearch as in-kernel *marching loops*, band-limited:
    the target rows split into static blocks (:func:`band_rows`), and each
    block's ``fori_loop`` walks only the source layers that can bracket
    its targets over the window (:func:`_band`), accumulating the
    bracketing values of every FoundLevel access with selects — no
    gathers, so the loops map onto the VPU on real TPUs.  The bounds come
    from reductions of the coordinate column and the target over the live
    window (``live``: the cells the statement writes; None for all); a
    non-monotone window marches every layer.  Trace size O(nk / rows)."""
    if read_col is None or nk is None:
        raise NotImplementedError(
            "LevelSearch requires whole-column blocks (no read_col here)")
    target = _eval_block(e.target, read, params, read_col=read_col, nk=nk)
    cwin = read_col(e.coord, 0, 0)
    lo, hi = e.resolve_bounds(nk)
    finds = e.found_levels()
    cols = {}
    for fl in finds:
        key = (fl.name, fl.di, fl.dj)
        if key not in cols:
            cols[key] = read_col(fl.name, fl.di, fl.dj)

    if cwin.ndim == 3:
        shape = jnp.broadcast_shapes(jnp.shape(target), tuple(cwin.shape[1:]))

        def lift(r):
            return r
    elif jnp.ndim(target) >= cwin.ndim:
        # chunked columns (C, K, J, I) against a (C, rows, J, I) target:
        # level rows keep a unit K axis so the chunk axis stays aligned
        shape = jnp.broadcast_shapes(
            jnp.shape(target),
            tuple(cwin.shape[:-3]) + (1,) + tuple(cwin.shape[-2:]))

        def lift(r):
            return r[..., None, :, :]
    else:
        # chunked per-level context (search evaluated inside a marching
        # body): target is (C, J, I) — level rows align as-is
        shape = jnp.broadcast_shapes(
            jnp.shape(target),
            tuple(cwin.shape[:-3]) + tuple(cwin.shape[-2:]))

        def lift(r):
            return r
    target = jnp.broadcast_to(target, shape)
    # a target with a row axis (whole-column statements) is blocked along
    # it; a per-level target (inside a marching body) is one block
    has_rows = len(shape) == cwin.ndim

    def vals_at(s, shp):
        return {(fl.name, fl.di, fl.dj, fl.dk): jnp.broadcast_to(
                    lift(cols[(fl.name, fl.di, fl.dj)].row(s + fl.dk)), shp)
                for fl in finds}

    def body(s, carry):
        # one function for every block (the band's first layer and the
        # targets ride in the carry), so the loop body is traced once per
        # block shape; the first layer is taken whatever it compares
        s_lo, tgt, acc = carry
        take = (lift(cwin.row(s)) <= tgt) | (s == s_lo)
        fresh = vals_at(s, tgt.shape)
        return s_lo, tgt, {k: jnp.where(take, fresh[k], acc[k])
                           for k in acc}

    def march(tgt, s_lo, s_hi):
        acc = {(fl.name, fl.di, fl.dj, fl.dk):
               tgt.astype(cols[(fl.name, fl.di, fl.dj)].dtype) for fl in finds}
        return jax.lax.fori_loop(s_lo, s_hi + 1, body, (s_lo, tgt, acc))[2]

    if hi <= lo + 1:
        acc = vals_at(lo, shape)
    else:
        trows = target if has_rows else target[..., None, :, :]
        rows = band_rows(shape[:-3] + shape[-2:] if has_rows else shape,
                         len(finds))
        accs = [march(trows[..., r0:r1, :, :] if has_rows else target,
                      s_lo, s_hi)
                for r0, r1, s_lo, s_hi in _block_bands(
                    cwin.whole(), trows, live, lo, hi, rows)]
        acc = {k: (jnp.concatenate([a[k] for a in accs], axis=-3)
                   if len(accs) > 1 else accs[0][k]) for k in accs[0]}

    def found(fl: FoundLevel):
        return acc[(fl.name, fl.di, fl.dj, fl.dk)]

    return _eval_block(e.body, read, params, read_col=read_col, nk=nk,
                       found=found)


def _eval_block(e: Expr, read, params, read_col=None, nk=None, found=None,
                live=None):
    """Evaluate expression over a block; ``read(name, off)`` yields arrays.

    ``read_col(name, di, dj)`` yields a field's *whole* K column over the
    horizontal window — required (and only available under whole-K blocks)
    for :class:`LevelSearch` lowering; ``found`` resolves FoundLevel
    accesses inside a search body; ``live`` masks the window's cells whose
    value the statement writes (None: all), which bound a search's band.
    """
    def ev(x, found=found):
        return _eval_block(x, read, params, read_col=read_col, nk=nk,
                           found=found, live=live)

    if isinstance(e, Const):
        return e.value
    if isinstance(e, ParamRef):
        return params[e.name]
    if isinstance(e, FieldAccess):
        return read(e.name, e.offset)
    if isinstance(e, LevelSearch):
        return _march_search(e, read, params, read_col, nk, live)
    if isinstance(e, FoundLevel):
        if found is None:
            raise TypeError("FoundLevel outside a LevelSearch body")
        return found(e)
    if isinstance(e, BinOp):
        return _BIN[e.op](ev(e.a), ev(e.b))
    if isinstance(e, UnaryOp):
        return _UNARY[e.op](ev(e.a))
    if isinstance(e, Pow):
        return jnp.power(ev(e.a), ev(e.b))
    if isinstance(e, Where):
        return jnp.where(ev(e.cond), ev(e.a), ev(e.b))
    if isinstance(e, Min):
        return jnp.minimum(ev(e.a), ev(e.b))
    if isinstance(e, Max):
        return jnp.maximum(ev(e.a), ev(e.b))
    raise TypeError(e)


def _member_index_map(imap, m, *grid):
    """Index map of a memberized BlockSpec: member grid index first (block
    index 0 along the squeezed member dim), then the base map's blocks."""
    return (m,) + tuple(imap(*grid))


def _param_spec():
    """BlockSpec of one scalar parameter: a whole ``(1,)`` array in SMEM,
    the only memory a Mosaic kernel reads scalars from directly."""
    return pl.BlockSpec(memory_space=pltpu.SMEM)


def _member_specs(specs, chunk: int = 0):
    """Prepend a member block dimension to every array BlockSpec: squeezed
    (``None``, one member per grid step) by default, or a non-squeezed
    extent-``chunk`` dim whose grid axis indexes *chunk blocks* — the
    hybrid ``vmap:C,grid`` lowering.  Scalar-param specs (SMEM, no block
    shape) are broadcast across members and pass through untouched."""
    out = []
    for spec in specs:
        if spec.block_shape is None:
            out.append(spec)
            continue
        lead = (chunk,) if chunk else (None,)
        out.append(pl.BlockSpec(
            lead + tuple(spec.block_shape),
            functools.partial(_member_index_map, spec.index_map)))
    return out


def _hwindow(dom: DomainSpec, dj: int, di: int):
    """Static (j, i) slices of the extended write window shifted by offset."""
    ei, ej = dom.extend
    h = dom.halo
    return (slice(h - ej + dj, h + dom.nj + ej + dj),
            slice(h - ei + di, h + dom.ni + ei + di))


def _k_align(win, dk: int, out_nk: int):
    """Align a ``(lead..., K_f, J, I)`` window onto an ``out_nk``-row
    iteration space shifted by ``dk``: row ``k`` of the result holds
    ``win[..., k + dk, :, :]``, edge-clamped — the one K-offset read idiom
    shared by the horizontal kernel and the PARALLEL passes of vertical
    kernels.  K sits at axis ``-3`` so leading member-chunk dims ride
    through.  ``K_f`` may differ from ``out_nk`` (K-interface fields carry
    nk+1 rows, centers nk); interval restrictions make the clamp-padded
    rows dead."""
    field_nk = win.shape[-3]
    if dk == 0 and field_nk == out_nk:
        return win
    lo = max(0, dk)
    hi = min(field_nk, out_nk + dk)
    sl = win[..., lo:hi, :, :]
    lead = sl.shape[:-3]
    parts = []
    front = lo - dk  # rows whose k + dk < 0
    if front > 0:
        parts.append(jnp.broadcast_to(sl[..., :1, :, :],
                                      lead + (front,) + sl.shape[-2:]))
    parts.append(sl)
    back = out_nk - front - (hi - lo)  # rows whose k + dk >= field_nk
    if back > 0:
        parts.append(jnp.broadcast_to(sl[..., -1:, :, :],
                                      lead + (back,) + sl.shape[-2:]))
    if len(parts) == 1:
        return sl
    return jnp.concatenate(parts, axis=-3)


def _kshift_read(ref, dk: int, out_nk: int, jsl, isl):
    """K-shifted slice of a block ref over the (j, i) window (see
    :func:`_k_align`; leading member-chunk dims pass through)."""
    return _k_align(ref[..., jsl, isl], dk, out_nk)


@dataclasses.dataclass(frozen=True)
class _Tile:
    """Where a kernel's write window sits in its blocks: the whole padded
    plane (``bj == 0``), or a tile of ``bj`` J rows (:func:`j_tile` — only
    for stencils that read no horizontal neighbour) whose rows outside the
    window are masked off by their absolute row index."""

    dom: DomainSpec
    bj: int = 0

    def window(self, dj: int = 0, di: int = 0):
        """Static (j, i) slices of the write window shifted by an offset."""
        jsl, isl = _hwindow(self.dom, dj, di)
        return (slice(0, self.bj), isl) if self.bj else (jsl, isl)

    @property
    def shape2d(self) -> tuple[int, int]:
        ei, ej = self.dom.extend
        return (self.bj or self.dom.nj + 2 * ej, self.dom.ni + 2 * ei)

    def rows(self) -> int:
        """J extent of one block."""
        return self.bj or self.dom.nj + 2 * self.dom.halo

    def n_tiles(self) -> int:
        return pl.cdiv(self.dom.nj + 2 * self.dom.halo, self.bj) \
            if self.bj else 1

    def mask(self, region: Region | None, jt):
        """Bool ``shape2d`` mask of the cells a statement writes in J tile
        ``jt`` (a traced grid index), or None when it writes the whole
        window: the tile's window rows, intersected with ``region``."""
        if not self.bj and region is None:
            return None
        dom = self.dom
        ei, ej = dom.extend
        # jj/ii: window coordinates, 0 at the first interior cell
        j0 = jt * self.bj - dom.halo if self.bj else -ej
        jj = jax.lax.broadcasted_iota(jnp.int32, self.shape2d, 0) + j0
        mask = (jj >= -ej) & (jj < dom.nj + ej) if self.bj else None
        if region is not None:
            ilo, ihi, jlo, jhi = region.resolve(dom.ni, dom.nj)
            ii = jax.lax.broadcasted_iota(jnp.int32, self.shape2d, 1) - ei
            rm = (jj >= jlo) & (jj < jhi) & (ii >= ilo) & (ii < ihi)
            mask = rm if mask is None else mask & rm
        return mask


def _inline_offset_temps(stencil: Stencil) -> Stencil:
    """OTF-style inlining of temporary reads at nonzero offsets.

    In-kernel temporaries live on the write window, so a read like PPM's
    ``br[-1, 0, 0]`` has no backing storage for the shifted cells.  Instead
    of materializing the temporary, replace every offset read with the
    defining expression shifted by that offset (the same substitution OTF
    map fusion performs between stencils).  Expandable temporaries have a
    single full-interval, region-free definition whose field-level expansion
    reads only fields the stencil never overwrites; zero-offset reads keep
    using the computed window value.
    """
    temps = set(stencil.temporaries())
    if not temps:
        return stencil
    written_fields = {w for w in stencil.written() if w in stencil.fields}
    stmts = [s for c in stencil.computations for s in c.statements]
    n_defs: dict[str, int] = {}
    for s in stmts:
        if s.target in temps:
            n_defs[s.target] = n_defs.get(s.target, 0) + 1
    expansions: dict[str, Expr] = {}
    full = Interval()
    for s in stmts:
        t = s.target
        if (t not in temps or n_defs[t] != 1 or s.region is not None
                or s.interval != full
                or expr_contains_level_search(s.value)):
            # level searches walk absolute coordinate levels; replicating
            # one at a shifted offset is not a pure IR shift
            continue

        def expand(e: Expr) -> Expr:
            if isinstance(e, FieldAccess) and e.name in expansions:
                return expansions[e.name].shift(e.offset)
            return e.map_children(expand)

        expr = expand(s.value)
        reads = {a.name for a in expr.accesses()}
        if reads & temps or reads & written_fields:
            continue  # chain through an unexpandable temp, or the inputs
            # change after the definition point — recompute would be wrong
        expansions[t] = expr

    def rewrite(e: Expr) -> Expr:
        if (isinstance(e, FieldAccess) and e.name in expansions
                and e.offset != (0, 0, 0)):
            return expansions[e.name].shift(e.offset)
        return e.map_children(rewrite)

    comps = tuple(
        Computation(c.direction, tuple(
            Assign(s.target, rewrite(s.value), s.interval, s.region,
                   loc=s.loc)
            for s in c.statements))
        for c in stencil.computations)
    return dataclasses.replace(stencil, computations=comps)


# ---------------------------------------------------------------------------
# Horizontal (PARALLEL) stencils — K-slab grid
# ---------------------------------------------------------------------------


def _horizontal_kernel(stencil: Stencil, dom: DomainSpec, sched: Schedule,
                       statements, param_names, gaxis: int = 0,
                       chunk: int = 0):
    written = [w for w in stencil.written() if w in stencil.fields]
    fields = list(stencil.fields)
    temps = stencil.temporaries()
    nk = dom.nk
    ksz = {f: stencil.k_extent_of(f, nk)
           for f in list(fields) + list(temps)}
    # whole columns for K offsets, interface fields (never co-tiled with
    # centers in K) and level searches (whole coordinate columns)
    bk = k_block(stencil, sched, nk) or nk
    whole_k = bk == nk
    # whole-column stencils with no horizontal reads may tile J instead
    tile = _Tile(dom, j_tile(stencil, sched) if whole_k else 0)

    def kernel(*refs):
        n_in = len(fields) + len(param_names)
        in_refs = dict(zip(fields, refs[:len(fields)]))
        params = {p: refs[len(fields) + i][0] for i, p in enumerate(param_names)}
        out_refs = dict(zip(written, refs[n_in:]))
        # read-modify-write init: copy input blocks into outputs
        for w in written:
            out_refs[w][...] = in_refs[w][...]
        env: dict[str, Any] = {}
        # gaxis: the K (or J-tile) grid axis shifts right by one when a
        # member grid axis is prepended (ensemble batching)
        pid = pl.program_id(gaxis) if not whole_k or tile.bj else 0
        k0 = 0 if whole_k else pid * bk

        def make_read(rows):
            # ``rows`` is the current statement's iteration-row count: its
            # target's whole K extent (interface nk+1 / center nk) under
            # whole-K blocks, else the block size.  All block addressing is
            # from the trailing axes so a leading member-chunk dim (blocks
            # are (C, K, J, I) under ``chunk``) batches straight through.
            def read(name, off):
                di, dj, dk = off
                jsl, isl = tile.window(dj, di)
                ref = out_refs.get(name, in_refs.get(name))
                if name in env and (di, dj) == (0, 0):
                    if dk == 0 and env[name].shape[-3] == rows:
                        return env[name]
                    if ref is None:
                        # kernel-local temporary on a staggered extent or at
                        # a K offset: realign its rows onto this statement's
                        # iteration space (requires whole-K blocks)
                        return _k_align(env[name], dk, rows)
                if name in env and (ref is None or (di, dj) != (0, 0)):
                    # temporary at a horizontal offset, or a horizontal
                    # offset of freshly-written values (the ref's halo ring
                    # still holds input data) — unrepresentable in one kernel.
                    return None
                # K-offset / staggered reads require whole-K blocks (enforced
                # above).  For fields written earlier in a fused kernel this
                # reads the ref, which carries updated values in the window
                # and the input copy elsewhere — exact sequential-statement
                # semantics.
                return _kshift_read(ref, dk, rows, jsl, isl)

            def read_resolved(name, off):
                out = read(name, off)
                if out is None:
                    raise NotImplementedError(
                        f"offset read {off} of in-kernel temporary {name!r}; "
                        "allocate it as a field or fuse with OTF instead")
                return out

            return read_resolved

        def read_col(name, di, dj):
            # whole-K column stack for LevelSearch walks (the schedule
            # rules force bk == nk whenever a search is present)
            ref = out_refs.get(name, in_refs.get(name))
            if ref is None:
                if (di, dj) != (0, 0):
                    raise NotImplementedError(
                        f"horizontal-offset search read of in-kernel "
                        f"temporary {name!r}")
                return _Column.of_value(env[name])
            jsl, isl = tile.window(dj, di)
            return _Column.of_ref(ref, jsl, isl)

        nj_w, ni_w = tile.shape2d
        lead = (chunk,) if chunk else ()
        for st in statements:
            tgt_nk = ksz.get(st.target, nk)
            rows = tgt_nk if whole_k else bk
            kk = (jax.lax.broadcasted_iota(
                jnp.int32, (rows, nj_w, ni_w), 0) + k0)
            tshape = lead + (rows, nj_w, ni_w)
            pm = tile.mask(st.region, pid)
            val = _eval_block(st.value, make_read(rows), params,
                              read_col=read_col if whole_k else None, nk=nk,
                              live=pm)
            klo, khi = st.interval.resolve(tgt_nk)
            jsl, isl = tile.window()
            tgt_ref = out_refs.get(st.target)
            if tgt_ref is not None:
                cur = tgt_ref[..., jsl, isl]
            else:
                cur = env.get(st.target)
                if cur is None:
                    cur = jnp.zeros_like(kk, dtype=val.dtype if hasattr(val, "dtype")
                                         else jnp.float32) * 0.0
            dt = cur.dtype if hasattr(cur, "dtype") else jnp.float32
            val = jnp.broadcast_to(val, tshape).astype(dt)
            cur = jnp.broadcast_to(cur, tshape).astype(dt)
            mask = (kk >= klo) & (kk < khi)
            if pm is not None:
                mask = mask & pm[None]
            new = jnp.where(mask, val, cur)
            if tgt_ref is not None:
                tgt_ref[..., jsl, isl] = new
            env[st.target] = new
        return

    nip = dom.ni + 2 * dom.halo
    if tile.bj:
        grid = (tile.n_tiles(),)

        def block(f_rows):
            return pl.BlockSpec((f_rows, tile.bj, nip), lambda j: (0, j, 0))
    else:
        # a ragged last slab is an edge block: Pallas pads its reads and
        # drops its stores past nk
        grid = (pl.cdiv(nk, bk),)

        def block(f_rows):
            return pl.BlockSpec((f_rows, tile.rows(), nip),
                                lambda k: (k, 0, 0))

    in_specs = ([block(ksz[f] if whole_k else bk) for f in fields] +
                [_param_spec() for _ in param_names])
    out_specs = [block(ksz[w] if whole_k else bk) for w in written]
    return kernel, grid, in_specs, out_specs, written, bk


# ---------------------------------------------------------------------------
# Vertical solvers — full-column kernel, fori_loop over K
# ---------------------------------------------------------------------------


def _vertical_kernel(stencil: Stencil, dom: DomainSpec, sched: Schedule,
                     param_names, gaxis: int = 0, chunk: int = 0):
    written = [w for w in stencil.written() if w in stencil.fields]
    fields = list(stencil.fields)
    temps = stencil.temporaries()
    nk = dom.nk
    tile = _Tile(dom, j_tile(stencil, sched))
    ksz = {f: stencil.k_extent_of(f, nk)
           for f in list(fields) + list(temps)}

    # which (field, k-offset) pairs are loop-carried reads of written values
    carried: set[str] = set()
    for comp in stencil.computations:
        if comp.direction is Direction.PARALLEL:
            continue
        prev = -1 if comp.direction is Direction.FORWARD else 1
        w = set(comp.written())
        for st in comp.statements:
            for a in st.value.accesses():
                if a.name in w and a.offset[2] == prev:
                    carried.add(a.name)

    def kernel(*refs):
        n_in = len(fields) + len(param_names)
        in_refs = dict(zip(fields, refs[:len(fields)]))
        params = {p: refs[len(fields) + i][0] for i, p in enumerate(param_names)}
        out_refs = dict(zip(written, refs[n_in:len(refs) - len(temps)]))
        temp_refs = dict(zip(temps, refs[len(refs) - len(temps):]))
        for w in written:
            out_refs[w][...] = in_refs[w][...]

        jsl, isl = tile.window()
        shape2d = tile.shape2d
        lead = (chunk,) if chunk else ()
        jt = pl.program_id(gaxis) if tile.bj else 0

        def ref_of(name):
            if name in out_refs:
                return out_refs[name]
            if name in temp_refs:
                return temp_refs[name]
            return in_refs[name]

        def read_col(name, di, dj):
            js, is_ = tile.window(dj, di)
            return _Column.of_ref(ref_of(name), js, is_)

        # traced-K level addressing: ellipsis + a traced index is not a
        # Pallas ref indexer, so the leading chunk slice is explicit
        def lvl_get(ref, k, js, is_):
            return ref[:, k, js, is_] if chunk else ref[k, js, is_]

        def lvl_set(ref, k, js, is_, v):
            if chunk:
                ref[:, k, js, is_] = v
            else:
                ref[k, js, is_] = v

        for comp in stencil.computations:
            if comp.direction is Direction.PARALLEL:
                # elementwise pass inside a solver stencil (fused subgraphs
                # mix PARALLEL and solver computations in one mega-kernel)
                for st in comp.statements:
                    rows = ksz.get(st.target, nk)
                    kk = jax.lax.broadcasted_iota(
                        jnp.int32, (rows,) + shape2d, 0)

                    def read_par(name, off, rows=rows):
                        di, dj, dk = off
                        js, is_ = tile.window(dj, di)
                        return _kshift_read(ref_of(name), dk, rows, js, is_)
                    pm = tile.mask(st.region, jt)
                    val = _eval_block(st.value, read_par, params,
                                      read_col=read_col, nk=nk, live=pm)
                    klo, khi = st.interval.resolve(rows)
                    tgt = ref_of(st.target)
                    cur = tgt[..., jsl, isl]
                    val = jnp.broadcast_to(val, cur.shape).astype(cur.dtype)
                    mask = (kk >= klo) & (kk < khi)
                    if pm is not None:
                        mask = mask & pm[None]
                    tgt[..., jsl, isl] = jnp.where(mask, val, cur)
                continue

            forward = comp.direction is Direction.FORWARD
            prev = -1 if forward else 1
            lo = min(st.interval.resolve(ksz.get(st.target, nk))[0]
                     for st in comp.statements)
            hi = max(st.interval.resolve(ksz.get(st.target, nk))[1]
                     for st in comp.statements)
            carry_names = sorted(carried & set(comp.written()))

            def init_carry():
                return {n: jnp.zeros(lead + shape2d,
                                     dtype=out_refs[n].dtype if n in out_refs
                                     else temp_refs[n].dtype)
                        for n in carry_names}

            def body(step, carry):
                k = lo + step if forward else hi - 1 - step
                level: dict[str, Any] = {}

                def read_lvl(name, off):
                    di, dj, dk = off
                    js, is_ = tile.window(dj, di)
                    if (dk == prev and name in carry_names
                            and sched.carry_storage == "vreg"
                            and di == 0 and dj == 0):
                        return carry[name]
                    return lvl_get(ref_of(name), k + dk, js, is_)

                new_carry = dict(carry)
                for st in comp.statements:
                    sklo, skhi = st.interval.resolve(ksz.get(st.target, nk))
                    pm = tile.mask(st.region, jt)
                    val = _eval_block(st.value, read_lvl, params,
                                      read_col=read_col, nk=nk, live=pm)
                    tgt = ref_of(st.target)
                    cur = lvl_get(tgt, k, jsl, isl)
                    val = jnp.broadcast_to(val, cur.shape).astype(cur.dtype)
                    active = (k >= sklo) & (k < skhi)
                    if pm is not None:
                        val = jnp.where(pm, val, cur)
                    newv = jnp.where(active, val, cur)
                    lvl_set(tgt, k, jsl, isl, newv)
                    if st.target in carry_names:
                        new_carry[st.target] = newv
                return new_carry

            jax.lax.fori_loop(0, hi - lo, body, init_carry())
        return

    nip = dom.ni + 2 * dom.halo
    grid = (tile.n_tiles(),)

    def full(f_rows):
        # one whole column, or one J tile of it per grid step
        return pl.BlockSpec((f_rows, tile.rows(), nip), lambda j: (0, j, 0))

    in_specs = ([full(ksz[f]) for f in fields] +
                [_param_spec() for _ in param_names])
    # stencil temporaries live in VMEM scratch — fused subgraphs keep their
    # internalized transients out of HBM entirely (paper §VI-A)
    out_specs = [full(ksz[w]) for w in written]
    return kernel, grid, in_specs, out_specs, written, temps, full


# ---------------------------------------------------------------------------
# Vertical solvers, K-blocked — sequential grid over K slabs, carry in
# scratch (the production-depth schedule: nk ~ 80 columns fit VMEM)
# ---------------------------------------------------------------------------


def _vertical_kernel_kblocked(stencil: Stencil, dom: DomainSpec,
                              sched: Schedule, param_names, gaxis: int = 0,
                              chunk: int = 0):
    """K-blocked marching schedule for single-direction vertical solvers.

    The TPU grid executes *sequentially*, so the K dimension becomes a grid
    of ``cdiv(nk, block_k)`` slabs walked in marching order (top-down
    FORWARD, bottom-up BACKWARD via a reversed index map); each invocation
    holds one ``(block_k, J, I)`` VMEM block per field and marches its levels
    with an in-kernel ``fori_loop``.  Where ``block_k`` does not divide nk
    the bottom block is ragged: an edge block whose rows past nk are
    padding, and its loop marches only its real levels — the last block
    marched going down, the first going up, whose carry then starts at
    level nk - 1.  Loop-carried values — the marching-previous
    level of every field read at that offset, written *or* input — live in
    registers within the block and cross block boundaries through VMEM
    scratch planes that persist across grid steps.  Legality is exactly
    :func:`~repro.core.stencil.schedule.solver_k_blockable`.
    """
    written = [w for w in stencil.written() if w in stencil.fields]
    fields = list(stencil.fields)
    temps = stencil.temporaries()
    nk = dom.nk
    bk = sched.block_k
    n_blocks, last = k_blocks(nk, bk)
    dirs = {c.direction for c in stencil.computations
            if c.direction is not Direction.PARALLEL}
    forward = Direction.FORWARD in dirs
    carried = solver_carried_fields(stencil)

    njp, nip = dom.nj + 2 * dom.halo, dom.ni + 2 * dom.halo
    shape2d = (dom.nj + 2 * dom.extend[1], dom.ni + 2 * dom.extend[0])
    jsl, isl = _hwindow(dom, 0, 0)
    lead = (chunk,) if chunk else ()

    def kernel(*refs):
        n_in = len(fields) + len(param_names)
        in_refs = dict(zip(fields, refs[:len(fields)]))
        params = {p: refs[len(fields) + i][0]
                  for i, p in enumerate(param_names)}
        out_refs = dict(zip(written, refs[n_in:n_in + len(written)]))
        scratch = refs[n_in + len(written):]
        temp_refs = dict(zip(temps, scratch[:len(temps)]))
        carry_refs = dict(zip(carried, scratch[len(temps):]))
        for w in written:
            out_refs[w][...] = in_refs[w][...]

        g = pl.program_id(gaxis)
        # grid step g is the g-th block in *marching order*; the index maps
        # place it top-down (FORWARD) or bottom-up (BACKWARD).  Under a
        # member (or member-chunk) grid axis (gaxis=1) g still runs
        # 0..n_blocks-1 *per member/chunk*, so the first-block carry zeroing
        # below resets at every member/chunk boundary — no carry leaks.
        blk = g if forward else (n_blocks - 1 - g)
        k0 = blk * bk
        # levels this block marches: all of them, but in a ragged bottom block
        rows = bk if last == bk else jnp.where(blk == n_blocks - 1, last, bk)

        def ref_of(name):
            if name in out_refs:
                return out_refs[name]
            if name in temp_refs:
                return temp_refs[name]
            return in_refs[name]

        def dtype_of(name):
            return ref_of(name).dtype

        # traced-K block-local addressing (the chunk dim, when present, is
        # an explicit leading slice — ellipsis can't mix with a traced index)
        def lvl_get(ref, local, js, is_):
            return ref[:, local, js, is_] if chunk else ref[local, js, is_]

        def lvl_set(ref, local, js, is_, v):
            if chunk:
                ref[:, local, js, is_] = v
            else:
                ref[local, js, is_] = v

        # block-boundary carry: the previous block's last marched level,
        # staged through scratch; zeros on the first marching step (those
        # reads are dead under the interval masks, but the selects must see
        # well-defined numbers, not uninitialized VMEM)
        first = g == 0
        carry0 = {n: jnp.where(first, jnp.zeros(lead + shape2d, dtype_of(n)),
                               carry_refs[n][...])
                  for n in carried}

        def body(step, carry):
            local = step if forward else rows - 1 - step
            k = k0 + local  # absolute level, for interval masks

            def read_lvl(name, off):
                di, dj, dk = off
                if dk != 0:
                    # solver_k_blockable guarantees dk == marching-previous
                    # with zero horizontal offset: always the carry
                    return carry[name]
                js, is_ = _hwindow(dom, dj, di)
                return lvl_get(ref_of(name), local, js, is_)

            level_vals: dict[str, Any] = {}
            for comp in stencil.computations:
                for st in comp.statements:
                    sklo, skhi = st.interval.resolve(nk)
                    val = _eval_block(st.value, read_lvl, params)
                    tgt = ref_of(st.target)
                    cur = lvl_get(tgt, local, jsl, isl)
                    val = jnp.broadcast_to(val, cur.shape).astype(cur.dtype)
                    active = (k >= sklo) & (k < skhi)
                    if st.region is not None:
                        rm = _Tile(dom).mask(st.region, 0)
                        val = jnp.where(rm, val, cur)
                    newv = jnp.where(active, val, cur)
                    lvl_set(tgt, local, jsl, isl, newv)
                    level_vals[st.target] = newv

            new_carry = {}
            for n in carried:
                if n in level_vals:
                    new_carry[n] = level_vals[n]
                else:  # carried input (or untouched temp): this level's row
                    new_carry[n] = lvl_get(ref_of(n), local, jsl, isl)
            return new_carry

        final = jax.lax.fori_loop(0, rows, body, carry0)
        for n in carried:
            carry_refs[n][...] = final[n]
        return

    if forward:
        imap = lambda g: (g, 0, 0)  # noqa: E731
    else:
        imap = lambda g: (n_blocks - 1 - g, 0, 0)  # noqa: E731

    def block():
        return pl.BlockSpec((bk, njp, nip), imap)

    grid = (n_blocks,)
    in_specs = ([block() for _ in fields] +
                [_param_spec() for _ in param_names])
    out_specs = [block() for _ in written]
    return kernel, grid, in_specs, out_specs, written, temps, carried


def _pallas_call(kernel, *, name: str, vmem_limit: int, **kw):
    """``pl.pallas_call`` in the platform's mode, named after its stencil
    (the Mosaic kernel's name, as ``trace_name`` gives it), with the
    kernel's scoped-VMEM limit (compiled kernels only; 0 keeps the
    compiler's default)."""
    interpret = _compile.pallas_interpret()
    params = (pltpu.CompilerParams(vmem_limit_bytes=vmem_limit)
              if vmem_limit and not interpret else None)
    return pl.pallas_call(kernel, interpret=interpret,
                          name=_compile.trace_name(name),
                          compiler_params=params, **kw)


def _compile_kblocked(stencil: Stencil, dom: DomainSpec, sched: Schedule,
                      param_names, dtype, vmem_limit: int,
                      n_members: int | None = None, member_chunk: int = 0):
    kernel, grid, in_specs, out_specs, written, temps, carried = \
        _vertical_kernel_kblocked(stencil, dom, sched, param_names,
                                  gaxis=1 if n_members else 0,
                                  chunk=member_chunk)
    njp, nip = dom.nj + 2 * dom.halo, dom.ni + 2 * dom.halo
    shape2d = (dom.nj + 2 * dom.extend[1], dom.ni + 2 * dom.extend[0])
    # temporaries hold only the current block's rows; carry planes persist
    # across the sequential grid — both VMEM scratch, never HBM.  The
    # member/chunk grid axis is outermost and sequential, so scratch needs
    # no member axis beyond the in-block chunk dim: the carry zeroes itself
    # at each member's/chunk's first block.
    slead = (member_chunk,) if member_chunk else ()
    scratch = ([pltpu.VMEM(slead + (sched.block_k, njp, nip), dtype)
                for _ in temps] +
               [pltpu.VMEM(slead + shape2d, dtype) for _ in carried])
    if n_members:
        m_steps = n_members // member_chunk if member_chunk else n_members
        grid = (m_steps,) + grid
        in_specs = _member_specs(in_specs, chunk=member_chunk)
        out_specs = _member_specs(out_specs, chunk=member_chunk)
    lead = (n_members,) if n_members else ()

    def shape_of(name):
        return lead + dom.padded_shape(stencil.is_interface(name))

    def run(fields: Mapping[str, Any], params: Mapping[str, Any] | None = None):
        params = dict(params or {})
        args = ([jnp.asarray(fields[f]) for f in stencil.fields] +
                [jnp.asarray(params[p], dtype=dtype).reshape(1)
                 for p in param_names])
        out_shapes = [jax.ShapeDtypeStruct(shape_of(w), args[0].dtype)
                      for w in written]
        outs = _pallas_call(
            kernel, grid=grid, in_specs=in_specs, out_specs=out_specs,
            out_shape=out_shapes, scratch_shapes=scratch,
            name=stencil.name, vmem_limit=vmem_limit,
        )(*args)
        return dict(zip(written, outs))

    return _compile.kernel_jit(run, stencil.name)


# ---------------------------------------------------------------------------
# Public entry
# ---------------------------------------------------------------------------


def compile_pallas(stencil: Stencil, dom: DomainSpec, *,
                   schedule: Schedule | None = None, dtype=jnp.float32,
                   scratch_temps: bool = True,
                   n_members: int | None = None, member_chunk: int = 0,
                   vmem_limit: int = 0):
    """Compile a stencil into a Pallas-backed functional callable.

    Kernels run in interpret mode exactly when
    :func:`~repro.core.backend.compile.pallas_interpret` says so (the CPU).
    ``vmem_limit`` is the scoped-VMEM limit each compiled kernel asks the
    compiler for (0: the compiler's default).
    ``scratch_temps`` keeps vertical-solver temporaries in ``pltpu.VMEM``
    scratch (never materialized in HBM); the GPU backend passes False —
    the TPU memory-space spec does not exist in the Triton lowering — and
    falls back to temporaries as extra outputs.

    ``n_members=M`` batches M ensemble members through ONE ``pallas_call``
    per kernel: fields gain a leading member axis, the grid gains an
    outermost *sequential* member dimension, and every BlockSpec maps the
    member grid index onto a squeezed leading block dim — the kernel body
    is untouched and per-member blocks/VMEM are identical to M=1.

    ``member_chunk=C`` (requires ``n_members``, M divisible by C) is the
    hybrid ``batch="vmap:C,grid"`` lowering: the outermost grid axis walks
    M//C member *chunks*, each block carries a non-squeezed leading C dim,
    and kernel bodies batch the chunk through every statement.  Per-
    invocation VMEM scales by C (``vmem_footprint(member_chunk=C)``).
    """
    if member_chunk:
        if not n_members:
            raise ValueError("member_chunk requires n_members")
        member_chunk = min(member_chunk, n_members)
        if n_members % member_chunk:
            raise ValueError(
                f"member_chunk={member_chunk} must divide "
                f"n_members={n_members} (callers pad the member axis)")
        if member_chunk == n_members and n_members == 1:
            member_chunk = 0
    sched = schedule or default_schedule(stencil, dom)
    param_names = list(stencil.params)
    lead = (n_members,) if n_members else ()
    m_steps = (n_members // member_chunk if member_chunk else n_members)

    def shape_of(name):
        return lead + dom.padded_shape(stencil.is_interface(name))

    if (stencil.is_vertical_solver()
            and kblocked_applies(stencil, sched, dom.nk,
                                 scratch=scratch_temps)):
        # K-blocked marching: sequential grid over K slabs with the loop
        # carry staged through persistent VMEM scratch.  Requires TPU-style
        # scratch (the GPU backend's parallel thread-block grid cannot
        # order blocks, so it never enumerates this schedule).
        return _compile_kblocked(stencil, dom, sched, param_names, dtype,
                                 vmem_limit, n_members=n_members,
                                 member_chunk=member_chunk)

    if stencil.is_vertical_solver():
        kernel, grid, in_specs, out_specs, written, temps, block = \
            _vertical_kernel(stencil, dom, sched, param_names,
                             gaxis=1 if n_members else 0, chunk=member_chunk)

        # scratch refs arrive after the outputs in kernel argument order —
        # the same positions temporaries-as-outputs occupy, so the kernel
        # body is agnostic to which mechanism backs them
        slead = (member_chunk,) if member_chunk else ()
        if scratch_temps:
            scratch = [pltpu.VMEM(
                slead + (stencil.k_extent_of(t, dom.nk),)
                + tuple(block(1).block_shape[1:]), dtype) for t in temps]
        else:
            scratch = []
            out_specs = out_specs + [
                block(stencil.k_extent_of(t, dom.nk)) for t in temps]
        if n_members:
            grid = (m_steps,) + grid
            in_specs = _member_specs(in_specs, chunk=member_chunk)
            out_specs = _member_specs(out_specs, chunk=member_chunk)

        def run(fields: Mapping[str, Any], params: Mapping[str, Any] | None = None):
            params = dict(params or {})
            args = ([jnp.asarray(fields[f]) for f in stencil.fields] +
                    [jnp.asarray(params[p], dtype=dtype).reshape(1)
                     for p in param_names])
            out_shapes = [jax.ShapeDtypeStruct(shape_of(w), args[0].dtype)
                          for w in written]
            if not scratch_temps:
                out_shapes += [jax.ShapeDtypeStruct(shape_of(t), dtype)
                               for t in temps]
            outs = _pallas_call(
                kernel, grid=grid, in_specs=in_specs, out_specs=out_specs,
                out_shape=out_shapes, scratch_shapes=scratch,
                name=stencil.name, vmem_limit=vmem_limit,
            )(*args)
            return dict(zip(written, outs[:len(written)]))

        return _compile.kernel_jit(run, stencil.name)

    # horizontal stencil — inline offset-read temporaries (PPM's br[-1]),
    # then possibly split regions into separate kernels
    stencil = _inline_offset_temps(stencil)
    statements = [st for c in stencil.computations for st in c.statements]
    if sched.region_strategy == "split":
        main = [st for st in statements if st.region is None]
        regionals = [st for st in statements if st.region is not None]
        groups = ([main] if main else []) + [[st] for st in regionals]
    else:
        groups = [statements]

    compiled = []
    for grp in groups:
        kernel, grid, in_specs, out_specs, written, bk = _horizontal_kernel(
            stencil, dom, sched, grp, param_names,
            gaxis=1 if n_members else 0, chunk=member_chunk)
        if n_members:
            grid = (m_steps,) + grid
            in_specs = _member_specs(in_specs, chunk=member_chunk)
            out_specs = _member_specs(out_specs, chunk=member_chunk)
        compiled.append((kernel, grid, in_specs, out_specs, written))

    def run(fields: Mapping[str, Any], params: Mapping[str, Any] | None = None):
        params = dict(params or {})
        cur = {f: jnp.asarray(fields[f]) for f in stencil.fields}
        for kernel, grid, in_specs, out_specs, written in compiled:
            args = ([cur[f] for f in stencil.fields] +
                    [jnp.asarray(params[p], dtype=dtype).reshape(1)
                     for p in param_names])
            out_shapes = [jax.ShapeDtypeStruct(shape_of(w), cur[w].dtype)
                          for w in written]
            outs = _pallas_call(
                kernel, grid=grid, in_specs=in_specs, out_specs=out_specs,
                out_shape=out_shapes, name=stencil.name,
                vmem_limit=vmem_limit,
            )(*args)
            for w, o in zip(written, outs):
                cur[w] = o
        return {w: cur[w] for w in stencil.written() if w in stencil.fields}

    return _compile.kernel_jit(run, stencil.name)
