"""Pure-jnp lowering of Stencil IR — the debuggable oracle backend.

Array convention: fields are stored ``(K, J, I)`` — I contiguous, matching
the paper's FORTRAN data-layout finding (§VI-A.3); on TPU this puts I on the
lane dimension.  Horizontal allocations carry ``halo`` ghost cells per side;
K is allocated exactly.

The compiled callable is functional: it returns updated arrays for every
written field (GT4Py mutates in place; JAX cannot).
"""

from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Any, Mapping

import jax
import jax.numpy as jnp
import numpy as np

from ..stencil.domain import DomainSpec
from .compile import kernel_jit, varying_zeros
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
)

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


def _read(arr: jnp.ndarray, off, dom: DomainSpec, k_slice):
    """Window of ``arr`` shifted by offset over the (extended) write domain.

    K reads are shifted by ``dk`` against the statement's interval; stencil
    authors restrict intervals so shifted reads stay in [0, nk] (the same
    contract GT4Py enforces)."""
    di, dj, dk = off
    ei, ej = dom.extend
    h = dom.halo
    jsl = slice(h - ej + dj, h + dom.nj + ej + dj)
    isl = slice(h - ei + di, h + dom.ni + ei + di)
    lo, hi = k_slice
    ksl = slice(lo + dk, hi + dk)
    return arr[ksl, jsl, isl]


def _read_col(arr: jnp.ndarray, di: int, dj: int, dom: DomainSpec):
    """Full-K column stack of ``arr`` over the (extended) write window at a
    horizontal offset — what a :class:`LevelSearch` walks."""
    ei, ej = dom.extend
    h = dom.halo
    jsl = slice(h - ej + dj, h + dom.nj + ej + dj)
    isl = slice(h - ei + di, h + dom.ni + ei + di)
    return arr[:, jsl, isl]


def _bisect_levels(cwin, target, lo: int, hi: int):
    """Largest layer ``s`` in ``[lo, hi-1]`` with ``s == lo`` or
    ``cwin[s] <= target`` — the LevelSearch selection rule — found by
    ``lax.fori_loop`` bisection: O(log nk) gathers, O(1) trace size.

    ``cwin`` is ``(K_c, J, I)``; ``target`` broadcasts against its planes
    (``(rows, J, I)`` for a PARALLEL sweep, ``(1, J, I)`` per solver
    level); returns int32 indices of ``target``'s shape.
    """
    shape = jnp.broadcast_shapes(jnp.shape(target),
                                 (1,) + tuple(cwin.shape[1:]))
    # the bisection state varies over whatever shard_map axes cwin does
    zero = varying_zeros(shape, jnp.int32, cwin)
    lo_a, hi_a = zero + lo, zero + (hi - 1)
    n = hi - lo
    if n <= 1:
        return lo_a
    steps = int(math.ceil(math.log2(n)))

    def body(_, lh):
        lo_i, hi_i = lh
        mid = (lo_i + hi_i + 1) // 2
        cm = jnp.take_along_axis(cwin, mid, axis=0)
        take = cm <= target
        return jnp.where(take, mid, lo_i), jnp.where(take, hi_i, mid - 1)

    lo_a, _ = jax.lax.fori_loop(0, steps, body, (lo_a, hi_a))
    return lo_a


def _eval_search(e: LevelSearch, env, dom: DomainSpec, k_slice, eval_fn):
    """Lower a LevelSearch: bisect the coordinate column, then evaluate the
    body with FoundLevel reads gathered at the selected layer."""
    target = eval_fn(e.target)
    cwin = _read_col(env[e.coord], 0, 0, dom)
    lo, hi = e.resolve_bounds(dom.nk)
    squeeze = jnp.ndim(target) == 2  # per-level solver evaluation
    if squeeze:
        target = target[None]
    idx = _bisect_levels(cwin, target, lo, hi)

    def found(fl: FoundLevel):
        win = _read_col(env[fl.name], fl.di, fl.dj, dom)
        v = jnp.take_along_axis(win, idx + fl.dk, axis=0)
        return v[0] if squeeze else v

    out = eval_fn(e.body, found)
    return out


def _eval(e: Expr, env, dom: DomainSpec, k_slice=None, found=None):
    def ev(x, found=found):
        return _eval(x, env, dom, k_slice, found)

    if isinstance(e, Const):
        return e.value
    if isinstance(e, ParamRef):
        return env[e.name]
    if isinstance(e, FieldAccess):
        return _read(env[e.name], e.offset, dom, k_slice)
    if isinstance(e, LevelSearch):
        return _eval_search(e, env, dom, k_slice,
                            lambda x, f=None: ev(x, f))
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
    raise TypeError(f"cannot lower {e!r}")


def _region_mask(region: Region, dom: DomainSpec, dtype=bool):
    """(nj_w, ni_w) mask of the region within the extended write window."""
    ei, ej = dom.extend
    ilo, ihi, jlo, jhi = region.resolve(dom.ni, dom.nj)
    ii = jnp.arange(-ei, dom.ni + ei)
    jj = jnp.arange(-ej, dom.nj + ej)
    mi = (ii >= ilo) & (ii < ihi)
    mj = (jj >= jlo) & (jj < jhi)
    return mj[:, None] & mi[None, :]


def _apply_parallel(comp: Computation, env: dict, dom: DomainSpec,
                    stencil: Stencil) -> None:
    for st in comp.statements:
        # the statement's vertical iteration space is its *target's* K
        # extent: interface targets sweep [0, nk+1), centers [0, nk)
        klo, khi = st.interval.resolve(stencil.k_extent_of(st.target, dom.nk))
        if khi <= klo:
            continue
        val = _eval(st.value, env, dom, k_slice=(klo, khi))
        tgt = env[st.target]
        w = dom.write_window
        window = (slice(klo, khi), w[1], w[2])
        if st.region is not None:
            mask = _region_mask(st.region, dom)
            val = jnp.where(mask[None, :, :], val, tgt[window])
        val = jnp.broadcast_to(val, tgt[window].shape).astype(tgt.dtype)
        env[st.target] = tgt.at[window].set(val)


def _apply_vertical(comp: Computation, env: dict, dom: DomainSpec,
                    stencil: Stencil) -> None:
    """fori_loop over k; reads of already-written levels observe updates —
    exact forward/backward solver semantics.

    Only arrays this computation actually touches ride in the loop carry:
    fused mega-stencils hold many fields, and carrying untouched ones
    through every level is pure copy traffic."""
    written = comp.written()
    lo = min(st.interval.resolve(stencil.k_extent_of(st.target, dom.nk))[0]
             for st in comp.statements)
    hi = max(st.interval.resolve(stencil.k_extent_of(st.target, dom.nk))[1]
             for st in comp.statements)
    used = set()
    for st in comp.statements:
        used.add(st.target)
        for a in st.value.accesses():
            used.add(a.name)
    names = list(env.keys())
    arrays = {n: env[n] for n in names
              if hasattr(env[n], "shape") and getattr(env[n], "ndim", 0) == 3
              and n in used}
    scalars = {n: env[n] for n in names if n not in arrays}
    forward = comp.direction is Direction.FORWARD
    w = dom.write_window

    def body(step, arrs):
        k = lo + step if forward else hi - 1 - step
        local = dict(arrs)
        local.update(scalars)
        for st in comp.statements:
            sklo, skhi = st.interval.resolve(
                stencil.k_extent_of(st.target, dom.nk))
            tgt = local[st.target]

            def read2d(name, off):
                di, dj, dk = off
                ei, ej = dom.extend
                h = dom.halo
                jsl = slice(h - ej + dj, h + dom.nj + ej + dj)
                isl = slice(h - ei + di, h + dom.ni + ei + di)
                sl = jax.lax.dynamic_index_in_dim(local[name], k + dk, 0, keepdims=False)
                return sl[jsl, isl]

            def ev(e: Expr, found=None):
                if isinstance(e, Const):
                    return e.value
                if isinstance(e, ParamRef):
                    return scalars[e.name]
                if isinstance(e, FieldAccess):
                    return read2d(e.name, e.offset)
                if isinstance(e, LevelSearch):
                    # FORWARD/BACKWARD-legal: the search walks the whole
                    # coordinate column regardless of the solver's level
                    return _eval_search(e, local, dom, None,
                                        lambda x, f=None: ev(x, f))
                if isinstance(e, FoundLevel):
                    if found is None:
                        raise TypeError(
                            "FoundLevel outside a LevelSearch body")
                    return found(e)
                if isinstance(e, BinOp):
                    return _BIN[e.op](ev(e.a, found), ev(e.b, found))
                if isinstance(e, UnaryOp):
                    return _UNARY[e.op](ev(e.a, found))
                if isinstance(e, Pow):
                    return jnp.power(ev(e.a, found), ev(e.b, found))
                if isinstance(e, Where):
                    return jnp.where(ev(e.cond, found), ev(e.a, found),
                                     ev(e.b, found))
                if isinstance(e, Min):
                    return jnp.minimum(ev(e.a, found), ev(e.b, found))
                if isinstance(e, Max):
                    return jnp.maximum(ev(e.a, found), ev(e.b, found))
                raise TypeError(e)

            new2d = ev(st.value)
            cur2d = jax.lax.dynamic_index_in_dim(tgt, k, 0, keepdims=False)
            new2d = jnp.broadcast_to(new2d, cur2d[w[1], w[2]].shape).astype(tgt.dtype)
            if st.region is not None:
                mask = _region_mask(st.region, dom)
                new2d = jnp.where(mask, new2d, cur2d[w[1], w[2]])
            active = (k >= sklo) & (k < skhi)
            upd = cur2d.at[w[1], w[2]].set(jnp.where(active, new2d, cur2d[w[1], w[2]]))
            local[st.target] = jax.lax.dynamic_update_index_in_dim(tgt, upd, k, 0)
        return {n: local[n] for n in arrs}

    arrays = jax.lax.fori_loop(0, hi - lo, body, arrays)
    env.update(arrays)


def compile_jnp(stencil: Stencil, dom: DomainSpec, *, dtype=jnp.float32):
    """Compile a stencil into a jitted functional callable.

    Returns ``fn(fields: dict, params: dict) -> dict`` with updated written
    fields.  Temporaries are allocated internally.
    """
    temps = stencil.temporaries()

    def run(fields: Mapping[str, jnp.ndarray], params: Mapping[str, Any] | None = None):
        params = dict(params or {})
        env: dict[str, Any] = dict(params)
        for f in stencil.fields:
            env[f] = fields[f]
        like = fields[stencil.fields[0]] if stencil.fields else None
        for t in temps:
            env[t] = varying_zeros(
                dom.padded_shape(stencil.is_interface(t)), dtype, like)
        for comp in stencil.computations:
            if comp.direction is Direction.PARALLEL:
                _apply_parallel(comp, env, dom, stencil)
            else:
                _apply_vertical(comp, env, dom, stencil)
        return {f: env[f] for f in stencil.written() if f in stencil.fields}

    return kernel_jit(run, stencil.name)
