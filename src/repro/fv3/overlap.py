"""Halo/compute overlap by domain splitting (paper §IV-C latency hiding).

Inside ``shard_map``, XLA schedules by data dependence: when a stencil
program consumes the *exchanged* arrays, every output point — including the
deep interior that never reads a ghost cell — transitively depends on the
``ppermute`` rounds, so compute serializes behind communication.  This
module breaks that false dependence the way production FV3 does, by
splitting each exchanged program's domain:

 * the **full local domain** is computed from the *pre-exchange* state —
   no dependence on the collectives, so the interior compute launches
   concurrently with the ppermute rounds.  Because every program validates
   ``node extent + stencil reach <= halo`` (``propagate_extents``), outputs
   at distance >= halo from the interior boundary never read a ghost cell
   and are exact;
 * four **edge strips** of width ``halo`` are recomputed *after* the
   exchange from slabs of the fresh arrays, and stitched over the stale
   band.  Horizontal regions are translated into strip-local coordinates so
   the paper's edge stencils (§IV-B) fire at the same physical columns.

The stitched result equals running the program on the exchanged state over
the whole interior; ghost cells of the outputs are stale, which is the
existing contract — every consumer re-exchanges before reading halos.
"""

from __future__ import annotations

import copy
import dataclasses
from typing import Callable, Mapping

from repro.core.backend import compile_program
from repro.core.graph import StencilProgram
from repro.core.stencil.domain import DomainSpec
from repro.core.stencil.ir import Assign, Computation, Region


def _translate_bound(b: tuple[int, int] | None, n_global: int,
                     origin: int) -> tuple[int, int] | None:
    """Rebase a region bound (base, offset) from the tile-local interior onto
    a strip whose interior starts at ``origin``; out-of-strip absolutes
    resolve to empty masks naturally."""
    if b is None:
        return None
    return (0, b[0] * n_global + b[1] - origin)


def _translate_region(r: Region, ni_g: int, nj_g: int,
                      oi: int, oj: int) -> Region:
    return Region(
        i_lo=_translate_bound(r.i_lo, ni_g, oi),
        i_hi=_translate_bound(r.i_hi, ni_g, oi),
        j_lo=_translate_bound(r.j_lo, nj_g, oj),
        j_hi=_translate_bound(r.j_hi, nj_g, oj),
    )


def _strip_program(program: StencilProgram, dom: DomainSpec,
                   oi: int, oj: int, tag: str) -> StencilProgram:
    """Clone ``program`` onto a strip domain with regions rebased."""
    q = StencilProgram(f"{program.name}/{tag}", dom)
    q.fields = {k: dataclasses.replace(v) for k, v in program.fields.items()}
    q.params = list(program.params)
    q.states = copy.deepcopy(program.states)
    q.extents_propagated = program.extents_propagated
    ni_g, nj_g = program.dom.ni, program.dom.nj
    for n in q.all_nodes():
        comps = tuple(
            Computation(c.direction, tuple(
                Assign(s.target, s.value, s.interval,
                       None if s.region is None else
                       _translate_region(s.region, ni_g, nj_g, oi, oj),
                       loc=s.loc)
                for s in c.statements))
            for c in n.stencil.computations)
        n.stencil = dataclasses.replace(n.stencil, computations=comps)
    return q


def written_fields(program: StencilProgram) -> tuple[str, ...]:
    """Non-transient program fields some node writes — the externally
    visible outputs the stitched runner must return."""
    out: list[str] = []
    for n in program.all_nodes():
        for f in n.writes():
            decl = program.fields.get(f)
            if decl is not None and not decl.transient and f not in out:
                out.append(f)
    return tuple(out)


def make_overlapped_runner(program: StencilProgram, *,
                           backend: str = "jnp", hardware=None,
                           opt_level: int = 0,
                           verify: str | None = None) -> Callable | None:
    """Compile ``program`` into ``fn(stale, fresh, params) -> outputs``.

    ``stale`` are the pre-exchange arrays (interior compute, overlappable
    with the halo collectives), ``fresh`` the post-exchange arrays (edge
    strips).  Returns ``None`` when the local interior is too small to hold
    a strip-free core (``n <= 2*halo``) — callers fall back to the
    sequential exchange-then-compute ordering.
    """
    dom = program.dom
    ni, nj, h, nk = dom.ni, dom.nj, dom.halo, dom.nk
    if ni <= 2 * h or nj <= 2 * h:
        return None

    full_run = compile_program(program, backend, hardware=hardware,
                               opt_level=opt_level,
                               verify=verify)
    outputs = written_fields(program)

    # (tag, strip dom, interior origin (oi, oj), input slab, src, dst):
    # ``src`` selects the strip runner's write window in slab coordinates,
    # ``dst`` the same cells in full-array coordinates.
    W = slice(None)
    specs = [
        ("W", DomainSpec(ni=h, nj=nj, nk=nk, halo=h), (0, 0),
         (W, W, slice(0, 3 * h)),
         (W, slice(h, h + nj), slice(h, 2 * h)),
         (W, slice(h, h + nj), slice(h, 2 * h))),
        ("E", DomainSpec(ni=h, nj=nj, nk=nk, halo=h), (ni - h, 0),
         (W, W, slice(ni - h, ni + 2 * h)),
         (W, slice(h, h + nj), slice(h, 2 * h)),
         (W, slice(h, h + nj), slice(ni, ni + h))),
        ("S", DomainSpec(ni=ni, nj=h, nk=nk, halo=h), (0, 0),
         (W, slice(0, 3 * h), W),
         (W, slice(h, 2 * h), slice(h, h + ni)),
         (W, slice(h, 2 * h), slice(h, h + ni))),
        ("N", DomainSpec(ni=ni, nj=h, nk=nk, halo=h), (0, nj - h),
         (W, slice(nj - h, nj + 2 * h), W),
         (W, slice(h, 2 * h), slice(h, h + ni)),
         (W, slice(nj, nj + h), slice(h, h + ni))),
    ]
    # strips compile at most at level 1: fusion trials and per-strip-domain
    # schedule tuning buy nothing on an h-wide recompute band, and level 1
    # (prune + strength-reduce) is exactly the bit-affecting prefix of the
    # ladder — levels 2–4 (fusion, schedules, and the pattern rewrites:
    # stencil-combine, cross-computation CSE) all preserve values bit for
    # bit, so strip and full-domain outputs stay bit-aligned across the
    # stitch seam at every opt_level
    strip_level = min(opt_level, 1)
    strips = []
    for tag, sdom, (oi, oj), slab, src, dst in specs:
        sp = _strip_program(program, sdom, oi, oj, tag)
        run = compile_program(sp, backend, hardware=hardware,
                              opt_level=strip_level,
                              verify=verify)
        strips.append((run, slab, src, dst))

    def runner(stale: Mapping, fresh: Mapping,
               params: Mapping | None = None) -> dict:
        # interior: full-domain compute on the pre-exchange state — no data
        # dependence on the ppermute rounds, so XLA overlaps it with them
        out = full_run(dict(stale), params)
        stitched = {k: out[k] for k in outputs}
        for run, slab, src, dst in strips:
            slab_in = {f: v[slab] for f, v in fresh.items()}
            so = run(slab_in, params)
            for k in outputs:
                stitched[k] = stitched[k].at[dst].set(so[k][src])
        return stitched

    runner.outputs = outputs
    runner.full_run = full_run
    runner.n_strips = len(strips)
    return runner
