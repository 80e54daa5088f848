"""Stencil schedules — the tunable hardware-mapping attributes (paper §V-A).

A :class:`Schedule` captures, per stencil node, exactly the knobs the paper
enumerates for its ``StencilComputation`` library nodes:

 * iteration order (which dimension is unit-stride → TPU lane dim),
 * tiling and tile sizes in each dimension,
 * map-vs-loop per dimension (parallel grid dim vs in-kernel loop),
 * local-storage kind for loop carries (re-read VMEM vs VREG carry),
 * horizontal-region strategy (predicated full-domain map vs split kernels).

Validity rules (the paper generates "a list of feasible options") are
*hardware-parameterized*: every enumeration takes a
:class:`~repro.core.hardware.Hardware` descriptor instead of reading
module-level TPU constants.  On TPU, vertical solvers cannot map K to the
grid; blocks must fit VMEM; the lane dim should be a multiple of 128 and the
sublane of 8 for f32.  On GPU the block is a thread-block tile: the
unit-stride extent aligns to the warp width and the per-block working set
must fit shared memory, which favors small IJ tiles with K as grid or loop.
"""

from __future__ import annotations

import dataclasses
import itertools
from typing import Iterator

from ..hardware import Hardware, resolve_hardware
from .domain import DomainSpec
from .ir import Direction, Stencil, expr_contains_level_search


@dataclasses.dataclass(frozen=True)
class Schedule:
    # tile sizes; 0 means "whole extent".  For vertical solvers a nonzero
    # ``block_k`` (with ``k_as_grid=False``) selects the K-blocked marching
    # schedule: the K grid dimension is *sequential* (TPU grids iterate in
    # order), each invocation marches ``block_k`` levels in VMEM and the
    # loop carry crosses block boundaries through persistent scratch —
    # production-depth columns (nk ~ 80) fit VMEM without giving up the
    # sequential solve.  ``block_k`` need not divide nk: the last block of
    # a K slab or march is ragged (:func:`k_blocks`).
    block_i: int = 0
    block_j: int = 0
    block_k: int = 8
    # map-vs-loop: True → dimension is a parallel grid dim
    k_as_grid: bool = True  # horizontal stencils only
    # local storage for vertical-solver carries: "vreg" | "vmem"
    carry_storage: str = "vreg"
    # horizontal regions: "predicated" | "split"
    region_strategy: str = "predicated"
    # unit-stride dimension; "I" is the paper's (FORTRAN-layout) choice
    unit_stride: str = "I"

    def describe(self) -> str:
        return (f"bi={self.block_i or 'full'},bj={self.block_j or 'full'},"
                f"bk={self.block_k or 'full'},kgrid={self.k_as_grid},"
                f"carry={self.carry_storage},region={self.region_strategy}")

    def to_dict(self) -> dict:
        """JSON-serializable form (persistent tuning-cache payload)."""
        return dataclasses.asdict(self)

    @classmethod
    def from_dict(cls, d: dict) -> "Schedule":
        return cls(**d)


def solver_carried_fields(stencil: Stencil) -> list[str]:
    """Fields (written *or* input) read at the marching-previous level
    inside a sequential computation — the values a K-blocked schedule must
    carry across block boundaries in scratch."""
    out: list[str] = []
    for c in stencil.computations:
        if c.direction is Direction.PARALLEL:
            continue
        prev = -1 if c.direction is Direction.FORWARD else 1
        for s in c.statements:
            for a in s.value.accesses():
                if a.offset[2] == prev and a.name not in out:
                    out.append(a.name)
    return out


def solver_k_blockable(stencil: Stencil) -> bool:
    """True when a vertical solver admits the K-blocked marching schedule.

    The blocked lowering marches all levels in one direction with a
    single-level carry, so it requires:

     * exactly one sequential direction (a FORWARD+BACKWARD stencil like
       the Thomas algorithm needs two passes over the column — it keeps
       whole-column blocks);
     * no interface fields (nk+1 rows cannot co-tile with nk-row centers);
     * every K read either at the current level or at the marching-previous
       level with zero horizontal offset (deeper or offset reads would
       reach outside the block and its one-level carry);
     * no marching-previous read of a field a *later* computation writes —
       reference semantics run each computation as a separate full K
       sweep, so such a read must observe the later computation's
       pre-sweep values, which the per-level interleaved march cannot
       provide (its carry already holds the updated level);
     * no :class:`~repro.core.stencil.ir.LevelSearch` (the search reads
       whole coordinate columns).
    """
    dirs = {c.direction for c in stencil.computations
            if c.direction is not Direction.PARALLEL}
    if len(dirs) != 1 or stencil.has_interface_fields():
        return False
    prev = -1 if Direction.FORWARD in dirs else 1
    # fields written strictly after each computation, in program order
    later_written: list[set[str]] = []
    suffix: set[str] = set()
    for c in reversed(stencil.computations):
        later_written.append(set(suffix))
        suffix |= set(c.written())
    later_written.reverse()
    for i, c in enumerate(stencil.computations):
        for s in c.statements:
            if expr_contains_level_search(s.value):
                return False
            for a in s.value.accesses():
                dk = a.offset[2]
                if c.direction is Direction.PARALLEL:
                    if dk != 0:
                        return False
                elif dk == prev:
                    if a.offset[0] != 0 or a.offset[1] != 0:
                        return False
                    if a.name in later_written[i]:
                        return False
                elif dk != 0:
                    return False
    return True


def kblocked_applies(stencil: Stencil, sched: Schedule, nk: int, *,
                     scratch: bool = True) -> bool:
    """THE K-blocked dispatch predicate — the single definition shared by
    the lowering (``compile_pallas``, which passes its backend's scratch
    capability), the footprint model (:func:`vmem_footprint`) and the cost
    model (``model_cost``), so the model never prices a blocked kernel the
    lowering would decline in favor of whole-column (or vice versa).  The
    block need not divide nk: the last one marches the levels left."""
    return (scratch and bool(sched.block_k) and sched.block_k < nk
            and solver_k_blockable(stencil))


def k_block(stencil: Stencil, sched: Schedule, nk: int, *,
            scratch: bool = True) -> int:
    """Levels per block in which the Pallas lowering walks K under
    ``sched`` (0: whole columns) — K slabs of a horizontal stencil on the
    grid, or the blocks a solver marches (:func:`kblocked_applies`).
    Shared by the lowering, :func:`vmem_footprint` and ``step.k_slabs``."""
    if stencil.is_vertical_solver():
        return (sched.block_k
                if kblocked_applies(stencil, sched, nk, scratch=scratch)
                else 0)
    if (not sched.k_as_grid or not sched.block_k or sched.block_k >= nk
            or stencil.has_k_offsets() or stencil.has_interface_fields()
            or stencil.has_level_search()):
        return 0
    return sched.block_k


def k_blocks(nk: int, bk: int) -> tuple[int, int]:
    """``(n_blocks, last)``: blocks of ``bk`` levels covering ``nk``, and
    the levels of the last one, which is ragged where ``bk`` does not
    divide ``nk`` (its rows past nk are padding: never stored, and never
    marched)."""
    n = -(-nk // bk)
    return n, nk - (n - 1) * bk


def j_tileable(stencil: Stencil) -> bool:
    """True when whole-column blocks may be split into tiles of J rows: the
    stencil reads no horizontal neighbour (a row tile carries no halo rows)
    and K cannot be tiled instead — K offsets, interface fields, level
    searches, or a solver the K-blocked march cannot take."""
    if stencil.max_halo():
        return False
    if stencil.is_vertical_solver():
        return not solver_k_blockable(stencil)
    return (stencil.has_k_offsets() or stencil.has_interface_fields()
            or stencil.has_level_search())


def j_tile(stencil: Stencil, sched: Schedule) -> int:
    """Rows per J tile the Pallas lowering uses under ``sched`` (0: the
    block holds the whole padded plane) — shared by the lowering and
    :func:`vmem_footprint`, like :func:`kblocked_applies`."""
    return sched.block_j if (sched.block_j and j_tileable(stencil)) else 0


def _dims(dom: DomainSpec) -> tuple[int, int, int, int]:
    """(nk, nj, ni, halo) of a DomainSpec."""
    return dom.nk, dom.nj, dom.ni, dom.halo


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def vmem_footprint(stencil: Stencil, sched: Schedule, dom_shape,
                   dtype_bytes: int = 4, member_chunk: int = 0,
                   hw: Hardware | str | None = None) -> int:
    """Bytes of fast on-chip memory one kernel invocation holds under this
    schedule (VMEM on TPU; shared-memory tile on GPU); callers compare it
    against ``hw.vmem_bytes``.  ``dom_shape`` is the kernel's
    :class:`~repro.core.stencil.domain.DomainSpec`.

    On TPU this counts the blocks the Pallas lowering allocates: each block
    spans the halo-padded plane (or a J tile of it, :func:`j_tile`), its
    second-minor and minor extents rounded up to ``(hw.sublane, hw.lane)``;
    every input and output block is double-buffered by the pipeline, and
    temporaries and carry planes are held once.  K-interface buffers carry
    one extra level (they only ever appear in whole-K blocks).  K-blocked
    vertical solvers hold ``block_k`` rows per field.  The model is an
    upper bound: Mosaic may pack an unaligned plane tighter than the
    rounded one.

    ``member_chunk=C`` prices a chunk-batched invocation
    (``batch="vmap:C,grid"``): every block and carry buffer gains a leading
    C-member extent, so the footprint scales by C — the feasibility limit
    on how wide the inner batch of the hybrid chunk loop can go."""
    hw = resolve_hardware(hw)
    nk, nj, ni, halo = _dims(dom_shape)
    mult = max(1, member_chunk)
    vertical = stencil.is_vertical_solver()
    bk = k_block(stencil, sched, nk) or nk
    whole_k = bk == nk

    def rows(name):
        return bk + 1 if (whole_k and stencil.is_interface(name)) else bk

    carried = (len(solver_carried_fields(stencil))
               if vertical and not whole_k else 0)
    temps = stencil.temporaries()
    if hw.kind == "gpu":
        # a thread-block tile of the interior, held once
        plane = mult * (sched.block_i or ni) * (sched.block_j or nj) \
            * dtype_bytes
        return (sum(rows(f) for f in tuple(stencil.fields) + tuple(temps))
                + carried) * plane
    plane = (mult * _round_up(j_tile(stencil, sched) or nj + 2 * halo,
                              hw.sublane)
             * _round_up(ni + 2 * halo, hw.lane) * dtype_bytes)
    written = [w for w in stencil.written() if w in stencil.fields]
    pipelined = sum(rows(f) for f in tuple(stencil.fields) + tuple(written))
    return (2 * pipelined + sum(rows(t) for t in temps) + carried) * plane


def _k_slabs(nk: int) -> list[int]:
    """K-slab heights a horizontal kernel's grid can walk; the last slab is
    ragged where one does not divide nk."""
    return [b for b in (16, 8, 4, 1) if b < nk]


def _k_heights(heights, nk: int) -> list[int]:
    """K block heights below nk, cheapest first: the fewest blocks plus
    padded rows of a ragged last block (a grid step and a padded plane
    cost about alike), the tallest first among equals.  Where every height
    divides nk this is tallest first."""
    def cost(b):
        n, last = k_blocks(nk, b)
        return n + b - last
    return sorted((b for b in heights if b < nk), key=lambda b: (cost(b), -b))


def _feasible_tpu(stencil: Stencil, dom_shape, dtype_bytes: int,
                  hw: Hardware) -> Iterator[Schedule]:
    nk, nj, ni, halo = _dims(dom_shape)
    vertical = stencil.is_vertical_solver()
    has_regions = any(s.region is not None
                      for c in stencil.computations for s in c.statements)
    sublane = hw.sublane
    # interface fields (nk+1 levels) never co-tile with centers in K: any
    # K slab of mixed extents would misalign block boundaries, so interface
    # stencils only get whole-column blocks (same rule as K offsets below);
    # level-search stencils read whole coordinate columns, same rule
    if vertical:
        # whole-column, plus K-blocked marching slabs where the solver
        # admits them (single direction, one-level carries): the K grid
        # dimension is sequential on TPU, the carry crosses block
        # boundaries in scratch — production-depth columns fit VMEM
        k_opts = [0]
        if solver_k_blockable(stencil):
            k_opts += [b for b in (4, 8, 16, 32) if b < nk]
    else:
        k_opts = ([0] if (stencil.has_interface_fields()
                          or stencil.has_level_search())
                  else _k_slabs(nk) + [0])
    # the lowering keeps the whole lane extent in every block; J tiles
    # exist only where whole columns are required and no halo row is read
    j_opts = [0]
    if j_tileable(stencil):
        j_opts += [b for b in (sublane, 4 * sublane, 16 * sublane)
                   if b < nj + 2 * halo]
    region_opts = ["predicated", "split"] if has_regions else ["predicated"]
    carry_opts = ["vreg", "vmem"] if vertical else ["vreg"]
    for bj, bk, reg, carry in itertools.product(
            j_opts, bk_dedup(k_opts, nk), region_opts, carry_opts):
        if vertical and bk != 0 and carry != "vreg":
            continue  # K-blocked marching always carries in registers
        s = Schedule(block_i=0, block_j=bj, block_k=bk,
                     k_as_grid=not vertical, carry_storage=carry,
                     region_strategy=reg)
        if vmem_footprint(stencil, s, dom_shape, dtype_bytes,
                          hw=hw) > hw.vmem_bytes:
            continue
        # stencils with k offsets need whole-K blocks (no overlapping blocks
        # across the K grid on TPU)
        if not vertical and stencil.has_k_offsets() and bk != 0:
            continue
        yield s


def _fitting_tpu(stencil: Stencil, base: Schedule, dom_shape,
                 dtype_bytes: int, hw: Hardware, budget: int) -> Schedule:
    """``base`` if its blocks fit ``budget``; else the first K block (in the
    order of :func:`_k_heights`) or the largest J tile the stencil admits
    that does (the one with the smallest blocks if none fits)."""
    nk = _dims(dom_shape)[0]
    cands = [base]
    if stencil.is_vertical_solver() and solver_k_blockable(stencil):
        cands += [dataclasses.replace(base, block_k=b, carry_storage="vreg")
                  for b in _k_heights((32, 16, 8, 4), nk)]
    if j_tileable(stencil):
        cands += [dataclasses.replace(base, block_j=b * hw.sublane)
                  for b in (16, 4, 1)]
    if not stencil.is_vertical_solver() and not (
            stencil.has_k_offsets() or stencil.has_interface_fields()
            or stencil.has_level_search()):
        cands += [dataclasses.replace(base, block_k=b)
                  for b in _k_heights(_k_slabs(nk), nk)]
    fp = [vmem_footprint(stencil, s, dom_shape, dtype_bytes, hw=hw)
          for s in cands]
    for s, b in zip(cands, fp):
        if b <= budget:
            return s
    return cands[fp.index(min(fp))]


def _feasible_gpu(stencil: Stencil, dom_shape, dtype_bytes: int,
                  hw: Hardware) -> Iterator[Schedule]:
    """GPU tiling rules: thread-block tiles whose unit-stride extent is a
    warp multiple and whose working set fits shared memory.  Full-domain
    blocks are allowed only when they fit (they essentially never do), so
    the enumeration is dominated by small IJ tiles — the paper's DaCe/GPU
    maps — with K either a grid dimension or an in-kernel loop."""
    nk, nj, ni, _ = _dims(dom_shape)
    vertical = stencil.is_vertical_solver()
    has_regions = any(s.region is not None
                      for c in stencil.computations for s in c.statements)
    warp = hw.lane
    i_opts = [w for w in (warp, 2 * warp, 4 * warp) if w <= ni] or [ni]
    j_opts = [1, 2, 4, 8]
    # K-offset / interface / level-search stencils need whole-K blocks
    # (same rule as TPU); otherwise small K slabs map to the thread-block z
    # dimension.  Vertical solvers stay whole-column: the K-blocked
    # marching schedule needs a *sequential* grid with persistent scratch,
    # which a parallel thread-block grid cannot provide.
    if (vertical or stencil.has_k_offsets() or stencil.has_interface_fields()
            or stencil.has_level_search()):
        k_opts = [0]
    else:
        k_opts = bk_dedup([1, 2, 4], nk)
    region_opts = ["predicated", "split"] if has_regions else ["predicated"]
    # GPU vertical carries live in registers; the "vmem" variant models
    # spilling the carry to local/shared memory for A/B comparison.
    carry_opts = ["vreg", "vmem"] if vertical else ["vreg"]
    for bi, bj, bk, reg, carry in itertools.product(
            i_opts, j_opts, k_opts, region_opts, carry_opts):
        s = Schedule(block_i=bi, block_j=bj, block_k=bk,
                     k_as_grid=not vertical, carry_storage=carry,
                     region_strategy=reg)
        if vmem_footprint(stencil, s, dom_shape, dtype_bytes,
                          hw=hw) > hw.vmem_bytes:
            continue
        yield s


def bk_dedup(k_opts: list[int], nk: int) -> list[int]:
    """Drop K-block sizes ≥ nk (equivalent to whole-extent 0)."""
    out = []
    for bk in k_opts:
        v = bk if bk < nk else 0
        if v not in out:
            out.append(v)
    return out


def feasible_schedules(stencil: Stencil, dom_shape, dtype_bytes: int = 4,
                       hw: Hardware | str | None = None) -> Iterator[Schedule]:
    """Enumerate valid schedules for a stencil on a local domain (paper §V-A:
    'for each node we generate a list of feasible options'), under the
    tiling rules of ``hw`` (TPU lane/sublane/VMEM vs GPU warp/smem)."""
    hw = resolve_hardware(hw)
    if hw.kind == "gpu":
        yield from _feasible_gpu(stencil, dom_shape, dtype_bytes, hw)
    else:
        yield from _feasible_tpu(stencil, dom_shape, dtype_bytes, hw)


def default_schedule(stencil: Stencil, dom_shape, dtype_bytes: int = 4,
                     hw: Hardware | str | None = None) -> Schedule:
    """The backend's default before any tuning (paper's 'Default' row in
    Table III): untransformed storage choices (memory-backed carries,
    predicated regions) on the largest tile the hardware's feasibility
    rules allow — whole-domain blocks on TPU where they fit VMEM, else the
    largest K block or J tile that does; a warp-aligned tile that fits
    shared memory on GPU (whole-domain blocks are never GPU-feasible, so
    defaulting to them would contradict ``feasible_schedules``)."""
    hw = resolve_hardware(hw)
    vertical = stencil.is_vertical_solver()
    whole_k = (vertical or stencil.has_interface_fields()
               or stencil.has_level_search())
    if hw.kind == "gpu":
        nk, nj, ni, _ = _dims(dom_shape)
        bi = min(ni, 4 * hw.lane)
        bj = 8
        while (vmem_footprint(stencil,
                              Schedule(block_i=bi, block_j=bj,
                                       block_k=0 if whole_k else 1,
                                       k_as_grid=not vertical),
                              dom_shape, dtype_bytes, hw=hw) > hw.vmem_bytes
               and bj > 1):
            bj //= 2
        return Schedule(block_i=bi, block_j=bj,
                        block_k=0 if whole_k else 1,
                        k_as_grid=not vertical,
                        carry_storage="vmem", region_strategy="predicated")
    base = Schedule(block_i=0, block_j=0, block_k=0,
                    k_as_grid=not vertical,
                    carry_storage="vmem", region_strategy="predicated")
    return _fitting_tpu(stencil, base, dom_shape, dtype_bytes, hw,
                        hw.vmem_bytes)


def heuristic_schedule(stencil: Stencil, dom_shape, dtype_bytes: int = 4,
                       hw: Hardware | str | None = None) -> Schedule:
    """Initial heuristics (paper §VI-A), per hardware kind.

    TPU: the tallest K slab whose blocks fill at most half of VMEM for
    horizontal stencils (full IJ for halo reuse); full-column blocks with
    VREG carries for vertical solvers, or the largest K block / J tile
    that fits where whole columns do not.

    GPU: a warp-aligned IJ thread-block tile with a one-level K slab —
    occupancy over reuse, the classic CUDA stencil starting point.
    """
    hw = resolve_hardware(hw)
    nk, nj, ni, _ = _dims(dom_shape)
    if stencil.is_vertical_solver():
        return _fitting_tpu(
            stencil, Schedule(block_i=0, block_j=0, block_k=0,
                              k_as_grid=False, carry_storage="vreg",
                              region_strategy="predicated"),
            dom_shape, dtype_bytes, hw, hw.vmem_bytes)
    # whole-column blocks only for K-offset / interface / level-search
    # stencils (interface and center fields never co-tile in K; searches
    # read whole coordinate columns) — decided BEFORE the GPU branch so the
    # fusion cost model never prices these stencils on a K slab the
    # lowering would silently refuse
    whole_k = (stencil.has_k_offsets() or stencil.has_interface_fields()
               or stencil.has_level_search())
    if hw.kind == "gpu":
        bk = 0 if whole_k else 1
        bi = min(ni, 4 * hw.lane)
        bj = 4
        while (vmem_footprint(stencil, Schedule(block_i=bi, block_j=bj,
                                                block_k=bk), dom_shape,
                              dtype_bytes, hw=hw) > hw.vmem_bytes
               and bj > 1):
            bj //= 2
        return Schedule(block_i=bi, block_j=bj, block_k=bk, k_as_grid=True,
                        carry_storage="vreg", region_strategy="predicated")
    base = Schedule(block_i=0, block_j=0, block_k=0, k_as_grid=True,
                    carry_storage="vreg", region_strategy="predicated")
    if whole_k:
        return _fitting_tpu(stencil, base, dom_shape, dtype_bytes, hw,
                            hw.vmem_bytes)
    return _fitting_tpu(stencil, dataclasses.replace(base, block_k=nk),
                        dom_shape, dtype_bytes, hw, hw.vmem_bytes // 2)
