"""``compile_program`` — the single entry point of the compilation pipeline.

frontend → IR → graph → **passes** → backend → schedule/tuning: every
consumer (`StencilProgram.compile`, `orchestrate`, the FV3 dycore, examples,
benchmarks) funnels through here; no module outside this package touches a
lowering directly.

``opt_level`` applies the automatic optimization ladder of
:mod:`repro.core.passes` to a clone of the program before lowering: pruning,
strength reduction, cost-model-guided fusion and transfer-tuned schedule
assignment (paper §VI).  The compiled callable threads only *live* fields
between kernels: inputs a node actually consumes are auto-allocated when
missing, and transient containers are dropped from the environment after
their last reader — after fusion they never exist in HBM at all, because
fused subgraphs keep them as kernel-local scratch.

Per-node compiled runners are memoized in-process keyed by
(stencil fingerprint, schedule, backend, hardware, domain, interpret mode):
benchmark harnesses and tuning loops compile the same program repeatedly,
and re-lowering every node each time is pure waste.  Stats are observable
via :func:`compile_cache_stats`.
"""

from __future__ import annotations

import dataclasses
import functools
import hashlib
from typing import TYPE_CHECKING, Any, Callable, Mapping

import jax
import jax.numpy as jnp

from ..hardware import Hardware, detect_hardware
from ..stencil.schedule import Schedule
from .base import Backend, get_backend
from .batching import AUTO, BatchSpec, pad_members, parse_batch
from .cache import CacheStats, stencil_fingerprint

if TYPE_CHECKING:  # pragma: no cover - typing only, avoids a circular import
    from ..graph import Node, StencilProgram

_runner_memo: dict[tuple, Callable] = {}
_runner_stats = CacheStats()
_clear_hooks: list[Callable[[], None]] = []


def compile_cache_stats() -> dict:
    """In-process per-node compilation memo counters."""
    return _runner_stats.as_dict()


def register_cache_clear(fn: Callable[[], None]) -> None:
    """Register an auxiliary in-process compile memo to be dropped by
    :func:`clear_compile_cache` (e.g. the FV3 remap-runner memo) — one
    clearing entry point, no stale runners left behind a benchmark reset."""
    _clear_hooks.append(fn)


def clear_compile_cache() -> None:
    """Drop memoized runners AND reset the hit/miss counters — benchmark
    harnesses call this between runs and must not read stale numbers."""
    _runner_memo.clear()
    _runner_stats.reset()
    for fn in _clear_hooks:
        fn()


def donation_supported() -> bool:
    """True when buffer donation actually takes effect for the active JAX
    platform (purely platform-based, not per-backend).  The sequential CPU
    path neither benefits nor supports it — XLA emits a 'donated buffer was
    not usable' warning and ignores the hint — so callers gate
    ``donate=True`` through this predicate."""
    return jax.default_backend() in ("gpu", "tpu")


def pallas_interpret() -> bool:
    """True when Pallas kernels run in interpret mode — the one place this
    is decided, from the platform: the interpreter on the CPU (validation
    against the jnp oracle), native Mosaic/Triton kernels everywhere else.
    Lowerings read it when they build a kernel; nothing above them takes
    an ``interpret`` option."""
    return jax.default_backend() == "cpu"


def jit_program(fn: Callable, backend: "str | Backend", *,
                donate: bool = False) -> Callable:
    """``jax.jit`` of a program built from ``backend``'s kernels — every
    top-level jit of a compiled program goes through here: the input
    fields dict (argument 0) is donated when asked and the platform honours
    it (:func:`donation_supported`), and a program holding native kernels
    takes the attached chip's ``Hardware.kernel_step_options`` (v5e: XLA's
    VMEM memory-space assignment off).  JAX takes compiler options only on
    an outermost jit, so a jitted program must not be nested in another."""
    opts = None
    if get_backend(backend).native_kernels and not pallas_interpret():
        hw = detect_hardware()
        opts = dict(hw.kernel_step_options) if hw else None
    return jax.jit(fn, compiler_options=opts or None,
                   donate_argnums=(0,) if donate and donation_supported()
                   else ())


#: longest stencil name a compiled program's metadata carries whole
TRACE_NAME_MAX = 128


def trace_name(name: str) -> str:
    """A stencil's name, or a node's label (``<name>#<n>``), as scopes and
    kernels show it: whole up to ``TRACE_NAME_MAX`` characters, else cut
    and ended by a hash of the whole.  Fusing many stencils joins their
    names, and XLA drops the enclosing scopes from an op whose op_name
    would pass about a thousand characters."""
    stem, sep, tag = name.partition("#")
    if len(stem) > TRACE_NAME_MAX:
        digest = hashlib.sha1(stem.encode()).hexdigest()[:8]
        stem = f"{stem[:TRACE_NAME_MAX - 9]}~{digest}"
    return stem + sep + tag


def kernel_jit(fn: Callable, name: str) -> Callable:
    """``jax.jit`` of one stencil's runner under the stencil's name, so its
    ops read ``jit(<name>)`` in the compiled program's metadata and in a
    profile (every runner function is otherwise called ``run``)."""
    fn.__name__ = fn.__qualname__ = trace_name(name)
    return jax.jit(fn)


def varying_zeros(shape, dtype, like=None) -> jax.Array:
    """Zeros that vary over the same ``shard_map`` manual axes as ``like``.

    Inside ``shard_map`` every value's type carries the mesh axes it varies
    over; plain zeros vary over none, so a loop carry or scan state seeded
    with them changes type on its first update and is refused.  Outside
    ``shard_map`` (or without a template) these are plain zeros."""
    z = jnp.zeros(shape, dtype)
    vma = getattr(jax.typeof(like), "vma", None) if like is not None else None
    return jax.lax.pcast(z, tuple(vma), to="varying") if vma else z


def compile_stencil(stencil, dom, *, backend: "str | Backend" = "jnp",
                    schedule: Schedule | None = None,
                    hardware: Hardware | str | None = None,
                    dtype=None,
                    memoize: bool = True,
                    n_members: int | None = None,
                    batch: "str | BatchSpec" = "vmap") -> Callable:
    """Compile one stencil through a registered backend (memoized).

    ``n_members``/``batch`` select the ensemble lowering (see
    :meth:`Backend.compile_stencil` for the accepted spec forms); both are
    part of the memo key — a member-batched runner accepts different shapes
    than a single-member one, and a chunked runner a different launch
    structure than an unchunked one.  ``batch="vmap:auto"`` resolves the
    chunk size through the cost model before compiling.
    """
    be = get_backend(backend)
    hw = be.resolve_hw(hardware)
    spec = parse_batch(batch)
    if n_members and spec.chunk == AUTO:
        from ..autotune import tune_member_chunk

        spec = dataclasses.replace(spec, chunk=tune_member_chunk(
            stencil, dom, hw=hw, backend=be.name, n_members=n_members))
    if not memoize:
        return be.compile_stencil(stencil, dom, schedule=schedule,
                                  hardware=hw, dtype=dtype,
                                  n_members=n_members, batch=spec)
    key = (stencil_fingerprint(stencil), dom,
           None if schedule is None else dataclasses.astuple(schedule),
           be.name, hw.name, pallas_interpret(),
           None if dtype is None else str(dtype),
           n_members, spec.token if n_members else None)
    runner = _runner_memo.get(key)
    if runner is None:
        _runner_stats.misses += 1
        runner = be.compile_stencil(stencil, dom, schedule=schedule,
                                    hardware=hw, dtype=dtype,
                                    n_members=n_members, batch=spec)
        _runner_memo[key] = runner
    else:
        _runner_stats.hits += 1
    return runner


def _resolve_override(node: "Node", overrides) -> Schedule | None:
    if not overrides:
        return node.schedule
    # per-instance label wins over per-motif base name
    if node.label in overrides:
        return overrides[node.label]
    if node.base_name in overrides:
        return overrides[node.base_name]
    return node.schedule


def _liveness(program: "StencilProgram", runners) -> tuple[list, list]:
    """Static dataflow facts for the run loop.

    ``inputs``: program fields some node consumes before any node writes
    them — the only fields the runner must materialize (auto-allocating the
    rest would resurrect exactly the transient HBM arrays fusion removed).

    ``drop_after[i]``: transient fields whose last use is node ``i`` — they
    leave the environment immediately, so XLA sees their true live ranges.
    """
    inputs: list[str] = []
    written: set[str] = set()
    last_use: dict[str, int] = {}
    for i, (n, _) in enumerate(runners):
        for f in n.stencil.fields:
            if f not in written and f not in inputs:
                inputs.append(f)
            last_use[f] = i
        written |= set(n.writes())
    drop_after: list[list[str]] = [[] for _ in runners]
    for f, i in last_use.items():
        decl = program.fields.get(f)
        if decl is not None and decl.transient:
            drop_after[i].append(f)
    return inputs, drop_after


def compile_program(program: "StencilProgram",
                    backend: "str | Backend" = "jnp", *,
                    hardware: Hardware | str | None = None,
                    schedule_overrides: Mapping[str, Schedule] | None = None,
                    donate: bool = False,
                    opt_level: int = 0,
                    n_members: int | None = None,
                    batch: "str | BatchSpec" = "vmap",
                    verify: str | None = None) -> Callable:
    """Compile a whole :class:`StencilProgram` into one functional callable
    ``fn(fields: dict, params: dict) -> dict`` (live fields threaded).

    ``backend`` is a registry name (``"jnp"``, ``"pallas-tpu"``,
    ``"pallas-gpu"``) or a :class:`Backend` instance; ``hardware`` a
    descriptor or registered name (defaults to the backend's);
    ``schedule_overrides`` maps node labels (``"al_x#3"``) or motif base
    names (``"al_x"``) to :class:`Schedule` objects, overriding any
    schedule stored on the node.

    ``opt_level`` (0–4) selects the automatic optimization pipeline
    (:mod:`repro.core.rewrite`; level 4 adds the pattern stencil rewrites)
    applied to a *clone* of ``program`` — the caller's graph is never
    mutated.  ``donate=True`` donates the
    input fields dict to the jitted step, but only on platforms where XLA
    honors donation (TPU/GPU); on CPU the flag degrades to a plain ``jit``
    instead of triggering per-call XLA warnings (see
    :func:`donation_supported`).

    ``n_members=M`` threads an ensemble/member axis through the whole
    pipeline: every program field gains a leading axis of extent M, the
    optimizer's cost model amortizes launch overhead across members, and
    each backend lowers the axis per ``batch``.  Accepted ``batch`` forms
    (see :mod:`repro.core.backend.batching`):

      * ``"vmap"`` — one :func:`jax.vmap` over all M (the jnp strategy;
        XLA owns the mapping; working set scales with M);
      * ``"grid"`` — members on the backend's launch structure (Pallas:
        outermost sequential grid axis, same kernel count as M=1);
      * ``"vmap:C"`` (= ``"vmap:C,scan"``) — the hybrid: a program-level
        :func:`jax.lax.scan` over ceil(M/C) chunks, each a C-wide vmap —
        one chunk's working set is live at a time (memory streaming);
      * ``"vmap:C,grid"`` — the chunk loop becomes the outermost
        sequential Pallas grid axis with C-member blocks inside each
        kernel (falls back to the scan form on gridless backends);
      * ``"grid:C"`` — scan over chunks of a C-member grid axis;
      * ``"vmap:auto"`` / ``"vmap:auto,grid"`` — C picked per program by
        the cost model (:func:`~repro.core.autotune.tune_program_chunk`).

    M not divisible by C replicate-pads the last member to a whole chunk
    and slices the pad off after — bit-identical for the real members.
    Malformed specs (unknown modes, bad chunk sizes) raise ``ValueError``.
    The batch dimension is a compilation-layer decision, not a
    per-stencil rewrite.

    ``verify`` selects the independent static verifier
    (:mod:`repro.core.analysis`): ``"off"`` skips it; ``"passes"`` runs it
    on the optimizer's input program and after every pass (violations raise
    :class:`~repro.core.errors.VerificationError` attributed to the
    responsible pass); ``"full"`` additionally verifies the program even
    when no pass runs (``opt_level=0``).  ``None`` (default) resolves via
    the ``REPRO_VERIFY`` environment variable, falling back to ``"passes"``
    under pytest/CI and ``"off"`` elsewhere.

    The returned callable exposes introspection attributes:
    ``n_kernels`` (number of compiled runners — invariant under chunking),
    ``opt_report`` (the :class:`~repro.core.passes.PipelineReport`,
    ``None`` at level 0), ``program`` (the graph actually lowered),
    ``input_fields`` and ``transient_inputs`` (fields auto-allocated when
    the caller omits them — empty of transients once fusion has localized
    them), ``k_blocks`` (``(node label, block_k, nk)`` of each kernel that
    walks K in slabs or blocks), plus ``n_members`` / ``batch`` /
    ``batch_spec`` / ``member_chunk`` / ``n_chunks`` describing the ensemble
    lowering.
    """
    be = get_backend(backend)
    hw = be.resolve_hw(hardware)
    spec = parse_batch(batch)
    if n_members and spec.chunk == AUTO:
        from ..autotune import tune_program_chunk

        spec = dataclasses.replace(spec, chunk=tune_program_chunk(
            program, backend=be.name, hw=hw, n_members=n_members))
    # effective spec for this M: clamp C, degrade grid-outer chunk loops on
    # gridless backends to the scan form, collapse single-chunk scans
    eff = spec
    if n_members and eff.chunk:
        C = eff.chunk_for(n_members)
        loop = eff.loop if be.member_grid else "scan"
        if loop == "scan" and C >= n_members:
            eff = BatchSpec(mode=eff.mode)
        else:
            eff = BatchSpec(mode=eff.mode, chunk=C, loop=loop)
    chunk_scan = bool(n_members and eff.chunk and eff.loop == "scan")
    chunk_grid = bool(n_members and eff.chunk and eff.loop == "grid")
    Mp = eff.padded_members(n_members) if (chunk_scan or chunk_grid) else \
        (n_members or 0)
    from ..analysis.verifier import resolve_verify_mode

    verify_mode = resolve_verify_mode(verify)
    opt_report = None
    if opt_level:
        from ..passes import optimize_program

        program, opt_report = optimize_program(
            program, opt_level=opt_level, backend=be.name, hardware=hw,
            n_members=n_members or 1,
            member_chunk=eff.chunk if n_members else 0,
            verify=verify_mode)
    elif verify_mode == "full":
        # no pass runs at level 0, but "full" still audits the program
        # actually being lowered
        from ..analysis import verify_program

        verify_program(program, raise_on_violation=True)
    # under loop="scan" each kernel sees one C-member chunk; under
    # loop="grid" the kernels own the chunk loop over the padded axis
    stencil_members, stencil_batch = n_members, eff
    if chunk_scan:
        stencil_members, stencil_batch = eff.chunk, BatchSpec(mode=eff.mode)
    elif chunk_grid:
        stencil_members = Mp
    runners = []
    k_blocks = []
    for s in program.states:
        for n in s.nodes:
            dom = program.node_dom(n)
            sched = _resolve_override(n, schedule_overrides)
            r = compile_stencil(n.stencil, dom, backend=be, schedule=sched,
                                hardware=hw, n_members=stencil_members,
                                batch=stencil_batch)
            runners.append((n, r))
            bk = be.k_block(n.stencil, dom, sched, hw)
            if bk:
                k_blocks.append((n.label, bk, dom.nk))

    fields_decl = program.fields
    dom = program.dom
    inputs, drop_after = _liveness(program, runners)

    def _exec(env: dict, params: dict, lead: tuple) -> dict:
        template = next((v for v in env.values()
                         if hasattr(v, "dtype")), None)
        for name in inputs:
            if name not in env:
                # consumed before any write and not supplied — the backend
                # owns allocation, never the user (paper §IV-A).  The
                # barrier keeps XLA from folding the zeros into their
                # readers, so batched and unbatched programs round alike
                # (the ensemble's bit-equality contract)
                decl = fields_decl[name]
                env[name] = jax.lax.optimization_barrier(varying_zeros(
                    lead + dom.padded_shape(decl.interface), decl.dtype,
                    template))
        for i, (n, r) in enumerate(runners):
            ins = {f: env[f] for f in n.stencil.fields}
            ps = {p: params[p] for p in n.stencil.params}
            # the node's label names its ops in the compiled program's
            # metadata (and so in a profile)
            with jax.named_scope(trace_name(n.label)):
                env.update(r(ins, ps))
            for f in drop_after[i]:
                env.pop(f, None)
        return env

    if chunk_scan:
        C, nC = eff.chunk, Mp // eff.chunk

        def run(fields: dict, params: dict | None = None) -> dict:
            params = dict(params or {})
            chunks = {k: pad_members(jnp.asarray(v), n_members, Mp)
                      .reshape((nC, C) + jnp.shape(v)[1:])
                      for k, v in fields.items()}

            def body(_, ch):
                # transients allocated inside the body are C-member wide:
                # only one chunk's working set is ever live
                return None, _exec(dict(ch), params, (C,))

            _, out = jax.lax.scan(body, None, chunks)
            return {k: v.reshape((Mp,) + v.shape[2:])[:n_members]
                    for k, v in out.items()}
    elif chunk_grid and Mp != n_members:
        def run(fields: dict, params: dict | None = None) -> dict:
            env = {k: pad_members(jnp.asarray(v), n_members, Mp)
                   for k, v in fields.items()}
            out = _exec(env, dict(params or {}), (Mp,))
            return {k: v[:n_members] for k, v in out.items()}
    else:
        lead0 = (Mp,) if n_members else ()

        def run(fields: dict, params: dict | None = None) -> dict:
            return _exec(dict(fields), dict(params or {}), lead0)

    fn: Callable = run
    donated = False
    if donate:
        jitted = jit_program(run, be, donate=True)
        donated = donation_supported()

        @functools.wraps(run)
        def fn(fields: dict, params: dict | None = None) -> dict:
            return jitted(fields, params)

    fn.n_kernels = len(runners)
    fn.k_blocks = tuple(k_blocks)
    fn.n_members = n_members
    fn.batch = spec.token if n_members else None
    fn.batch_spec = eff if n_members else None
    fn.member_chunk = eff.chunk if (n_members and eff.chunk) else None
    fn.n_chunks = (Mp // eff.chunk) if (chunk_scan or chunk_grid) else None
    fn.opt_report = opt_report
    fn.verify_mode = verify_mode
    fn.program = program
    fn.input_fields = tuple(inputs)
    fn.transient_inputs = tuple(
        f for f in inputs
        if f in fields_decl and fields_decl[f].transient)
    fn.donated = donated
    return fn
