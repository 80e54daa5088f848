"""FV3-lite dynamical core driver (paper Fig. 2 structure).

Sub-stepping hierarchy, exactly the paper's:
  * remapping loop (``k_split``): tracer advection + vertical remap
  * acoustic loop  (``n_split``): c_sw-lite → riem_solver_c → halo exchange
                                  → d_sw-lite (FVT + Smagorinsky) → exchange

Two execution modes share all stencil programs:
  * sequential (single device, 6-tile global arrays, reference halo
    exchange) — the paper's §IV-A "sequential mode" for fine-grained testing;
  * distributed (``shard_map`` over a ("tile","y","x") mesh with the
    ppermute halo updater) — the production path; the halo collectives sit
    off the interior critical path so XLA's scheduler overlaps them.

Vertical remapping compiles through the stencil toolchain like everything
else: the cumulative interface pressures and mass integrals are FORWARD
stencils on K-interface fields, the data-dependent level search of the old
hand-written ``jnp.interp`` path is the DSL's ``index_search`` construct
(lowered to ``lax.fori_loop`` bisection in jnp and in-kernel marching loops
in Pallas — O(nk) program IR at any column depth), and the remapped means
come from exact interface differencing (mass-conserving by construction).
Both step factories roll their sub-stepping loops into ``jax.lax.scan``
inside one jitted step — a single dispatch per physics step.
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
import time
import warnings
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import StencilProgram, compile_program
from repro.core.backend import jit_program, register_cache_clear
from repro.core.backend.batching import BatchSpec, parse_batch, scan_chunked
from repro.core.stencil import DomainSpec
from repro.core.stencil.schedule import k_blocks
from . import stencils as S
from .halo import exchange_reference, make_halo_exchanger
from .overlap import make_overlapped_runner
from .topology import Decomposition, sphere_center

TRACER_NAMES = ("qvapor", "qliquid", "qice", "qrain")


@dataclasses.dataclass(frozen=True)
class FV3Config:
    npx: int = 24            # interior points per tile per dim
    nk: int = 16             # vertical levels (80 in production)
    halo: int = 6
    layout: tuple[int, int] = (1, 1)   # ranks per tile (py, px)
    dt: float = 0.02         # acoustic step (nondimensional units)
    n_split: int = 4         # acoustic substeps per remap step
    k_split: int = 2         # remap steps per physics step
    n_tracers: int = 4
    beta: float = 4.0        # implicit-solver diagonal weight
    smag_coeff: float = 0.02
    ptop: float = 10.0
    dtype: str = "float32"

    @property
    def n_local(self) -> int:
        assert self.npx % self.layout[1] == 0 and self.layout[0] == self.layout[1]
        return self.npx // self.layout[1]

    @property
    def tracers(self) -> tuple[str, ...]:
        return TRACER_NAMES[: self.n_tracers]

    def decomposition(self) -> Decomposition:
        return Decomposition(self.layout, self.n_local, self.halo)

    def local_dom(self) -> DomainSpec:
        return DomainSpec(ni=self.n_local, nj=self.n_local, nk=self.nk,
                          halo=self.halo)

    def seq_dom(self) -> DomainSpec:
        return DomainSpec(ni=self.npx, nj=self.npx, nk=self.nk, halo=self.halo)


def add_fvtp2d(prog: StencilProgram, q: str, out: str, tag: str) -> None:
    """Lin–Rood 2D transport of field ``q`` → ``out`` (10 stencil nodes —
    the recurring motif transfer tuning exploits)."""
    t = lambda n: f"{tag}_{n}"
    for name in ["alx", "fxi", "qx", "aly2", "fyf",
                 "aly", "fyi", "qy", "alx2", "fxf"]:
        prog.declare(t(name), transient=True)
    prog.add(S.al_x, {"q": q, "al": t("alx")})
    prog.add(S.fx_ppm, {"q": q, "al": t("alx"), "cx": "cx", "fx": t("fxi")})
    prog.add(S.inner_x_update, {"q": q, "fx": t("fxi"), "qx": t("qx")})
    prog.add(S.al_y, {"q": t("qx"), "al": t("aly2")})
    prog.add(S.fy_ppm, {"q": t("qx"), "al": t("aly2"), "cy": "cy", "fy": t("fyf")})
    prog.add(S.al_y, {"q": q, "al": t("aly")})
    prog.add(S.fy_ppm, {"q": q, "al": t("aly"), "cy": "cy", "fy": t("fyi")})
    prog.add(S.inner_y_update, {"q": q, "fy": t("fyi"), "qy": t("qy")})
    prog.add(S.al_x, {"q": t("qy"), "al": t("alx2")})
    prog.add(S.fx_ppm, {"q": t("qy"), "al": t("alx2"), "cx": "cx", "fx": t("fxf")})
    prog.add(S.flux_divergence, {"q": q, "fx": t("fxf"), "fy": t("fyf"),
                                 "qout": out})


def build_csw_program(cfg: FV3Config, dom: DomainSpec) -> StencilProgram:
    """c_sw-lite + riem_solver_c (runs between halo exchanges)."""
    p = StencilProgram("c_sw+riem", dom)
    for f in ["u", "v", "delp", "pt", "w", "cosa", "sina"]:
        p.declare(f)
    # delpc/ptc escape the program (the dycore driver exchanges delpc and
    # feeds both into d_sw) — they must stay materialized, so they are NOT
    # transient; fusion passes may localize everything below.
    for f in ["delpc", "ptc"]:
        p.declare(f)
    for f in ["div", "pe", "aa", "bb", "cc", "rhs", "pp", "cflux"]:
        p.declare(f, transient=True)
    p.add(S.divergence, {"u": "u", "v": "v", "div": "div"})
    p.add(S.csw_update, {"delp": "delp", "pt": "pt", "div": "div",
                         "delpc": "delpc", "ptc": "ptc"})
    # the paper's §IV-B region-corrected edge flux (C-grid correction motif)
    p.add(S.edge_flux, {"flux": "cflux", "velocity": "u", "velocity_c": "v",
                        "cosa": "cosa", "sina": "sina"})
    p.add(S.precompute_pe, {"delp": "delpc", "pe": "pe"})
    p.add(S.riem_coeffs, {"delp": "delpc", "ptc": "ptc", "aa": "aa",
                          "bb": "bb", "cc": "cc", "rhs": "rhs", "w": "w"})
    p.add(S.tridiag_solve, {"aa": "aa", "bb": "bb", "cc": "cc", "rhs": "rhs",
                            "pp": "pp"})
    p.add(S.w_update, {"w": "w", "pp": "pp", "delp": "delpc", "dt": "dt2"},
          params={"dt": "dt2"})
    p.propagate_extents()
    return p


def build_dsw_program(cfg: FV3Config, dom: DomainSpec) -> StencilProgram:
    """d_sw-lite: vorticity/KE/Smagorinsky + FVT of delp and pt."""
    p = StencilProgram("d_sw", dom)
    for f in ["u", "v", "delp", "pt", "delpc"]:
        p.declare(f)
    for f in ["vort", "ke", "damp", "pe", "cx", "cy"]:
        p.declare(f, transient=True)
    p.declare("delp_out")
    p.declare("pt_out")
    p.add(S.vorticity, {"u": "u", "v": "v", "vort": "vort"})
    p.add(S.kinetic_energy, {"u": "u", "v": "v", "ke": "ke"})
    p.add(S.smagorinsky_diffusion, {"delpc": "delpc", "vort": "vort",
                                    "damp": "damp", "dt": "smag_dt"},
          params={"dt": "smag_dt"})
    p.add(S.precompute_pe, {"delp": "delp", "pe": "pe"})
    # Courant numbers from the time-centered (pre-update) winds — must
    # precede wind_update, which overwrites u/v in place.
    p.add(S.courant_x, {"u": "u", "cx": "cx"})
    p.add(S.courant_y, {"v": "v", "cy": "cy"})
    p.add(S.wind_update, {"u": "u", "v": "v", "ke": "ke", "vort": "vort",
                          "damp": "damp", "pe": "pe"})
    add_fvtp2d(p, "delp", "delp_out", "dp")
    add_fvtp2d(p, "pt", "pt_out", "pt")
    p.propagate_extents()
    return p


def build_tracer_program(cfg: FV3Config, dom: DomainSpec) -> StencilProgram:
    p = StencilProgram("tracer_2d", dom)
    p.declare("u")
    p.declare("v")
    for f in ["cx", "cy"]:
        p.declare(f, transient=True)
    p.add(S.courant_x, {"u": "u", "cx": "cx"})
    p.add(S.courant_y, {"v": "v", "cy": "cy"})
    for q in cfg.tracers:
        p.declare(q)
        p.declare(f"{q}_out")
        add_fvtp2d(p, q, f"{q}_out", q)
    p.propagate_extents()
    return p


def default_params(cfg: FV3Config) -> dict:
    dtdx = cfg.dt  # unit metric: dx = dy = 1 grid unit
    return {
        "dt": cfg.dt, "dt2": 0.5 * cfg.dt, "smag_dt": cfg.smag_coeff * cfg.dt,
        "dtdx": dtdx, "dtdy": dtdx, "rdx": 1.0, "rdy": 1.0,
        "ptop": cfg.ptop, "beta": cfg.beta, "rk": 1.0 / cfg.nk,
    }


# ---------------------------------------------------------------------------
# Vertical remapping (paper Fig. 2 orange region) — DSL stencil program
# ---------------------------------------------------------------------------


def vertical_remap_reference(cfg: FV3Config, delp: jax.Array,
                             fields: dict) -> tuple:
    """The pre-DSL hand-written remap, kept as the regression oracle.

    Known bug (why the DSL path replaced it): the ``maximum(delp_ref,
    1e-10)`` denominator floor silently violates mass conservation whenever
    a reference layer is thinner than the floor — ``sum(q * delp)`` is no
    longer preserved.  The stencil path divides by the exact interface
    difference instead.  It also bypasses the pass manager, the Pallas
    backends and the tuning cache entirely.
    """
    nk = cfg.nk
    ptop = cfg.ptop
    pe = ptop + jnp.concatenate(
        [jnp.zeros_like(delp[:1]), jnp.cumsum(delp, axis=0)], axis=0)
    psfc = pe[-1]
    sigma = jnp.arange(nk + 1, dtype=delp.dtype) / nk
    pe_ref = ptop + sigma[:, None, None] * (psfc[None] - ptop)
    delp_ref = pe_ref[1:] - pe_ref[:-1]

    def remap_one(f):
        # cumulative mass-weighted integral at Lagrangian interfaces
        F = jnp.concatenate(
            [jnp.zeros_like(f[:1]), jnp.cumsum(f * delp, axis=0)], axis=0)
        shape = pe.shape[1:]
        Fcols = F.reshape(nk + 1, -1).T        # (ncol, nk+1)
        pcols = pe.reshape(nk + 1, -1).T
        prefs = pe_ref.reshape(nk + 1, -1).T
        Fi = jax.vmap(jnp.interp)(prefs, pcols, Fcols)  # (ncol, nk+1)
        Fi = Fi.T.reshape(nk + 1, *shape)
        return (Fi[1:] - Fi[:-1]) / jnp.maximum(delp_ref, 1e-10)

    out = {k: remap_one(v) for k, v in fields.items()}
    return delp_ref, out


def build_remap_program(cfg: FV3Config, dom: DomainSpec,
                        fields: tuple[str, ...] | None = None, *,
                        unrolled_interp: bool = False) -> StencilProgram:
    """First-order conservative Lagrangian→reference remap as a stencil
    program on K-interface fields: FORWARD cumulative builds of ``pe`` /
    ``pe_ref`` and the per-field mass integrals, the ``index_search`` level
    search onto the reference interfaces (lowered to real loops by every
    backend — O(nk) program IR instead of the old O(nk²) static-offset
    unrolling), and exact interface differencing for the remapped means.
    Compiling through ``compile_program`` puts the remap under the pass
    manager, the Pallas lowerings and the persistent tuning cache like
    every other motif.

    ``unrolled_interp=True`` swaps the pre-construct unrolled
    interpolation back in — the A/B baseline the trace-time benchmarks
    compare against.
    """
    if fields is None:
        fields = ("pt", "w", "u", "v", *cfg.tracers)
    p = StencilProgram("vertical_remap", dom)
    p.declare("delp")
    p.declare("delp_out")
    for t in ("cum", "total"):
        p.declare(t, transient=True)
    for t in ("pe", "pe_ref"):
        p.declare(t, transient=True, interface=True)
    p.add(S.lagrangian_pe, {"delp": "delp", "pe": "pe"})
    p.add(S.column_total, {"delp": "delp", "cum": "cum", "total": "total"})
    p.add(S.reference_pe, {"total": "total", "pe_ref": "pe_ref"})
    p.add(S.remap_delp, {"pe_ref": "pe_ref", "delp_out": "delp_out"})
    interp = (S.interface_interp_stencil(cfg.nk) if unrolled_interp
              else S.interface_interp)
    for q in fields:
        p.declare(q)
        p.declare(f"{q}_out")
        p.declare(f"{q}_fm", transient=True, interface=True)
        p.declare(f"{q}_fi", transient=True, interface=True)
        p.add(S.cumsum_mass, {"q": q, "delp": "delp", "fm": f"{q}_fm"})
        p.add(interp, {"fm": f"{q}_fm", "pe": "pe", "pe_ref": "pe_ref",
                       "fi": f"{q}_fi"})
        p.add(S.remap_field, {"fi": f"{q}_fi", "pe_ref": "pe_ref",
                              "q_out": f"{q}_out"})
    p.propagate_extents()
    return p


def make_vertical_remap(cfg: FV3Config, dom: DomainSpec,
                        fields: tuple[str, ...], *, backend: str = "jnp",
                        hardware=None, opt_level: int = 0):
    """Compile the remap program; returns ``remap(delp, field_dict, params)
    -> (delp_ref, remapped_dict)`` plus the compiled runner (for
    introspection) as ``remap.run``."""
    prog = build_remap_program(cfg, dom, fields)
    run = compile_program(prog, backend, hardware=hardware,
                          opt_level=opt_level)

    def remap(delp, field_dict, params):
        ins = {"delp": delp, **{q: field_dict[q] for q in fields}}
        out = run(ins, params)
        return out["delp_out"], {q: out[f"{q}_out"] for q in fields}

    remap.run = run
    remap.fields = tuple(fields)
    return remap


_REMAP_MEMO: dict[tuple, Callable] = {}
# drop memoized remap runners together with the backend compile memo, so a
# benchmark-harness clear_compile_cache() leaves no stale runners behind
register_cache_clear(_REMAP_MEMO.clear)


def vertical_remap(cfg: FV3Config, delp: jax.Array, fields: dict) -> tuple:
    """First-order conservative remap from the deformed Lagrangian levels
    back to reference sigma levels; delp/fields: (nk, nyp, nxp).

    Thin convenience wrapper over :func:`make_vertical_remap` — the remap is
    a compiled stencil program (jnp backend), memoized per (config, field
    set, shape).  Step factories build their own runner once instead.
    """
    names = tuple(fields)
    nyp = delp.shape[1] - 2 * cfg.halo
    nxp = delp.shape[2] - 2 * cfg.halo
    key = (cfg.nk, cfg.halo, nyp, nxp, names)
    fn = _REMAP_MEMO.get(key)
    if fn is None:
        dom = DomainSpec(ni=nxp, nj=nyp, nk=cfg.nk, halo=cfg.halo)
        fn = _REMAP_MEMO[key] = make_vertical_remap(cfg, dom, names)
    return fn(delp, fields, {"ptop": cfg.ptop, "rk": 1.0 / cfg.nk})


# ---------------------------------------------------------------------------
# Step functions
# ---------------------------------------------------------------------------


STATE_FIELDS = ("delp", "pt", "w", "u", "v")


def all_state_fields(cfg: FV3Config) -> list[str]:
    return list(STATE_FIELDS) + list(cfg.tracers)


def _resolve_opt_level(optimize: bool, opt_level: int | None) -> int:
    """``opt_level`` wins when given; the legacy ``optimize`` flag maps to
    the full automatic ladder (True) or the untransformed graph (False)."""
    if opt_level is not None:
        return opt_level
    return 3 if optimize else 0


def _build_programs(cfg: FV3Config, dom: DomainSpec):
    return (build_csw_program(cfg, dom), build_dsw_program(cfg, dom),
            build_tracer_program(cfg, dom),
            build_remap_program(cfg, dom))


@contextlib.contextmanager
def _timed_build(name: str, seconds: dict):
    """A program's build in a step factory (rewrite ladder, tuning, runner
    construction): its host seconds added to ``seconds[name]``."""
    t = time.perf_counter()
    yield
    seconds[name] = seconds.get(name, 0.0) + time.perf_counter() - t


def _build_seconds(t0: float, progs, runners, seconds: dict) -> dict:
    """``step.build_seconds``: ``total`` host seconds from the factory's
    entry (``t0``) to now, ``programs`` each program's build, and
    ``rewrite`` the rewrite ladder's share of it (its ``PassStats``,
    verification included)."""
    rewrite = {}
    for p, r in zip(progs, runners):
        rep = r.opt_report
        rewrite[p.name] = 0.0 if rep is None else rep.input_verify_seconds \
            + sum(s.seconds + s.verify_seconds for s in rep.passes)
    return {"total": time.perf_counter() - t0, "programs": dict(seconds),
            "rewrite": rewrite}


def _k_slabs(progs, runners) -> dict:
    """``step.k_slabs``: per program, the number of kernels that walk K in
    slabs (horizontal stencils) or marched blocks (vertical solvers), and
    per kernel its ``block_k``, ``n_blocks`` and the levels of its last
    block (fewer than ``block_k`` where the block does not divide nk)."""
    out = {}
    for p, r in zip(progs, runners):
        blocks = {}
        for label, bk, nk in r.k_blocks:
            n, last = k_blocks(nk, bk)
            blocks[label] = {"block_k": bk, "n_blocks": n, "last": last}
        out[p.name] = {"kernels": len(blocks), "blocks": blocks}
    return out


def _make_programs(cfg: FV3Config, dom: DomainSpec, backend: str,
                   opt_level: int, hardware=None,
                   n_members: int | None = None, batch: str = "vmap",
                   verify: str | None = None):
    """Build the four stencil programs (acoustic c_sw / d_sw, tracer
    transport, vertical remap) and compile each through the automatic
    optimization ladder (the paper's opt pipeline applies to the whole
    dycore — remap included — with no per-program hand-tuning).
    ``n_members``/``batch`` thread the ensemble axis into every program;
    ``verify`` selects the static-verifier mode (``None`` resolves from
    ``$REPRO_VERIFY`` / the pytest-CI default, see
    :func:`repro.core.analysis.resolve_verify_mode`).  Returns the
    programs, their runners and each program's build seconds."""
    progs = _build_programs(cfg, dom)
    seconds: dict[str, float] = {}
    runners = []
    for p in progs:
        with _timed_build(p.name, seconds):
            runners.append(compile_program(
                p, backend, hardware=hardware, opt_level=opt_level,
                n_members=n_members, batch=batch, verify=verify))
    return progs, tuple(runners), seconds


def _metric_terms(cfg: FV3Config, shape, dtype=jnp.float32) -> dict:
    """cosa/sina: fixed synthetic grid metric terms shared by every
    execution path — built ONCE per step closure so the scan body never
    re-materializes constants (the old per-substep ``ones_like`` rebuild)."""
    return {"cosa": jnp.full(shape, 0.2, dtype),
            "sina": jnp.full(shape, 0.8, dtype)}


def _csw_inputs(src, metrics):
    """c_sw input dict from a state dict + hoisted metric constants."""
    return {"u": src["u"], "v": src["v"], "delp": src["delp"],
            "pt": src["pt"], "w": src["w"],
            "cosa": metrics["cosa"], "sina": metrics["sina"]}


#: The step's layers as named scopes: every program call runs under its
#: program's scope (the program name with non-word characters replaced) and
#: every halo exchange under ``halo_exchange``, so each op of the compiled
#: step carries its layer in its ``op_name`` metadata (and, inside a
#: program, the label of its stencil node: see ``compile_program``).
CSW_SCOPE, DSW_SCOPE, TRACER_SCOPE, REMAP_SCOPE = (
    "c_sw_riem", "d_sw", "tracer_2d", "vertical_remap")
HALO_SCOPE = "halo_exchange"
STEP_SCOPES = (CSW_SCOPE, DSW_SCOPE, TRACER_SCOPE, REMAP_SCOPE, HALO_SCOPE)


def _exchange(halo_fn, st, names):
    with jax.named_scope(HALO_SCOPE):
        return halo_fn(st, names)


def _acoustic_iteration(cfg, runners, params, halo_fn, state, metrics,
                        overlap=None, skip_delpc_exchange=False):
    """One acoustic substep on local (or per-tile) padded arrays.

    Structure matches the paper's blue region (Fig. 2): c_sw-lite +
    riem_solver_c, halo update of the C-grid mass, then d_sw-lite with FVT.

    With ``overlap`` (distributed path), each exchanged program computes its
    full domain from the *pre-exchange* state — no data dependence on the
    ppermute rounds, so XLA launches interior compute concurrently with the
    collectives — and recomputes only the edge strips from the exchanged
    arrays afterwards (:mod:`repro.fv3.overlap`).
    """
    if overlap is not None and overlap[0] is not None and overlap[1] is not None:
        ov_csw, ov_dsw, _ = overlap
        st = dict(state)
        ex = _exchange(halo_fn, st, list(STATE_FIELDS))   # ppermute rounds
        with jax.named_scope(CSW_SCOPE):
            out = ov_csw(_csw_inputs(st, metrics), _csw_inputs(ex, metrics),
                         params)                      # interior ∥ exchange
        st = ex
        st["w"] = out["w"]
        delpc = _exchange(halo_fn, {**st, "delpc": out["delpc"]},
                          ["delpc"])["delpc"]
        dsw_stale = {"u": st["u"], "v": st["v"], "delp": st["delp"],
                     "pt": st["pt"], "delpc": out["delpc"]}
        dsw_fresh = {**dsw_stale, "delpc": delpc}
        with jax.named_scope(DSW_SCOPE):
            out2 = ov_dsw(dsw_stale, dsw_fresh, params)   # interior ∥ exchange
        st["u"], st["v"] = out2["u"], out2["v"]
        st["delp"], st["pt"] = out2["delp_out"], out2["pt_out"]
        return st

    run_csw, run_dsw = runners[0], runners[1]
    st = dict(state)
    st = _exchange(halo_fn, st, list(STATE_FIELDS))
    with jax.named_scope(CSW_SCOPE):
        out = run_csw(_csw_inputs(st, metrics), params)
    st["w"] = out["w"]
    if skip_delpc_exchange:
        # recompute-vs-exchange applied: c_sw computed delpc on a one-cell
        # wider rim from the exchanged inputs, so d_sw's (1,1) read is
        # already satisfied — no per-substep scalar exchange
        delpc = out["delpc"]
    else:
        # d_sw's Smagorinsky reads delpc at extent (1,1) — one scalar
        # exchange
        delpc = _exchange(halo_fn, {**st, "delpc": out["delpc"]},
                          ["delpc"])["delpc"]
    dsw_in = {"u": st["u"], "v": st["v"], "delp": st["delp"],
              "pt": st["pt"], "delpc": delpc}
    with jax.named_scope(DSW_SCOPE):
        out2 = run_dsw(dsw_in, params)
    st["u"], st["v"] = out2["u"], out2["v"]
    st["delp"], st["pt"] = out2["delp_out"], out2["pt_out"]
    return st


REMAP_FIELDS = ("pt", "w", "u", "v")


def _reference_halo_fn(cfg: FV3Config):
    """Sequential-mode halo update over global tile arrays.  The reference
    exchange addresses the tile axis at -4, so the same closure serves
    (6, nk, J, I) single-member state and (M, 6, nk, J, I) ensembles —
    the batched exchange is the per-member one, bit for bit."""
    def halo_fn(st, names):
        vec = [("u", "v")] if ("u" in names and "v" in names) else []
        ex = {k: st[k] for k in names if k not in ("u", "v")}
        if vec:
            ex["u"], ex["v"] = st["u"], st["v"]
        out = exchange_reference(ex, cfg.halo, vector_pairs=vec)
        return {**st, **out}

    return halo_fn


def _counting_tile_runner(run, counters, axis: int = 0):
    """vmap a compiled runner over the tile axis (``axis`` 0 for
    (6, nk, J, I) state, 1 when a member axis leads) and count Python-level
    dispatches for the instrumentation tests."""
    vmapped = jax.vmap(run, in_axes=(axis, None), out_axes=axis)

    def counting(fields, ps):
        counters["runner_dispatches"] += 1
        return vmapped(fields, ps)

    return counting


def _scan_substeps(body, st, n, unroll):
    """Run ``body`` n times over the state dict: ``lax.scan``-rolled by
    default (the body is traced once and compiled once, regardless of n —
    one dispatch per step), or a Python-level unrolled loop for A/B
    comparison and debugging."""
    if unroll:
        for _ in range(n):
            st = body(st)
        return st

    def scan_body(carry, _):
        return body(carry), None

    st, _ = jax.lax.scan(scan_body, st, None, length=n)
    return st


def _remap_iteration(cfg, runners, params, halo_fn, state, metrics,
                     overlap=None, unroll=False, counters=None,
                     skip_delpc_exchange=False):
    run_trc, run_remap = runners[2], runners[3]

    def acoustic_body(st):
        if counters is not None:
            counters["acoustic_traces"] += 1
        return _acoustic_iteration(cfg, runners, params, halo_fn, st,
                                   metrics, overlap=overlap,
                                   skip_delpc_exchange=skip_delpc_exchange)

    st = _scan_substeps(acoustic_body, dict(state), cfg.n_split, unroll)
    if overlap is not None and overlap[2] is not None:
        ex = _exchange(halo_fn, st, ["u", "v", *cfg.tracers])
        stale = {"u": st["u"], "v": st["v"],
                 **{q: st[q] for q in cfg.tracers}}
        fresh = {"u": ex["u"], "v": ex["v"],
                 **{q: ex[q] for q in cfg.tracers}}
        with jax.named_scope(TRACER_SCOPE):
            out = overlap[2](stale, fresh, params)    # interior ∥ exchange
        st = ex
    else:
        st = _exchange(halo_fn, st, ["u", "v", *cfg.tracers])
        trc_in = {"u": st["u"], "v": st["v"]}
        for q in cfg.tracers:
            trc_in[q] = st[q]
        with jax.named_scope(TRACER_SCOPE):
            out = run_trc(trc_in, params)
    for q in cfg.tracers:
        st[q] = out[f"{q}_out"]
    # vertical remap back to reference levels — a compiled stencil program
    # like every other motif (interface fields, pass manager, tuning cache)
    names = (*REMAP_FIELDS, *cfg.tracers)
    with jax.named_scope(REMAP_SCOPE):
        rout = run_remap({"delp": st["delp"],
                          **{q: st[q] for q in names}}, params)
    st["delp"] = rout["delp_out"]
    for q in names:
        st[q] = rout[f"{q}_out"]
    return st


def _assemble_step(cfg: FV3Config, progs, runners, runners_v, halo_fn,
                   metrics, params, counters, *, backend: str, unroll: bool,
                   donate: bool,
                   member_chunks: tuple[int, int] | None = None) -> Callable:
    """Shared tail of the sequential/ensemble step factories: the
    scan-rolled remap loop behind one jit, with counters and the standard
    introspection attributes.  Keeping this in one place is what keeps the
    ensemble and single-member paths bit-identical by construction.

    ``member_chunks=(M, C)`` wraps the WHOLE step in a member chunk loop:
    the runners (compiled C-wide) execute every substep for one C-member
    chunk before the next chunk starts — a ``lax.scan`` over ceil(M/C)
    chunks, so only one chunk's transients/halo working set is ever live.
    With ``donate=True`` the scan carry double-buffers through the same
    storage: the M-member state streams through a C-member footprint."""
    def _inner(state: dict) -> dict:
        def remap_body(st):
            return _remap_iteration(cfg, runners_v, params, halo_fn, st,
                                    metrics, unroll=unroll,
                                    counters=counters)

        return _scan_substeps(remap_body, dict(state), cfg.k_split, unroll)

    if member_chunks:
        n_members, chunk = member_chunks
        _step = scan_chunked(lambda ch, _ps: _inner(ch), n_members, chunk)
    else:
        _step = _inner

    jitted = jit_program(_step, backend, donate=donate)

    @functools.wraps(_step)
    def step(state: dict) -> dict:
        counters["step_calls"] += 1
        with jax.profiler.TraceAnnotation("repro.step"):
            return jitted(state)

    step.counters = counters
    # ahead-of-time: step.lower(state).compile() — compile time apart from
    # run time, and the lowered text shows which kernels the step holds
    step.lower = jitted.lower
    step.opt_report = {p.name: r.opt_report for p, r in zip(progs, runners)}
    step.n_kernels = sum(r.n_kernels for r in runners)
    step.programs = progs
    step.unrolled = unroll
    return step


def make_step_sequential(cfg: FV3Config, *, backend: str = "jnp",
                         hardware=None, optimize: bool = True,
                         opt_level: int | None = None,
                         unroll: bool = False,
                         donate: bool = False) -> Callable:
    """Physics step on global (6, nk, npx+2h, npx+2h) arrays, one device.

    The whole step — ``k_split`` remap iterations, each holding ``n_split``
    acoustic substeps rolled into ``jax.lax.scan``, tracer transport and the
    compiled vertical remap — is ONE jitted callable: a single dispatch per
    step, instead of a Python-level dispatch per substep.  ``unroll=True``
    restores the unrolled Python loop for A/B comparison; both paths are
    bit-equivalent.

    ``donate=True`` donates the input state dict on platforms where XLA
    honors donation (TPU/GPU; see :func:`donation_supported`) — the
    steady-state production loop ``state = step(state)``.  It is opt-in
    (matching ``compile_program``): a donated input's buffers are invalid
    after the call, so callers that keep reading the pre-step state must
    leave it off.

    The returned callable exposes ``opt_report`` (per-program pass-pipeline
    reports covering acoustic + tracer + remap), ``n_kernels`` and
    ``counters`` (trace/dispatch instrumentation used by the
    dispatch-count tests and benchmarks), ``build_seconds`` (host
    seconds of the factory, per program and for the rewrite ladder) and
    ``k_slabs`` (the kernels that walk K in blocks, see :func:`_k_slabs`).
    """
    t0 = time.perf_counter()
    dom = cfg.seq_dom()
    progs, runners, seconds = _make_programs(
        cfg, dom, backend, _resolve_opt_level(optimize, opt_level), hardware)
    params = default_params(cfg)
    counters = {"acoustic_traces": 0, "runner_dispatches": 0,
                "step_calls": 0}
    runners_v = tuple(_counting_tile_runner(r, counters) for r in runners)
    # cosa/sina hoisted out of the scan body: constants are built once per
    # step closure, not re-materialized every acoustic substep
    metrics = _metric_terms(cfg, (6,) + dom.padded_shape())
    step = _assemble_step(cfg, progs, runners, runners_v,
                          _reference_halo_fn(cfg), metrics, params, counters,
                          backend=backend, unroll=unroll, donate=donate)
    step.build_seconds = _build_seconds(t0, progs, runners, seconds)
    step.k_slabs = _k_slabs(progs, runners)
    return step


def make_step_ensemble(cfg: FV3Config, n_members: int, *,
                       backend: str = "jnp", hardware=None,
                       optimize: bool = True, opt_level: int | None = None,
                       batch: str | None = None,
                       unroll: bool = False,
                       donate: bool = False) -> Callable:
    """Ensemble physics step: M perturbed members on one device, state laid
    out ``(M, 6, nk, npx+2h, npx+2h)`` (member outermost).

    This is :func:`make_step_sequential`'s scan-rolled step with the member
    axis threaded through the whole toolchain instead of a Python loop over
    members: every stencil program compiles once via
    ``compile_program(..., n_members=M, batch=...)`` (jnp lowers the axis
    with ``jax.vmap``; the Pallas backends place members on the outermost
    sequential grid axis — same kernel count as M=1), and the halo exchange
    runs *batched* — the reference gathers carry the member axis like the
    distributed ppermute rounds carry arbitrary leading dims.  The result
    is bit-identical to M independent sequential steps at every opt level;
    what changes is dispatch structure: one jitted step, one kernel per
    fused group, launch overhead amortized across members.

    ``batch`` defaults per backend ("vmap" for jnp, "grid" for Pallas) and
    accepts the full chunk-spec grammar of :func:`compile_program`.  A
    chunked scan-outer spec (``"vmap:C"``) lifts the chunk loop to the
    *step* level: runners compile C-wide and the whole step — halo
    exchanges, acoustic scan, remap — runs chunk by chunk under one
    ``lax.scan``, so only one C-member working set is live at a time.
    With ``donate=True`` (on donation-capable platforms) the M-member
    state streams through that C-member footprint in place — the
    large-ensemble memory-scaling path.  ``"vmap:C,grid"`` instead keeps
    the step M-wide and pushes the chunk loop into each Pallas kernel's
    outermost grid axis.
    """
    t0 = time.perf_counter()
    if batch is None:
        batch = "grid" if str(backend).startswith("pallas") else "vmap"
    spec = parse_batch(batch)
    member_chunks = None
    prog_members, prog_batch = n_members, spec
    if spec.chunk > 0:  # explicit chunk width (AUTO resolves per program)
        C = spec.chunk_for(n_members)
        grid_loop = (spec.loop == "grid"
                     and str(backend).startswith("pallas"))
        if C < n_members and not grid_loop:
            # step-level chunk loop: compile everything C-wide, scan chunks
            member_chunks = (n_members, C)
            prog_members, prog_batch = C, BatchSpec(mode=spec.mode)
    dom = cfg.seq_dom()
    progs, runners, seconds = _make_programs(
        cfg, dom, backend, _resolve_opt_level(optimize, opt_level), hardware,
        n_members=prog_members, batch=prog_batch)
    params = default_params(cfg)
    counters = {"acoustic_traces": 0, "runner_dispatches": 0,
                "step_calls": 0}
    # member-batched runners take (C|M, nk, J, I): tiles vmap over axis 1
    runners_v = tuple(_counting_tile_runner(r, counters, axis=1)
                      for r in runners)
    base_metrics = _metric_terms(cfg, (6,) + dom.padded_shape())
    metrics = {k: jnp.broadcast_to(v, (prog_members,) + v.shape)
               for k, v in base_metrics.items()}
    step = _assemble_step(cfg, progs, runners, runners_v,
                          _reference_halo_fn(cfg), metrics, params, counters,
                          backend=backend, unroll=unroll, donate=donate,
                          member_chunks=member_chunks)
    step.n_members = n_members
    step.batch = spec.token
    step.member_chunk = member_chunks[1] if member_chunks else \
        (runners[0].member_chunk if n_members else None)
    step.n_chunks = (-(-n_members // member_chunks[1])
                     if member_chunks else runners[0].n_chunks)
    step.build_seconds = _build_seconds(t0, progs, runners, seconds)
    step.k_slabs = _k_slabs(progs, runners)
    return step


def make_step_distributed(cfg: FV3Config, mesh, *, backend: str = "jnp",
                          hardware=None, optimize: bool = True,
                          opt_level: int | None = None,
                          ensemble: bool = False,
                          member_axis: str | None = None,
                          n_members: int | None = None,
                          batch: str | None = None,
                          overlap: bool = True,
                          unroll: bool = False) -> Callable:
    """shard_map'd physics step over mesh ("tile","y","x") — or, multi-pod,
    (member, "tile","y","x") with independent ensemble members (the NWP
    production multi-pod workload).

    ``member_axis`` names an extra *leading* mesh axis members shard over,
    orthogonally to the ``tile/y/x`` domain decomposition — each member
    group runs an independent dycore; no collective ever crosses the member
    axis (the halo ppermutes name only ``tile/y/x``).  The legacy
    ``ensemble=True`` flag (deprecated shorthand for ``member_axis="ens"``;
    emits a :class:`DeprecationWarning`) will be removed next release.

    At ``opt_level >= 4`` the non-overlap path additionally applies the
    recompute-vs-exchange rewrite
    (:class:`repro.core.rewrite.RecomputeVsExchange`): when the cost model
    prefers it, ``c_sw`` computes ``delpc`` on a one-cell-wider rim from
    the already-exchanged inputs and the per-substep ``delpc`` halo
    exchange is dropped — bit-identical (the rim equals the neighbor's
    interior values), ``n_split * k_split`` fewer exchange rounds per step
    (``step.delpc_exchange_skipped`` reports whether it applied).

    Without ``n_members`` the mesh's member extent must equal the ensemble
    size (one member per member-group).  ``n_members=M`` composes the
    sharded and batched ensemble lowerings: M must be a multiple of the
    member-axis extent D, each group owns ``ml = M // D`` members, and the
    per-group dycore compiles member-batched over ``ml`` with ``batch``
    (full chunk-spec grammar — e.g. ``"vmap:4,grid"`` chunk-batches within
    each shard).  A 64-member ensemble on a 4-group mesh thus runs 16
    members per group, chunked 4 at a time inside each kernel.

    Input state: per-rank local blocks laid out
    ([member…,] tile, y, x, nk, nl+2h, nl+2h) — the member axis sharded
    over ``member_axis``, ``ml`` members contiguous per shard.

    ``overlap=True`` hides halo-exchange latency by splitting each exchanged
    program's domain (:mod:`repro.fv3.overlap`): interior compute runs from
    the pre-exchange state concurrently with the ppermute rounds, edge
    strips are recomputed afterwards.  It degrades automatically to the
    sequential exchange-then-compute ordering when the local interior is
    too small (``n_local <= 2*halo``) to hold a strip-free core, and is
    skipped when groups hold more than one member (the overlap splitter is
    single-member; the member batch already fills the schedule).
    """
    from jax.sharding import PartitionSpec as P

    t0 = time.perf_counter()
    if ensemble:
        warnings.warn(
            "make_step_distributed(ensemble=True) is deprecated; pass "
            "member_axis='ens' (or your mesh's member axis name) instead",
            DeprecationWarning, stacklevel=2)
        if member_axis is None:
            member_axis = "ens"
    ml = 1
    if n_members is not None:
        if member_axis is None:
            raise ValueError("n_members requires member_axis (an ensemble "
                             "mesh axis to shard members over)")
        d = mesh.shape[member_axis]
        if n_members % d:
            raise ValueError(
                f"n_members={n_members} must be a multiple of the "
                f"member-axis extent {d}")
        ml = n_members // d
    if batch is None:
        batch = "grid" if str(backend).startswith("pallas") else "vmap"

    dom = cfg.local_dom()
    dec = cfg.decomposition()
    lvl = _resolve_opt_level(optimize, opt_level)
    progs = _build_programs(cfg, dom)
    params = default_params(cfg)
    exchanger = make_halo_exchanger(dec)
    py, px = cfg.layout
    nl, h, nk = cfg.n_local, cfg.halo, cfg.nk

    memb = {"n_members": ml, "batch": batch} if ml > 1 else {}
    seconds: dict[str, float] = {}
    # the remap program is purely vertical (no horizontal reads), so it
    # never participates in halo/compute overlap — compile it plain
    with _timed_build(progs[3].name, seconds):
        run_remap = compile_program(progs[3], backend, hardware=hardware,
                                    opt_level=lvl, **memb)
    ov = None
    if overlap and ml == 1:
        cands = []
        for p in progs[:3]:
            with _timed_build(p.name, seconds):
                cands.append(make_overlapped_runner(
                    p, backend=backend, hardware=hardware, opt_level=lvl))
        if all(c is not None for c in cands):
            ov = tuple(cands)
    skip_delpc = False
    if ov is None and lvl >= 4:
        # recompute-vs-exchange: widen c_sw so delpc is valid on a one-cell
        # rim (d_sw's widest read) — drops the per-substep delpc exchange
        # when the cost model prefers redundant rim compute over the
        # ppermute rounds.  The rim equals the neighbor's interior bit for
        # bit: c_sw runs on the already-exchanged inputs (halo-h ghosts)
        # and its reads from the widened window stay within h.
        from repro.core.backend import get_backend
        from repro.core.rewrite import (
            ExchangeModel, PassContext, widen_for_exchange,
        )
        itemsize = np.dtype(cfg.dtype).itemsize
        model = ExchangeModel(
            n_rounds=len(exchanger.rounds),
            ring_bytes=4 * nl * h * nk * itemsize)
        ctx = PassContext(
            backend=backend,
            hardware=get_backend(backend).resolve_hw(hardware))
        skip_delpc = widen_for_exchange(
            progs[0], {"delpc": (1, 1)}, model, ctx) > 0
    if ov is not None:
        # the overlapped runners embed the opt-ladder-compiled full-domain
        # program — reuse it rather than running the optimizer again for
        # fallback runners the overlap branch never calls
        runners = tuple(c.full_run for c in ov) + (run_remap,)
    else:
        built = []
        for p in progs[:3]:
            with _timed_build(p.name, seconds):
                built.append(compile_program(p, backend, hardware=hardware,
                                             opt_level=lvl, **memb))
        runners = tuple(built) + (run_remap,)

    def halo_fn(st, names):
        vec = [("u", "v")] if ("u" in names and "v" in names) else []
        ex = {k: st[k] for k in names}
        out = exchanger(ex, vector_pairs=vec)
        return {**st, **out}

    lead = 4 if member_axis else 3
    base_metrics = _metric_terms(cfg, dom.padded_shape())
    metrics = ({k: jnp.broadcast_to(v, (ml,) + v.shape)
                for k, v in base_metrics.items()} if ml > 1 else base_metrics)
    local_shape = ((ml, nk, nl + 2 * h, nl + 2 * h) if ml > 1
                   else (nk, nl + 2 * h, nl + 2 * h))

    def local_step(state: dict) -> dict:
        st = {k: v.reshape(local_shape) for k, v in state.items()}

        def remap_body(s):
            return _remap_iteration(cfg, runners, params, halo_fn, s,
                                    metrics, overlap=ov, unroll=unroll,
                                    skip_delpc_exchange=skip_delpc)

        st = _scan_substeps(remap_body, st, cfg.k_split, unroll)
        return {k: v.reshape((ml,) + (1,) * (lead - 1)
                             + (nk, nl + 2 * h, nl + 2 * h))
                for k, v in st.items()}

    spec = (P(member_axis, "tile", "y", "x") if member_axis
            else P("tile", "y", "x"))
    fields = all_state_fields(cfg)
    sharded = jax.shard_map(
        local_step, mesh=mesh,
        in_specs=(dict.fromkeys(fields, spec),),
        out_specs=dict.fromkeys(fields, spec),
    )
    jitted = jit_program(sharded, backend)

    def step(state: dict) -> dict:
        with jax.profiler.TraceAnnotation("repro.step"):
            return jitted(state)

    step.n_members = n_members
    step.members_per_group = ml
    step.batch = batch if ml > 1 else None
    step.member_chunk = runners[0].member_chunk if ml > 1 else None
    step.overlapped = ov is not None
    step.delpc_exchange_skipped = skip_delpc
    step.build_seconds = _build_seconds(t0, progs, runners, seconds)
    step.k_slabs = _k_slabs(progs, runners)
    return step
