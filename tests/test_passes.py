"""Pass-manager tests: registry/ladders, per-pass stats, the acceptance
criteria for the automatic optimization pipeline (fewer kernels, transients
out of HBM, lower modeled traffic), and property-based jnp-vs-fused-pallas
equivalence over random fusable chains."""

import numpy as np
import pytest
import jax.numpy as jnp
from _hypothesis_compat import given, settings, strategies as st

from repro.core import (
    OPT_LADDERS,
    StencilProgram,
    available_passes,
    compile_program,
    get_pass,
    optimize_program,
)
from repro.core.stencil import DomainSpec
from repro.core.stencil.ir import (
    Assign, BinOp, Computation, Const, Direction, FieldAccess, Interval,
    Stencil,
)
from repro.fv3.dyncore import (
    FV3Config, build_csw_program, build_dsw_program, default_params,
)


# ---------------------------------------------------------------------------
# registry and ladders
# ---------------------------------------------------------------------------


def test_builtin_passes_registered():
    assert {"prune_transients", "strength_reduce", "greedy_fuse",
            "tune_schedules"} <= set(available_passes())
    with pytest.raises(KeyError, match="greedy_fuse"):
        get_pass("no-such-pass")


def test_ladders_are_cumulative():
    # every level contains the previous level's passes as an ordered
    # subsequence (level 4 inserts its pattern rewrites before
    # tune_schedules, so containment is subsequence, not prefix)
    for lvl in range(1, max(OPT_LADDERS) + 1):
        prev, cur = OPT_LADDERS[lvl - 1], iter(OPT_LADDERS[lvl])
        assert all(name in cur for name in prev)
        assert len(OPT_LADDERS[lvl]) > len(prev)


def test_optimize_program_reports_stats_and_preserves_input():
    cfg = FV3Config(npx=8, nk=4, halo=6)
    p = build_csw_program(cfg, cfg.seq_dom())
    n_before = len(p.all_nodes())
    opt, report = optimize_program(p, opt_level=3, backend="jnp", cache=None)
    # the caller's graph is untouched; the clone got rewritten
    assert len(p.all_nodes()) == n_before
    assert len(opt.all_nodes()) < n_before
    assert [s.name for s in report.passes] == list(OPT_LADDERS[3])
    assert all(s.seconds >= 0 for s in report.passes)
    assert report.total_rewrites > 0
    assert report.kernels_after < report.kernels_before
    assert "kernels" in report.summary()
    d = report.as_dict()
    assert d["opt_level"] == 3 and len(d["passes"]) == len(report.passes)


def test_tune_schedules_assigns_schedules():
    cfg = FV3Config(npx=8, nk=4, halo=6)
    p = build_csw_program(cfg, cfg.seq_dom())
    opt, _ = optimize_program(p, opt_level=3, backend="pallas-tpu",
                              cache=None)
    assert all(n.schedule is not None for n in opt.all_nodes())
    # at level 2 fused nodes carry the feasibility-checked heuristic (the
    # schedule they will lower with); tuning proper happens at level 3 only
    opt2, _ = optimize_program(p, opt_level=2, backend="pallas-tpu")
    fused = [n for n in opt2.all_nodes()
             if "&" in n.label or "+" in n.label]
    assert fused and all(n.schedule is not None for n in fused)


def test_opt2_leaves_unfused_nodes_untuned():
    cfg = FV3Config(npx=8, nk=4, halo=6)
    dom = cfg.seq_dom()
    p = StencilProgram("single", dom)
    p.declare("q")
    p.declare("out")
    from repro.fv3 import stencils as S
    p.add(S.kinetic_energy, {"u": "q", "v": "q", "ke": "out"})
    p.propagate_extents()
    opt2, _ = optimize_program(p, opt_level=2, backend="pallas-tpu")
    assert all(n.schedule is None for n in opt2.all_nodes())
    opt3, _ = optimize_program(p, opt_level=3, backend="pallas-tpu")
    assert all(n.schedule is not None for n in opt3.all_nodes())


# ---------------------------------------------------------------------------
# acceptance: the C-grid program through the full ladder
# ---------------------------------------------------------------------------


def _csw_setup():
    cfg = FV3Config(npx=8, nk=4, halo=6, n_split=1, k_split=1)
    dom = cfg.seq_dom()
    p = build_csw_program(cfg, dom)
    rng = np.random.default_rng(2)
    fields = {f: jnp.asarray(rng.uniform(0.8, 1.2, dom.padded_shape()),
                             jnp.float32)
              for f in ("u", "v", "delp", "pt", "w", "cosa", "sina")}
    return cfg, dom, p, fields, default_params(cfg)


def test_csw_opt3_fewer_kernels_no_transients_less_traffic():
    _, _, p, fields, params = _csw_setup()
    f0 = compile_program(p, "jnp")
    f3 = compile_program(p, "jnp", opt_level=3)
    # strictly fewer kernels at the top of the ladder
    assert f3.n_kernels < f0.n_kernels
    # the fused path auto-allocates no transient HBM arrays
    assert f0.transient_inputs and f3.transient_inputs == ()
    # and the cost model prices strictly less HBM traffic
    assert f3.opt_report.hbm_bytes_after < f3.opt_report.hbm_bytes_before


def test_fv3_acoustic_roundtrip_opt0_vs_opt3_both_backends():
    cfg, dom, p, fields, params = _csw_setup()
    h, N = cfg.halo, cfg.npx
    I = np.s_[:, h:h + N, h:h + N]
    ref = compile_program(p, "jnp")(dict(fields), params)
    for backend in ("jnp", "pallas-tpu"):
        got = compile_program(p, backend,
                              opt_level=3)(dict(fields), params)
        for k in ("w", "delpc", "ptc"):
            np.testing.assert_allclose(
                np.asarray(ref[k])[I], np.asarray(got[k])[I],
                rtol=1e-6, atol=1e-6, err_msg=f"{backend}/{k}")


def test_dsw_opt3_matches_opt0_interior():
    cfg = FV3Config(npx=12, nk=4, halo=6)
    dom = cfg.seq_dom()
    p = build_dsw_program(cfg, dom)
    params = default_params(cfg)
    h, N = cfg.halo, cfg.npx
    I = np.s_[:, h:h + N, h:h + N]
    rng = np.random.default_rng(3)
    fields = {f: jnp.asarray(rng.uniform(0.8, 1.2, dom.padded_shape()),
                             jnp.float32)
              for f in ("u", "v", "delp", "pt", "delpc")}
    f0 = compile_program(p, "jnp")
    f3 = compile_program(p, "jnp", opt_level=3)
    assert f3.n_kernels < f0.n_kernels
    ref = f0(dict(fields), params)
    got = f3(dict(fields), params)
    for k in ("u", "v", "delp_out", "pt_out"):
        np.testing.assert_allclose(np.asarray(ref[k])[I],
                                   np.asarray(got[k])[I],
                                   rtol=1e-6, atol=1e-6, err_msg=k)


# ---------------------------------------------------------------------------
# property-based: random fusable chains, bit-level jnp vs fused pallas
# ---------------------------------------------------------------------------


@st.composite
def chain_spec(draw):
    n = draw(st.integers(min_value=2, max_value=4))
    offsets = [draw(st.tuples(st.integers(-1, 1), st.integers(-1, 1)))
               for _ in range(n)]
    coefs = [draw(st.floats(min_value=0.25, max_value=2.0))
             for _ in range(n)]
    return offsets, coefs


def _build_chain(offsets, coefs, dom):
    n = len(offsets)

    def mk(i, src, dst):
        di, dj = offsets[i]
        expr = BinOp("*", Const(coefs[i]),
                     BinOp("+", FieldAccess(src, (di, dj, 0)),
                           FieldAccess(src, (0, 0, 0))))
        return Stencil(name=f"s{i}", computations=(
            Computation(Direction.PARALLEL,
                        (Assign(dst, expr, Interval()),)),),
            fields=(src, dst), outputs=(dst,))

    p = StencilProgram("chain", dom)
    p.declare("f0")
    for i in range(n):
        p.declare(f"f{i + 1}", transient=(i + 1 < n))
    for i in range(n):
        p.add(mk(i, f"f{i}", f"f{i + 1}"),
              {f"f{i}": f"f{i}", f"f{i + 1}": f"f{i + 1}"})
    p.propagate_extents()
    return p


@settings(max_examples=10, deadline=None)
@given(chain_spec())
def test_fused_chain_jnp_vs_pallas_bitwise(spec):
    """The optimized program must produce bit-identical results on the jnp
    oracle and the fused-pallas lowering (same IR, same op order), and stay
    allclose to the unoptimized program."""
    offsets, coefs = spec
    n = len(offsets)
    dom = DomainSpec(ni=6, nj=6, nk=2, halo=4)
    p = _build_chain(offsets, coefs, dom)
    rng = np.random.default_rng(7)
    fields = {f"f{i}": jnp.asarray(
        rng.uniform(0.5, 1.5, dom.padded_shape()), jnp.float32)
        for i in range(n + 1)}
    h = dom.halo
    sl = np.s_[:, h:h + dom.nj, h:h + dom.ni]
    out = f"f{n}"

    base = np.asarray(compile_program(p, "jnp")(dict(fields))[out])[sl]
    j3 = compile_program(p, "jnp", opt_level=3)
    p3 = compile_program(p, "pallas-tpu", opt_level=3)
    got_j = np.asarray(j3(dict(fields))[out])[sl]
    got_p = np.asarray(p3(dict(fields))[out])[sl]
    assert p3.n_kernels <= j3.n_kernels <= len(offsets)
    # bit-level equivalence between the two lowerings of the fused program
    np.testing.assert_array_equal(got_j, got_p)
    # and semantic equivalence with the unfused original
    np.testing.assert_allclose(base, got_j, rtol=1e-5, atol=1e-6)
