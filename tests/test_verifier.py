"""Static-verifier tests: seeded IR mutations each rejected with a
diagnostic naming the offending stencil/statement, the unmutated dycore
clean under ``verify="full"`` at every opt level on both backends, per-pass
violation attribution, source-location capture, and the typed
``AnalysisError`` hierarchy."""

import dataclasses

import pytest
import jax.numpy as jnp

from repro.core import (
    AnalysisError,
    FusionLegalityError,
    StencilProgram,
    VerificationError,
    check_lints,
    compile_program,
    optimize_program,
    register_pass,
    verify_program,
)
from repro.core.analysis import resolve_verify_mode
from repro.core.stencil import DomainSpec, Field, Schedule, gtstencil
from repro.core.stencil.ir import (
    Assign, Computation, Const, Direction, FieldAccess, FoundLevel, Interval,
    LevelSearch, Stencil,
)
from repro.fv3.dyncore import FV3Config, _build_programs


# ---------------------------------------------------------------------------
# a small clean program to mutate
# ---------------------------------------------------------------------------


@gtstencil
def lap(q: Field, lp: Field):
    with computation(PARALLEL), interval(...):
        lp = q[1, 0, 0] + q[-1, 0, 0] + q[0, 1, 0] + q[0, -1, 0] - 4.0 * q


@gtstencil
def diff(lp: Field, out: Field):
    with computation(PARALLEL), interval(...):
        out = lp[1, 0, 0] - 2.0 * lp + lp[-1, 0, 0]


@gtstencil
def cumsum(a: Field, x: Field):
    with computation(FORWARD):
        with interval(0, 1):
            x = a
        with interval(1, None):
            x = a + 0.5 * x[0, 0, -1]


def clean_program(nk: int = 4) -> StencilProgram:
    dom = DomainSpec(ni=8, nj=8, nk=nk, halo=3)
    p = StencilProgram("toy", dom)
    p.declare("q")
    p.declare("lp", transient=True)
    p.declare("out")
    p.add(lap, {"q": "q", "lp": "lp"})
    p.add(diff, {"lp": "lp", "out": "out"})
    p.propagate_extents()
    return p


def solver_program(nk: int = 8) -> StencilProgram:
    dom = DomainSpec(ni=8, nj=8, nk=nk, halo=3)
    p = StencilProgram("march", dom)
    p.declare("a")
    p.declare("x")
    node = p.add(cumsum, {"a": "a", "x": "x"})
    node.schedule = Schedule(block_k=nk // 2, k_as_grid=False,
                             carry_storage="vmem")
    p.propagate_extents()
    return p


def _replace_stmt(node, ci, si, **changes):
    st = node.stencil
    comps = list(st.computations)
    stmts = list(comps[ci].statements)
    stmts[si] = dataclasses.replace(stmts[si], **changes)
    comps[ci] = Computation(comps[ci].direction, tuple(stmts))
    node.stencil = dataclasses.replace(st, computations=tuple(comps))


def _analyses(violations):
    return {v.analysis for v in violations}


def test_clean_program_verifies():
    assert verify_program(clean_program()) == []
    assert verify_program(solver_program()) == []


# ---------------------------------------------------------------------------
# the mutation suite — every seeded defect is rejected with a diagnostic
# naming the stencil (and statement, where one exists)
# ---------------------------------------------------------------------------


def test_mutation_dropped_extent_is_stale_halo():
    # the "dropped exchange" class: the producer's recompute window is
    # narrowed below what the downstream offset reads require
    p = clean_program()
    producer = p.all_nodes()[0]
    assert producer.extend == (1, 0)  # diff reads lp at i±1 only
    producer.extend = (0, 0)
    vs = verify_program(p)
    assert "halo" in _analyses(vs)
    v = next(v for v in vs if v.analysis == "halo")
    assert v.field == "lp" and "stale-halo" in v.message
    assert v.stencil == "lap"


def test_mutation_offset_widened_past_halo():
    p = clean_program()
    reader = p.all_nodes()[1]
    wide = FieldAccess("lp", (p.dom.halo + 1, 0, 0))
    _replace_stmt(reader, 0, 0, value=wide)
    vs = verify_program(p)
    assert "halo" in _analyses(vs)
    assert any("halo" in v.message for v in vs)


def test_mutation_fused_write_then_offset_read_races():
    # the can_otf_fuse class: producer/consumer statements reordered into
    # one kernel so the consumer reads the producer's output at an offset
    # inside the same parallel sweep
    dom = DomainSpec(ni=8, nj=8, nk=4, halo=3)
    p = StencilProgram("racy", dom)
    p.declare("q")
    p.declare("f", transient=True)
    p.declare("g")
    st = Stencil(
        name="fused",
        computations=(Computation(Direction.PARALLEL, (
            Assign("f", FieldAccess("q", (0, 0, 0)), Interval(), None),
            Assign("g", FieldAccess("f", (1, 0, 0)), Interval(), None),
        )),),
        fields=("q", "f", "g"), outputs=("f", "g"))
    p.add(st, {n: n for n in st.fields})
    p.propagate_extents()
    vs = verify_program(p)
    assert "race" in _analyses(vs)
    v = next(v for v in vs if v.analysis == "race")
    assert v.field == "f" and v.offset == (1, 0, 0)
    assert v.statement is not None  # names the offending Assign


def test_mutation_marching_carry_horizontal_offset():
    # the solver_k_blockable class: a K-blocked marching schedule whose
    # carry read gains a horizontal offset would bleed across block (and
    # chunked-ensemble member) boundaries
    p = solver_program()
    node = p.all_nodes()[0]
    carried = FieldAccess("x", (1, 0, -1))
    val = node.stencil.computations[0].statements[1].value
    new = val.substitute("x", lambda off: carried)
    _replace_stmt(node, 0, 1, value=new)
    vs = verify_program(p)
    assert "race" in _analyses(vs)
    assert any("carry" in v.message and v.field == "x" for v in vs
               if v.analysis == "race")


def test_mutation_marching_deep_k_read():
    p = solver_program()
    node = p.all_nodes()[0]
    deep = FieldAccess("a", (0, 0, -2))
    _replace_stmt(node, 0, 1, value=deep)
    vs = verify_program(p)
    assert "race" in _analyses(vs)
    assert any("marching-previous" in v.message for v in vs
               if v.analysis == "race")


def test_mutation_read_of_undeclared_name():
    p = clean_program()
    _replace_stmt(p.all_nodes()[1], 0, 0,
                  value=FieldAccess("ghost", (0, 0, 0)))
    vs = verify_program(p)
    assert any(v.analysis == "wellformed" and v.field == "ghost"
               and "undeclared" in v.message for v in vs)


def test_mutation_temp_read_before_write():
    dom = DomainSpec(ni=8, nj=8, nk=4, halo=3)
    p = StencilProgram("t", dom)
    p.declare("q")
    p.declare("out")
    st = Stencil(
        name="scratch",
        computations=(Computation(Direction.PARALLEL, (
            Assign("out", FieldAccess("tmp", (0, 0, 0)), Interval(), None),
            Assign("tmp", FieldAccess("q", (0, 0, 0)), Interval(), None),
        )),),
        fields=("q", "out"), outputs=("out",))
    p.add(st, {"q": "q", "out": "out"})
    p.propagate_extents()
    vs = verify_program(p)
    assert any(v.analysis == "wellformed"
               and "read before any statement writes" in v.message
               for v in vs)


def test_mutation_flipped_interface_staggering():
    p = clean_program()
    p.fields["q"] = dataclasses.replace(p.fields["q"], interface=True)
    vs = verify_program(p)
    assert any(v.analysis == "wellformed" and v.field == "q"
               and "K-staggering" in v.message for v in vs)


def test_mutation_k_offset_outside_column():
    p = clean_program()
    _replace_stmt(p.all_nodes()[1], 0, 0,
                  value=FieldAccess("lp", (0, 0, -1)))
    vs = verify_program(p)
    assert any(v.analysis == "wellformed" and "edge-clamp" in v.message
               and v.offset == (0, 0, -1) for v in vs)


def test_mutation_nested_level_search():
    p = clean_program()
    inner = LevelSearch("q", Const(1.0), FoundLevel("q"), (0, 0), (1, 0))
    outer = LevelSearch("lp", Const(1.0), inner, (0, 0), (1, 0))
    _replace_stmt(p.all_nodes()[1], 0, 0, value=outer)
    vs = verify_program(p)
    assert any(v.analysis == "wellformed" and "nested index_search"
               in v.message for v in vs)


def test_mutation_found_level_outside_search():
    p = clean_program()
    _replace_stmt(p.all_nodes()[1], 0, 0, value=FoundLevel("lp"))
    vs = verify_program(p)
    assert any(v.analysis == "wellformed"
               and "outside an index_search" in v.message for v in vs)


def test_mutation_at_found_past_column_end():
    p = clean_program()
    body = FoundLevel("lp", dk=+1)
    search = LevelSearch("lp", Const(1.0), body, (0, 0), (1, 0))
    _replace_stmt(p.all_nodes()[1], 0, 0, value=search)
    vs = verify_program(p)
    assert any(v.analysis == "wellformed" and "at_found" in v.message
               and "outside its" in v.message for v in vs)


def test_shadowed_declare_is_linted():
    p = clean_program()
    p.declare("q")
    assert any("shadowed declare" in v.message and v.field == "q"
               for v in check_lints(p))


# ---------------------------------------------------------------------------
# verify= wiring: pass attribution, modes, full dycore clean
# ---------------------------------------------------------------------------


@register_pass("_test_break_extent")
def _break_extent(program, ctx):
    program.all_nodes()[0].extend = (0, 0)
    return 1


def test_violation_attributed_to_responsible_pass():
    p = clean_program()
    with pytest.raises(VerificationError) as ei:
        optimize_program(p, passes=("_test_break_extent",), verify="passes")
    err = ei.value
    assert err.pass_name == "_test_break_extent"
    assert err.violations and all(v.pass_name == "_test_break_extent"
                                  for v in err.violations)
    assert "_test_break_extent" in str(err)


def test_broken_input_attributed_to_no_pass():
    p = clean_program()
    p.all_nodes()[0].extend = (0, 0)
    with pytest.raises(VerificationError) as ei:
        optimize_program(p, opt_level=1, verify="passes")
    assert ei.value.pass_name is None


def test_verify_report_records_mode_and_timing():
    p = clean_program()
    opt, rep = optimize_program(p, opt_level=3, verify="passes")
    assert rep.verify_mode == "passes"
    assert rep.input_verify_seconds > 0
    assert all(ps.verify_violations == 0 for ps in rep.passes)
    assert rep.total_verify_seconds > 0
    assert "verif" in rep.summary()


def test_resolve_verify_mode(monkeypatch):
    assert resolve_verify_mode("full") == "full"
    monkeypatch.setenv("REPRO_VERIFY", "off")
    assert resolve_verify_mode(None) == "off"
    monkeypatch.delenv("REPRO_VERIFY")
    # under pytest the default is "passes"
    assert resolve_verify_mode(None) == "passes"
    with pytest.raises(ValueError):
        resolve_verify_mode("loud")


@pytest.mark.parametrize("backend", ["jnp", "pallas-tpu"])
@pytest.mark.parametrize("opt_level", [0, 1, 2, 3])
def test_dycore_clean_under_full_verification(backend, opt_level):
    cfg = FV3Config(npx=8, nk=4, halo=6)
    dom = cfg.seq_dom()
    for p in _build_programs(cfg, dom):
        fn = compile_program(p, backend,
                             opt_level=opt_level, verify="full")
        assert fn.verify_mode == "full"


# ---------------------------------------------------------------------------
# source locations + typed errors
# ---------------------------------------------------------------------------


def test_frontend_captures_source_locations():
    stmt = lap.computations[0].statements[0]
    assert stmt.loc is not None
    assert stmt.loc.file.endswith("test_verifier.py")
    assert stmt.loc.line > 0
    # loc is diagnostic metadata: excluded from equality and repr so
    # stencil fingerprints (tuning cache keys) stay stable
    assert "loc" not in repr(stmt)
    assert stmt == dataclasses.replace(stmt, loc=None)


def test_violation_diagnostics_carry_loc():
    p = clean_program()
    p.all_nodes()[0].extend = (0, 0)
    [v] = [v for v in verify_program(p) if v.analysis == "halo"]
    text = v.format()
    assert "lap" in text and "stale-halo" in text
    d = v.as_dict()
    assert d["analysis"] == "halo" and d["field"] == "lp"


def test_fusion_legality_error_is_typed():
    ls = LevelSearch("pe", Const(1.0), FoundLevel("fm"), (0, 0), (1, 0))
    with pytest.raises(FusionLegalityError) as ei:
        ls.substitute("pe", lambda off: Const(0.0))
    err = ei.value
    assert isinstance(err, AnalysisError)
    assert isinstance(err, ValueError)  # legacy guard compatibility
    err.with_context(stencil="remap")
    assert err.stencil == "remap"
    assert "remap" in str(err)


def test_verify_full_compiles_and_runs():
    p = clean_program()
    fn = compile_program(p, "jnp", verify="full")
    fields = {"q": jnp.ones(p.dom.padded_shape(), jnp.float32),
              "out": jnp.zeros(p.dom.padded_shape(), jnp.float32)}
    out = fn(fields, {})
    assert out["out"].shape == p.dom.padded_shape()
