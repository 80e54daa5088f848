"""Sequential-K compilation: the ``index_search`` construct + K-blocked
vertical solver schedules.

Covers the production-scale vertical-column work:
 * ``index_search``/``at_found`` frontend parsing and IR analysis (whole-K
   gating, nk-independent IR size, rename through program namespace);
 * lowering correctness at production depth — nk=80 remap vs the
   ``jnp.interp``/``np.searchsorted`` oracle, jnp↔pallas bit-equivalence,
   opt levels 0–3 on both backends;
 * O(nk) IR growth of the remap program vs the O(nk²) unrolled baseline;
 * K-blocked marching schedules: legality (``solver_k_blockable``),
   enumeration/feasibility at depths where whole-column blocks exceed VMEM,
   kernel correctness FORWARD and BACKWARD, fusion interplay;
 * tuning-cache invalidation across the COST_MODEL_VERSION bump.
"""

import numpy as np
import pytest
import jax.numpy as jnp

from repro.core import compile_program, model_cost, tune_stencil
from repro.core.backend import compile_stencil
from repro.core.backend.cache import (COST_MODEL_VERSION, TuningCache,
                                      make_key)
from repro.core.stencil import (
    DomainSpec,
    Field,
    Param,
    Schedule,
    feasible_schedules,
    gtstencil,
    interface,
    solver_k_blockable,
)
from repro.core.stencil.ir import FoundLevel, LevelSearch
from repro.core.transforms import can_otf_fuse, can_subgraph_fuse
from repro.core.hardware import Hardware, resolve_hardware
from repro.fv3 import stencils as S
from repro.fv3.dyncore import FV3Config, build_remap_program, default_params


# ---------------------------------------------------------------------------
# frontend + IR analysis
# ---------------------------------------------------------------------------


def test_index_search_parses_and_analyzes():
    st = S.interface_interp
    assert st.name == "remap_interp"
    assert st.fields == ("fm", "pe", "pe_ref", "fi")
    assert st.has_level_search()
    assert st.count_level_searches() == 1
    # the search forces whole-column blocks but reports no K offsets (its
    # synthetic accesses are zero-K; the schedule gate is has_level_search)
    assert not st.has_k_offsets()
    # read set covers the coordinate and every at_found field
    assert set(st.read_fields()) == {"fm", "pe", "pe_ref"}


def test_index_search_ir_size_is_nk_independent():
    assert S.interface_interp.ir_size() < 25
    # the unrolled variant pays O(nk^2)
    assert S.interface_interp_stencil(8).ir_size() > 8 * 8
    assert S.interface_interp_stencil(16).ir_size() > 16 * 16


def test_remap_program_ir_grows_linearly():
    """Acceptance: nk=80 remap ≤ 25·nk IR nodes (vs ~nk² unrolled)."""
    sizes = {}
    for nk in (8, 32, 80):
        cfg = FV3Config(npx=6, nk=nk, halo=6, n_tracers=0)
        p = build_remap_program(cfg, cfg.seq_dom(), fields=("pt",))
        sizes[nk] = p.ir_node_count()
    assert sizes[80] <= 25 * 80
    # constant program: the search replaces every nk-dependent statement
    assert sizes[80] == sizes[32] == sizes[8]
    cfg = FV3Config(npx=6, nk=32, halo=6, n_tracers=0)
    unrolled = build_remap_program(cfg, cfg.seq_dom(), fields=("pt",),
                                   unrolled_interp=True)
    assert unrolled.ir_node_count() > 32 * 32
    assert unrolled.ir_node_count() > 4 * sizes[32]


def test_nested_index_search_rejected_at_construction():
    from repro.core.stencil.ir import FieldAccess, at_found, index_search

    inner = index_search("pe", FieldAccess("pe_ref"), at_found("fm"))
    with pytest.raises(ValueError, match="nested"):
        index_search("pe", FieldAccess("pe_ref"), inner)
    with pytest.raises(ValueError, match="nested"):
        index_search("pe", inner, at_found("fm"))


def test_level_search_schedules_whole_column_only():
    for hw in ("tpu-v5e", "p100"):
        for sched in feasible_schedules(S.interface_interp,
                                        DomainSpec(ni=16, nj=16, nk=16,
                                                   halo=6),
                                        hw=hw):
            assert sched.block_k == 0


# ---------------------------------------------------------------------------
# oracle correctness at production depth
# ---------------------------------------------------------------------------


def _interp_inputs(nk, dom, seed=1):
    rng = np.random.default_rng(seed)
    delp = rng.uniform(0.5, 1.5, dom.padded_shape()).astype(np.float32)
    q = rng.uniform(0.5, 1.5, dom.padded_shape()).astype(np.float32)
    pe = np.concatenate([np.zeros((1,) + delp.shape[1:], np.float32),
                         np.cumsum(delp, 0)], 0) + 10.0
    fm = np.concatenate([np.zeros((1,) + delp.shape[1:], np.float32),
                         np.cumsum(q * delp, 0)], 0)
    sigma = (np.arange(nk + 1, dtype=np.float32) / nk)[:, None, None]
    pe_ref = 10.0 + sigma * (pe[-1:] - 10.0)
    return pe, fm, pe_ref


@pytest.mark.parametrize("backend", ["jnp", "pallas-tpu"])
def test_search_interp_matches_jnp_interp_nk80(backend):
    nk = 80
    dom = DomainSpec(ni=3, nj=3, nk=nk, halo=2)
    pe, fm, pe_ref = _interp_inputs(nk, dom)
    run = compile_stencil(S.interface_interp, dom, backend=backend)
    fi = np.asarray(run({"fm": jnp.asarray(fm), "pe": jnp.asarray(pe),
                         "pe_ref": jnp.asarray(pe_ref),
                         "fi": jnp.zeros(dom.padded_shape(interface=True),
                                         jnp.float32)}, {})["fi"])
    h = dom.halo
    for j in range(h, h + dom.nj):
        for i in range(h, h + dom.ni):
            ref = np.interp(pe_ref[:, j, i], pe[:, j, i], fm[:, j, i])
            np.testing.assert_allclose(fi[:, j, i], ref, rtol=2e-5, atol=2e-5)
            # the bracketing layer equals searchsorted's (monotone column)
            s = np.clip(np.searchsorted(pe[1:-1, j, i], pe_ref[:, j, i],
                                        side="right"), 0, nk - 1)
            lo = pe[s, j, i]
            hi_ = pe[s + 1, j, i]
            interior = (pe_ref[:, j, i] >= pe[1, j, i]) & \
                       (pe_ref[:, j, i] <= pe[-2, j, i])
            assert np.all(lo[interior] <= pe_ref[interior, j, i] + 1e-5)
            assert np.all(pe_ref[interior, j, i] <= hi_[interior] + 1e-5)


def test_search_interp_jnp_pallas_bit_equal():
    nk = 80
    dom = DomainSpec(ni=3, nj=3, nk=nk, halo=2)
    pe, fm, pe_ref = _interp_inputs(nk, dom, seed=7)
    ins = {"fm": jnp.asarray(fm), "pe": jnp.asarray(pe),
           "pe_ref": jnp.asarray(pe_ref),
           "fi": jnp.zeros(dom.padded_shape(interface=True), jnp.float32)}
    outs = {}
    for backend in ("jnp", "pallas-tpu"):
        run = compile_stencil(S.interface_interp, dom, backend=backend)
        outs[backend] = np.asarray(run(dict(ins), {})["fi"])
    h = dom.halo
    I = np.s_[:, h:h + dom.nj, h:h + dom.ni]
    np.testing.assert_array_equal(outs["jnp"][I], outs["pallas-tpu"][I])


def _band_columns(case, nk, dom, rng, lead=()):
    """(pe, fm, pe_ref) interface columns for one band-limited search case:
    ``pe`` the Lagrangian interfaces (the search's coordinate), ``pe_ref``
    the reference interfaces it brackets."""
    shape = lead + dom.padded_shape()
    kprof = ((np.arange(nk) + 0.5) / nk)[:, None, None]
    if case == "initial_offset":
        # the dycore's initial thickness profile: up to 0.05 nk layers off
        # the uniform reference, columns apart by a smooth factor
        delp = rng.uniform(1.0, 1.02, lead + (1,) + shape[-2:]) \
            * (0.8 + 0.4 * kprof)
    elif case == "ties":
        delp = rng.integers(1, 3, shape).astype(np.float64)
    else:
        delp = 1.0 + 0.01 * rng.standard_normal(shape)
    if case == "zero_thickness":
        delp[..., ::5, :, :] = 0.0
        delp[..., :3, :, :] = 0.0
    delp = np.broadcast_to(delp, shape).astype(np.float32)
    zero = np.zeros(lead + (1,) + shape[-2:], np.float32)
    pe = np.cumsum(np.concatenate([zero + 10.0, delp], -3), -3,
                   dtype=np.float32)
    q = rng.uniform(0.5, 1.5, shape).astype(np.float32)
    fm = np.cumsum(np.concatenate([zero, q * delp], -3), -3, dtype=np.float32)
    total = pe[..., -1:, :, :] - 10.0
    sigma = (np.arange(nk + 1, dtype=np.float32) / nk)[:, None, None]
    pe_ref = (10.0 + sigma * total).astype(np.float32)
    if case == "ties":
        pe_ref = pe.copy()          # every target equals a coordinate
    elif case == "catch_alls":
        # targets above the first searched interface and below the last
        pe_ref = (5.0 + sigma * (total + 10.0)).astype(np.float32)
    elif case == "non_monotone":
        # a deep interface dips to the top of its column: the full march
        # selects it for every target above it
        col = (Ellipsis, slice(None), dom.halo + 1, dom.halo + 2)
        pe[col][..., nk - 3] = pe[col][..., 2]
    return pe, fm, pe_ref


_BAND_CASES = {
    # case: (data, nk, members, batch, J-tile rows)
    "near_reference": ("near_reference", 24, None, "vmap", 4),
    "initial_offset_nk80": ("initial_offset", 80, None, "vmap", 0),
    "ties": ("ties", 24, None, "vmap", 4),
    "zero_thickness": ("zero_thickness", 24, None, "vmap", 4),
    "catch_alls": ("catch_alls", 24, None, "vmap", 4),
    "non_monotone": ("non_monotone", 24, None, "vmap", 4),
    "member_grid_m2": ("initial_offset", 24, 2, "grid", 4),
    "chunked_members": ("near_reference", 24, 4, "vmap:2,grid", 0),
}


@pytest.mark.parametrize("case", sorted(_BAND_CASES))
def test_band_search_matches_full_march(case, monkeypatch):
    """The band-limited Pallas search selects what a march over every
    source layer selects, bit for bit: the full march is the same lowering
    with each block's band widened to the whole ``[lo, hi-1]``."""
    from repro.core.backend import lowering_pallas as LP

    data, nk, members, batch, bj = _BAND_CASES[case]
    dom = DomainSpec(ni=6, nj=10, nk=nk, halo=2)
    lead = (members,) if members else ()
    pe, fm, pe_ref = _band_columns(data, nk, dom, np.random.default_rng(3),
                                   lead)
    if case == "non_monotone":
        assert np.any(np.diff(pe, axis=-3) < 0)
    ins = {"fm": jnp.asarray(fm), "pe": jnp.asarray(pe),
           "pe_ref": jnp.asarray(pe_ref),
           "fi": jnp.zeros(lead + dom.padded_shape(interface=True),
                           jnp.float32)}
    sched = Schedule(block_j=bj)

    def run():
        fn = compile_stencil(S.interface_interp, dom, backend="pallas-tpu",
                             schedule=sched, memoize=False,
                             n_members=members, batch=batch)
        return np.asarray(fn(dict(ins), {})["fi"])

    band = run()
    # cmax holds the compared levels lo+1 .. hi-1
    monkeypatch.setattr(LP, "_band", lambda cmax, cmin, tmin, tmax, lo: (
        jnp.int32(lo), jnp.int32(lo + cmax.shape[0])))
    full = run()
    np.testing.assert_array_equal(band, full)
    assert np.isfinite(band).all()


@pytest.mark.parametrize("span", ["column", "reference"])
def test_search_band_share(span):
    """The counter of marched pairs: a window whose surfaces span the whole
    column marches everything; one on the reference marches about a
    block's rows plus two layers per block."""
    from repro.core.backend.lowering_pallas import band_rows, \
        search_band_share

    nk, window = 80, (8, 128)     # a C128 tile's J tile, as the remap runs
    rows = band_rows(window, 4)
    assert rows == 4
    rng = np.random.default_rng(0)
    sigma = (np.arange(nk + 1, dtype=np.float32) / nk)[:, None, None]
    scale = rng.uniform(1.0, 1.02, (2, 1) + window).astype(np.float32)
    pe = (10.0 + 80.0 * sigma * scale).astype(np.float32)
    if span == "column":
        # one column's surfaces crowd at the top, another's at the bottom
        pe[0, :, 0, 0] = 10.0 + 1e-3 * np.arange(nk + 1)
        pe[0, :, 0, 1] = 90.0 + 1e-3 * np.arange(nk + 1)
    share = search_band_share(pe, pe.copy(), window, lo=0, hi=nk, n_found=4)
    if span == "column":
        assert share > 0.5
        one = search_band_share(pe[:1], pe[:1].copy(), window, lo=0, hi=nk,
                                n_found=4)
        assert one == 1.0
    else:
        assert share <= (rows + 2) / nk


def test_search_matches_unrolled_path():
    """The construct replaces the unrolled where-chain bit for bit."""
    cfg = FV3Config(npx=4, nk=6, halo=6, n_tracers=0)
    dom = cfg.seq_dom()
    rng = np.random.default_rng(3)
    ins = {"delp": jnp.asarray(rng.uniform(0.8, 1.2, dom.padded_shape()),
                               jnp.float32),
           "pt": jnp.asarray(rng.uniform(0.9, 1.1, dom.padded_shape()),
                             jnp.float32)}
    params = default_params(cfg)
    new = compile_program(build_remap_program(cfg, dom, fields=("pt",)),
                          "jnp")(dict(ins), params)
    old = compile_program(build_remap_program(cfg, dom, fields=("pt",),
                                              unrolled_interp=True),
                          "jnp")(dict(ins), params)
    h, N = cfg.halo, cfg.npx
    I = np.s_[:, h:h + N, h:h + N]
    for k in ("delp_out", "pt_out"):
        np.testing.assert_allclose(np.asarray(new[k])[I],
                                   np.asarray(old[k])[I],
                                   rtol=1e-6, atol=1e-6, err_msg=k)


@pytest.mark.parametrize("backend,opt_level",
                         [("jnp", 0), ("jnp", 3),
                          ("pallas-tpu", 0), ("pallas-tpu", 3)])
def test_remap_nk80_compiles_and_matches_oracle(backend, opt_level):
    """Acceptance: the nk=80 remap compiles and matches the jnp oracle on
    both backends at the opt-ladder extremes."""
    cfg = FV3Config(npx=3, nk=80, halo=6, n_tracers=0)
    dom = cfg.seq_dom()
    rng = np.random.default_rng(11)
    ins = {"delp": jnp.asarray(rng.uniform(0.8, 1.2, dom.padded_shape()),
                               jnp.float32),
           "pt": jnp.asarray(rng.uniform(0.9, 1.1, dom.padded_shape()),
                             jnp.float32)}
    params = default_params(cfg)
    p = build_remap_program(cfg, dom, fields=("pt",))
    ref = compile_program(p, "jnp")(dict(ins), params)
    got = compile_program(p, backend,
                          opt_level=opt_level)(dict(ins), params)
    h, N = cfg.halo, cfg.npx
    I = np.s_[:, h:h + N, h:h + N]
    for k in ("delp_out", "pt_out"):
        np.testing.assert_allclose(np.asarray(ref[k])[I],
                                   np.asarray(got[k])[I],
                                   rtol=1e-5, atol=1e-5, err_msg=k)


# ---------------------------------------------------------------------------
# K-blocked vertical solver schedules
# ---------------------------------------------------------------------------


@gtstencil
def _fwd_cumsum(delp: Field, q: Field, fm: Field):
    with computation(FORWARD):
        with interval(0, 1):
            fm = q * delp
        with interval(1, None):
            fm = fm[0, 0, -1] + q[0, 0, -1] * delp[0, 0, -1]


@gtstencil
def _bwd_subst(rhs: Field, cc: Field, pp: Field):
    with computation(BACKWARD):
        with interval(-1, None):
            pp = rhs
        with interval(0, -1):
            pp = rhs[0, 0, 0] - cc[0, 0, 0] * pp[0, 0, 1]


@gtstencil
def _bwd_open_sum(d: Field, x: Field):
    # every level reads the one below it, the bottom level too: the Pallas
    # marches carry a zero plane into the first level they march
    with computation(BACKWARD), interval(...):
        x = d + x[0, 0, 1]


@gtstencil
def _cross_comp_prev_read(a: Field, b: Field):
    # comp1 reads comp2's target at the marching-previous level: reference
    # semantics give comp1 b's PRE-sweep values, which a per-level
    # interleaved march cannot provide
    with computation(FORWARD):
        with interval(1, None):
            a = b[0, 0, -1] + 1.0
    with computation(FORWARD):
        with interval(...):
            b = a[0, 0, 0] * 2.0


def test_cross_computation_prev_read_not_blockable():
    assert not solver_k_blockable(_cross_comp_prev_read)
    # and therefore a blocked schedule silently lowers whole-column,
    # bit-matching the jnp reference
    dom = DomainSpec(ni=4, nj=3, nk=8, halo=2)
    rng = np.random.default_rng(13)
    ins = {f: jnp.asarray(rng.uniform(0.2, 1.2, dom.padded_shape()),
                          jnp.float32) for f in ("a", "b")}
    ref = compile_stencil(_cross_comp_prev_read, dom, backend="jnp")(
        dict(ins), {})
    got = compile_stencil(_cross_comp_prev_read, dom, backend="pallas-tpu",
                          schedule=Schedule(block_k=4, k_as_grid=False))(
                              dict(ins), {})
    for k in ref:
        np.testing.assert_array_equal(np.asarray(ref[k]), np.asarray(got[k]),
                                      err_msg=k)


def test_level_search_shift_raises():
    st = S.interface_interp
    search = st.computations[0].statements[0].value
    assert isinstance(search, LevelSearch)
    for off in ((1, 0, 0), (0, 1, 0), (0, 0, 1)):
        with pytest.raises(ValueError, match="cannot shift|cannot K-shift"):
            search.shift(off)
    assert search.shift((0, 0, 0)) is search


def test_solver_k_blockable_rules():
    # single-direction solvers with one-level carries: blockable
    assert solver_k_blockable(_fwd_cumsum)
    assert solver_k_blockable(_bwd_subst)
    assert solver_k_blockable(S.precompute_pe)
    # FORWARD+BACKWARD (Thomas algorithm) needs two passes: whole column
    assert not solver_k_blockable(S.tridiag_solve)
    # interface fields never K-tile
    assert not solver_k_blockable(S.lagrangian_pe)
    assert not solver_k_blockable(S.cumsum_mass)
    # level searches read whole coordinate columns
    assert not solver_k_blockable(S.interface_interp)


def test_kblocked_schedules_enumerated_and_fit_vmem():
    """At production depth on a large tile, whole-column blocks exceed VMEM
    and the K-blocked marching schedules are the only feasible options."""
    # 4 MiB holds the double-buffered halo-padded blocks of K blocks of 4
    # levels (~2.5 MiB), not those of 8 levels or of whole columns
    tiny = Hardware("test-tiny-vmem", peak_flops=1e12, hbm_bw=1e11,
                    link_bw=0, vmem_bytes=4 * 1024 * 1024, kind="tpu")
    dom = DomainSpec(ni=128, nj=96, nk=80, halo=3)
    scheds = list(feasible_schedules(S.precompute_pe, dom, hw=tiny))
    assert scheds, "nk=80 must stay schedulable via K blocking"
    assert all(s.block_k != 0 for s in scheds), \
        "whole-column blocks cannot fit this VMEM"
    assert all(not s.k_as_grid for s in scheds)
    # the cost model agrees: whole-column is priced infeasible, blocked not
    whole = Schedule(block_k=0, k_as_grid=False)
    assert model_cost(S.precompute_pe, whole, dom, tiny) == float("inf")
    assert model_cost(S.precompute_pe, scheds[0], dom, tiny) < float("inf")
    # non-blockable solvers never get blocked schedules
    for s in feasible_schedules(S.tridiag_solve,
                                DomainSpec(ni=16, nj=16, nk=80, halo=6),
                                hw="tpu-v5e"):
        assert s.block_k == 0


@pytest.mark.parametrize("stencil,fields", [
    (_fwd_cumsum, ("delp", "q", "fm")),
    (_bwd_subst, ("rhs", "cc", "pp")),
])
@pytest.mark.parametrize("bk,nk", [
    pytest.param(4, 16, id="4"),
    pytest.param(8, 16, id="8"),
    # ragged: the last block down (first up) holds 1 or 5 levels
    pytest.param(4, 13, id="4-nk13"),
    pytest.param(8, 13, id="8-nk13"),
])
def test_kblocked_kernel_matches_whole_column(stencil, fields, bk, nk):
    dom = DomainSpec(ni=5, nj=4, nk=nk, halo=2)
    rng = np.random.default_rng(5)
    ins = {f: jnp.asarray(rng.uniform(0.2, 1.2, dom.padded_shape()),
                          jnp.float32) for f in fields}
    ref = compile_stencil(stencil, dom, backend="jnp")(dict(ins), {})
    sched = Schedule(block_i=0, block_j=0, block_k=bk, k_as_grid=False)
    got = compile_stencil(stencil, dom, backend="pallas-tpu",
                          schedule=sched)(dict(ins), {})
    whole = compile_stencil(stencil, dom, backend="pallas-tpu",
                            schedule=Schedule(block_k=0, k_as_grid=False))(
        dict(ins), {})
    for k in ref:
        np.testing.assert_array_equal(np.asarray(ref[k]), np.asarray(got[k]),
                                      err_msg=k)
        np.testing.assert_array_equal(np.asarray(whole[k]),
                                      np.asarray(got[k]), err_msg=k)


@pytest.mark.parametrize("bk", [4, 8])
def test_kblocked_ragged_march_starts_at_the_bottom_level(bk):
    """Going up, the ragged block is marched first: its march starts at
    level nk - 1 with the zero carry, never at a padded row past nk."""
    dom = DomainSpec(ni=5, nj=4, nk=13, halo=2)
    rng = np.random.default_rng(3)
    ins = {f: jnp.asarray(rng.uniform(0.2, 1.2, dom.padded_shape()),
                          jnp.float32) for f in ("d", "x")}
    got = compile_stencil(_bwd_open_sum, dom, backend="pallas-tpu",
                          schedule=Schedule(block_k=bk, k_as_grid=False))(
        dict(ins), {})
    whole = compile_stencil(_bwd_open_sum, dom, backend="pallas-tpu",
                            schedule=Schedule(block_k=0, k_as_grid=False))(
        dict(ins), {})
    np.testing.assert_array_equal(np.asarray(whole["x"]), np.asarray(got["x"]))


@pytest.mark.parametrize("bk", [4, 8])
def test_kblocked_ragged_precompute_pe_matches_oracle(bk):
    """d_sw's FORWARD solver at a depth no K block divides: the ragged
    bottom block marches only its real levels."""
    dom = DomainSpec(ni=6, nj=5, nk=13, halo=2)
    rng = np.random.default_rng(11)
    ins = {f: jnp.asarray(rng.uniform(0.3, 1.3, dom.padded_shape()),
                          jnp.float32) for f in ("delp", "pe")}
    params = {"ptop": 10.0}
    ref = compile_stencil(S.precompute_pe, dom, backend="jnp")(
        dict(ins), params)
    sched = Schedule(block_k=bk, k_as_grid=False)
    got = compile_stencil(S.precompute_pe, dom, backend="pallas-tpu",
                          schedule=sched)(dict(ins), params)
    whole = compile_stencil(S.precompute_pe, dom, backend="pallas-tpu",
                            schedule=Schedule(block_k=0, k_as_grid=False))(
        dict(ins), params)
    np.testing.assert_array_equal(np.asarray(ref["pe"]), np.asarray(got["pe"]))
    np.testing.assert_array_equal(np.asarray(whole["pe"]),
                                  np.asarray(got["pe"]))


def test_kblocked_fused_solver_legality_and_correctness():
    """SGF-fusing two FORWARD stencils stays K-blockable and bit-exact."""
    from repro.core import StencilProgram
    from repro.core.transforms import subgraph_fuse

    dom = DomainSpec(ni=4, nj=4, nk=16, halo=2)
    p = StencilProgram("fused_solver", dom)
    for f in ("delp", "q", "fm", "pe"):
        p.declare(f)
    n1 = p.add(S.precompute_pe, {"delp": "delp", "pe": "pe"})
    n2 = p.add(_fwd_cumsum, {"delp": "delp", "q": "q", "fm": "fm"})
    p.propagate_extents()
    assert can_subgraph_fuse([n1, n2], halo=p.dom.halo)
    fused = subgraph_fuse(p, p.states[0], [n1, n2])
    assert solver_k_blockable(fused.stencil)
    rng = np.random.default_rng(9)
    ins = {f: jnp.asarray(rng.uniform(0.3, 1.3, dom.padded_shape()),
                          jnp.float32) for f in ("delp", "q", "fm", "pe")}
    params = {"ptop": 10.0}
    ref = compile_stencil(fused.stencil, dom, backend="jnp")(dict(ins), params)
    sched = Schedule(block_k=4, k_as_grid=False)
    got = compile_stencil(fused.stencil, dom, backend="pallas-tpu",
                          schedule=sched)(dict(ins), params)
    for k in ref:
        np.testing.assert_array_equal(np.asarray(ref[k]), np.asarray(got[k]),
                                      err_msg=k)


def test_otf_fusion_rejects_level_search():
    """OTF inlining across a LevelSearch is illegal in both directions."""
    cfg = FV3Config(npx=4, nk=4, halo=6, n_tracers=0)
    p = build_remap_program(cfg, cfg.seq_dom(), fields=("pt",))
    nodes = p.all_nodes()
    interp = next(n for n in nodes if n.stencil.name == "remap_interp")
    cumsum = next(n for n in nodes
                  if n.stencil.name.startswith("cumsum_mass"))
    remapf = next(n for n in nodes
                  if n.stencil.name.startswith("remap_field"))
    assert not can_otf_fuse(cumsum, interp)   # consumer reads via search
    assert not can_otf_fuse(interp, remapf)   # producer def is a search


# ---------------------------------------------------------------------------
# tuning-cache invalidation across the cost-model version bump
# ---------------------------------------------------------------------------


def test_cost_model_version_bump_invalidates_cache(tmp_path):
    assert COST_MODEL_VERSION >= 5, \
        "sequential-K schedules require a cost-model version bump"
    cache = TuningCache(tmp_path / "tuning.json")
    dom = DomainSpec(ni=16, nj=16, nk=16, halo=3)
    stale_key = make_key("tune_stencil", COST_MODEL_VERSION - 1,
                         S.precompute_pe, dom, "pallas-tpu", "tpu-v5e", 1)
    live_key = make_key("tune_stencil", COST_MODEL_VERSION,
                        S.precompute_pe, dom, "pallas-tpu", "tpu-v5e", 1)
    assert stale_key != live_key
    # a v(N-1) entry must never be served to the vN model
    cache.put(stale_key, [{"schedule": Schedule().to_dict(),
                           "cost": 0.0, "n_evaluated": 1}])
    res = tune_stencil(S.precompute_pe, dom, hw="tpu-v5e",
                       backend="pallas-tpu", cache=cache)
    assert res and not res[0].from_cache
    # the same model version hits its own entry
    res2 = tune_stencil(S.precompute_pe, dom, hw="tpu-v5e",
                        backend="pallas-tpu", cache=cache)
    assert res2[0].from_cache
    assert res2[0].schedule == res[0].schedule
