"""K-interface fields + compiled vertical remap + scan-rolled model step.

Covers the vertical-dimension compiler work:
 * ``Field[interface]`` parsing and nk+1-level lowering (jnp and Pallas);
 * the DSL vertical remap through ``compile_program`` — reference
   equivalence, interface fields visible in the IR, opt-ladder round trip;
 * the mass-conservation regression the old hand-written remap fails
   (``maximum(delp_ref, 1e-10)`` denominator floor on thin layers);
 * fusion/schedule legality: interface and center fields never co-tile in K;
 * scan-rolled vs unrolled step bit-equivalence at opt levels 0 and 3, and
   the single-dispatch property of ``make_step_sequential``.
"""

import numpy as np
import pytest
import jax.numpy as jnp

from repro.core import compile_program
from repro.core.backend import compile_stencil
from repro.core.stencil import (DomainSpec, Field, Param,
                                feasible_schedules, gtstencil, interface)
from repro.core.transforms import can_otf_fuse
from repro.fv3 import stencils as S
from repro.fv3.dyncore import (
    FV3Config,
    build_remap_program,
    default_params,
    make_step_sequential,
    vertical_remap,
    vertical_remap_reference,
)
from repro.fv3.state import init_state


# ---------------------------------------------------------------------------
# Field[interface] frontend + lowerings
# ---------------------------------------------------------------------------


@gtstencil
def _iface_build(delp: Field, pe: Field[interface], ptop: Param):
    with computation(FORWARD):
        with interval(0, 1):
            pe = ptop
        with interval(1, None):
            pe = pe[0, 0, -1] + delp[0, 0, -1]


@gtstencil
def _iface_diff(pe: Field[interface], dp: Field):
    with computation(PARALLEL), interval(...):
        dp = pe[0, 0, 1] - pe[0, 0, 0]


def test_interface_annotation_parses():
    assert _iface_build.fields == ("delp", "pe")
    assert _iface_build.interface_fields == ("pe",)
    assert _iface_build.params == ("ptop",)
    assert _iface_build.is_interface("pe") and not _iface_build.is_interface("delp")
    assert _iface_build.k_extent_of("pe", 8) == 9
    assert _iface_build.k_extent_of("delp", 8) == 8


def test_domain_padded_shape_interface():
    dom = DomainSpec(ni=4, nj=3, nk=8, halo=2)
    assert dom.padded_shape() == (8, 7, 8)
    assert dom.padded_shape(interface=True) == (9, 7, 8)


@pytest.mark.parametrize("backend", ["jnp", "pallas-tpu"])
def test_interface_build_and_diff_roundtrip(backend):
    """FORWARD build onto nk+1 interface levels, then exact differencing
    back: recovers delp identically on the interior."""
    dom = DomainSpec(ni=5, nj=4, nk=6, halo=2)
    rng = np.random.default_rng(0)
    delp = jnp.asarray(rng.uniform(0.5, 1.5, dom.padded_shape()), jnp.float32)
    pe0 = jnp.zeros(dom.padded_shape(interface=True), jnp.float32)
    f = compile_stencil(_iface_build, dom, backend=backend)
    pe = f({"delp": delp, "pe": pe0}, {"ptop": 10.0})["pe"]
    assert pe.shape == dom.padded_shape(interface=True)
    h = dom.halo
    I = np.s_[:, h:h + dom.nj, h:h + dom.ni]
    ref = 10.0 + np.concatenate(
        [np.zeros((1,) + delp.shape[1:]), np.cumsum(np.asarray(delp), 0)], 0)
    np.testing.assert_allclose(np.asarray(pe)[I], ref[I], rtol=1e-6)
    g = compile_stencil(_iface_diff, dom, backend=backend)
    dp = g({"pe": pe, "dp": jnp.zeros(dom.padded_shape(), jnp.float32)}, {})["dp"]
    np.testing.assert_allclose(np.asarray(dp)[I], np.asarray(delp)[I],
                               rtol=1e-5, atol=1e-6)


def test_interp_stencil_matches_jnp_interp():
    """The data-oblivious piecewise-linear interpolation stencil equals the
    hand-written ``jnp.interp`` level search it replaces."""
    nk = 6
    dom = DomainSpec(ni=4, nj=3, nk=nk, halo=2)
    st = S.interface_interp_stencil(nk)
    assert set(st.interface_fields) == {"fm", "pe", "pe_ref", "fi"}
    rng = np.random.default_rng(1)
    shape_i = dom.padded_shape(interface=True)
    delp = rng.uniform(0.5, 1.5, dom.padded_shape()).astype(np.float32)
    q = rng.uniform(0.5, 1.5, dom.padded_shape()).astype(np.float32)
    pe = np.concatenate([np.zeros((1,) + delp.shape[1:], np.float32),
                         np.cumsum(delp, 0)], 0) + 10.0
    fm = np.concatenate([np.zeros((1,) + delp.shape[1:], np.float32),
                         np.cumsum(q * delp, 0)], 0)
    sigma = (np.arange(nk + 1, dtype=np.float32) / nk)[:, None, None]
    pe_ref = 10.0 + sigma * (pe[-1:] - 10.0)
    run = compile_stencil(st, dom, backend="jnp")
    fi = run({"fm": jnp.asarray(fm), "pe": jnp.asarray(pe),
              "pe_ref": jnp.asarray(pe_ref),
              "fi": jnp.zeros(shape_i, jnp.float32)}, {})["fi"]
    # oracle: per-column numpy interp
    h = dom.halo
    got = np.asarray(fi)
    for j in range(h, h + dom.nj):
        for i in range(h, h + dom.ni):
            ref = np.interp(pe_ref[:, j, i], pe[:, j, i], fm[:, j, i])
            np.testing.assert_allclose(got[:, j, i], ref, rtol=2e-5,
                                       atol=2e-5)


# ---------------------------------------------------------------------------
# compiled vertical remap
# ---------------------------------------------------------------------------


def _remap_cfg(**kw):
    base = dict(npx=6, nk=4, halo=6, n_tracers=1)
    base.update(kw)
    return FV3Config(**base)


def test_remap_program_has_interface_fields_in_ir():
    cfg = _remap_cfg()
    p = build_remap_program(cfg, cfg.seq_dom())
    iface_nodes = [n for n in p.all_nodes() if n.stencil.has_interface_fields()]
    assert iface_nodes, "remap program must carry interface fields in the IR"
    assert p.fields["pe"].interface and p.fields["pe_ref"].interface
    fn = compile_program(p, "jnp")
    assert fn.n_kernels == len(p.all_nodes())


def test_remap_matches_reference_on_benign_columns():
    cfg = _remap_cfg()
    dom = cfg.seq_dom()
    rng = np.random.default_rng(2)
    delp = jnp.asarray(rng.uniform(0.8, 1.2, dom.padded_shape()), jnp.float32)
    flds = {k: jnp.asarray(rng.uniform(0.5, 1.5, dom.padded_shape()),
                           jnp.float32) for k in ("pt", "w")}
    d_ref, o_ref = vertical_remap_reference(cfg, delp, dict(flds))
    d_new, o_new = vertical_remap(cfg, delp, dict(flds))
    h, N = cfg.halo, cfg.npx
    I = np.s_[:, h:h + N, h:h + N]
    np.testing.assert_allclose(np.asarray(d_ref)[I], np.asarray(d_new)[I],
                               rtol=1e-5, atol=1e-6)
    for k in flds:
        np.testing.assert_allclose(np.asarray(o_ref[k])[I],
                                   np.asarray(o_new[k])[I],
                                   rtol=1e-4, atol=1e-5, err_msg=k)


def _tracer_mass(q, delp, cfg):
    h, N = cfg.halo, cfg.npx
    I = np.s_[:, h:h + N, h:h + N]
    return float(np.sum(np.asarray(q, np.float64)[I]
                        * np.asarray(delp, np.float64)[I]))


def test_mass_conservation_regression_thin_layers():
    """The old remap's ``maximum(delp_ref, 1e-10)`` floor destroys tracer
    mass when reference layers are thinner than the floor; the DSL path's
    exact interface differencing conserves ``sum(q * delp)``.  This test
    fails on the old code by construction (its error is asserted large)."""
    cfg = _remap_cfg(ptop=0.0)
    dom = cfg.seq_dom()
    rng = np.random.default_rng(3)
    # delp_ref ~ 2e-11 per layer — far below the old 1e-10 denominator floor
    delp = jnp.asarray(rng.uniform(1e-11, 3e-11, dom.padded_shape()),
                       jnp.float32)
    q = jnp.asarray(rng.uniform(0.5, 1.5, dom.padded_shape()), jnp.float32)
    m0 = _tracer_mass(q, delp, cfg)

    d_old, o_old = vertical_remap_reference(cfg, delp, {"q": q})
    m_old = _tracer_mass(o_old["q"], d_old, cfg)
    assert abs(m_old - m0) / m0 > 0.5, \
        "expected the floored remap to violate conservation badly"

    d_new, o_new = vertical_remap(cfg, delp, {"q": q})
    m_new = _tracer_mass(o_new["q"], d_new, cfg)
    assert abs(m_new - m0) / m0 < 1e-5


def test_mass_conservation_exact_differencing_normal_columns():
    cfg = _remap_cfg()
    dom = cfg.seq_dom()
    rng = np.random.default_rng(4)
    delp = jnp.asarray(rng.uniform(0.3, 1.7, dom.padded_shape()), jnp.float32)
    q = jnp.asarray(rng.uniform(0.0, 2.0, dom.padded_shape()), jnp.float32)
    m0 = _tracer_mass(q, delp, cfg)
    d_new, o_new = vertical_remap(cfg, delp, {"q": q})
    m_new = _tracer_mass(o_new["q"], d_new, cfg)
    assert abs(m_new - m0) / m0 < 1e-5


@pytest.mark.parametrize("backend", ["pallas-tpu"])
def test_remap_program_pallas_matches_jnp(backend):
    cfg = _remap_cfg(npx=4, nk=3, n_tracers=0)
    dom = cfg.seq_dom()
    p = build_remap_program(cfg, dom, fields=("pt",))
    rng = np.random.default_rng(5)
    ins = {"delp": jnp.asarray(rng.uniform(0.8, 1.2, dom.padded_shape()),
                               jnp.float32),
           "pt": jnp.asarray(rng.uniform(0.9, 1.1, dom.padded_shape()),
                             jnp.float32)}
    params = default_params(cfg)
    ref = compile_program(p, "jnp")(dict(ins), params)
    got = compile_program(p, backend)(dict(ins), params)
    h, N = cfg.halo, cfg.npx
    I = np.s_[:, h:h + N, h:h + N]
    for k in ("delp_out", "pt_out"):
        np.testing.assert_allclose(np.asarray(ref[k])[I],
                                   np.asarray(got[k])[I],
                                   rtol=1e-6, atol=1e-6, err_msg=k)


def test_remap_opt3_matches_opt0():
    cfg = _remap_cfg()
    dom = cfg.seq_dom()
    p = build_remap_program(cfg, dom)
    rng = np.random.default_rng(6)
    names = ("pt", "w", "u", "v", *cfg.tracers)
    ins = {k: jnp.asarray(rng.uniform(0.8, 1.2, dom.padded_shape()),
                          jnp.float32) for k in ("delp", *names)}
    params = default_params(cfg)
    ref = compile_program(p, "jnp")(dict(ins), params)
    got = compile_program(p, "jnp", opt_level=3)(dict(ins), params)
    h, N = cfg.halo, cfg.npx
    I = np.s_[:, h:h + N, h:h + N]
    for q in names:
        np.testing.assert_allclose(np.asarray(ref[f"{q}_out"])[I],
                                   np.asarray(got[f"{q}_out"])[I],
                                   rtol=1e-6, atol=1e-6, err_msg=q)


# ---------------------------------------------------------------------------
# fusion / schedule legality: interface and center never co-tile in K
# ---------------------------------------------------------------------------


def test_interface_schedules_never_tile_k():
    from repro.core.stencil import default_schedule, heuristic_schedule

    dom_shape = DomainSpec(ni=16, nj=16, nk=8, halo=1)
    for hw in ("tpu-v5e", "p100"):
        for sched in feasible_schedules(_iface_diff, dom_shape, hw=hw):
            assert sched.block_k == 0, \
                f"interface stencil offered a K tile on {hw}: {sched}"
        # the heuristic (what greedy_fuse prices fusions with) and the
        # default must obey the same whole-column rule on every hardware
        assert heuristic_schedule(_iface_diff, dom_shape, hw=hw).block_k == 0
        assert default_schedule(_iface_diff, dom_shape, hw=hw).block_k == 0


def test_otf_rejects_interface_center_boundary():
    cfg = _remap_cfg(npx=4, nk=3, n_tracers=0)
    dom = cfg.seq_dom()
    p = build_remap_program(cfg, dom, fields=("pt",))
    nodes = p.all_nodes()
    interp = next(n for n in nodes if n.stencil.name.startswith("remap_interp"))
    remapf = next(n for n in nodes if n.stencil.name.startswith("remap_field"))
    # interp produces the interface field fi consumed by remap_field: OTF
    # inlining across the interface/center extent boundary is illegal
    assert not can_otf_fuse(interp, remapf)


# ---------------------------------------------------------------------------
# scan-rolled step: bit equivalence + single dispatch
# ---------------------------------------------------------------------------


STEP_CFG = FV3Config(npx=8, nk=4, halo=6, n_split=2, k_split=2, n_tracers=1)


def _fresh_state():
    # per-call state: with donate=True the step consumes its input on
    # platforms honoring donation, so never share a state between step
    # functions — init_state is deterministic, so fresh copies are
    # identical inputs
    return init_state(STEP_CFG)


@pytest.mark.parametrize("opt_level", [0, 3])
def test_scan_step_bit_equals_unrolled(opt_level):
    scan_step = make_step_sequential(STEP_CFG, opt_level=opt_level)
    unrolled_step = make_step_sequential(STEP_CFG, opt_level=opt_level,
                                         unroll=True)
    s_scan = scan_step(_fresh_state())
    s_unrl = unrolled_step(_fresh_state())
    for k in s_scan:
        np.testing.assert_array_equal(
            np.asarray(s_scan[k]), np.asarray(s_unrl[k]),
            err_msg=f"opt{opt_level}/{k}: scan path diverged from the "
                    "unrolled loop")


def test_step_single_dispatch_and_trace_counts():
    # donate=True is safe here: every input is fresh or the previous output
    scan_step = make_step_sequential(STEP_CFG, opt_level=0, donate=True)
    unrolled_step = make_step_sequential(STEP_CFG, opt_level=0, unroll=True)
    s = scan_step(_fresh_state())      # trace + compile
    unrolled_step(_fresh_state())
    # scan traces the acoustic body once regardless of n_split * k_split;
    # the unrolled loop traces it per substep
    assert scan_step.counters["acoustic_traces"] <= 2
    assert (unrolled_step.counters["acoustic_traces"]
            >= STEP_CFG.n_split * STEP_CFG.k_split)
    # steady state: the whole step is ONE jitted call — re-invoking it runs
    # no Python-level kernel dispatch and no re-trace
    before = dict(scan_step.counters)
    s2 = scan_step(s)
    assert scan_step.counters["acoustic_traces"] == before["acoustic_traces"]
    assert (scan_step.counters["runner_dispatches"]
            == before["runner_dispatches"])
    assert scan_step.counters["step_calls"] == before["step_calls"] + 1
    # introspection covers acoustic + tracer + remap
    assert set(scan_step.opt_report) == {"c_sw+riem", "d_sw", "tracer_2d",
                                         "vertical_remap"}
    assert scan_step.n_kernels > 0
