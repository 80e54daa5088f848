"""Ensemble axis through the whole toolchain.

The member/batch dimension is a *compilation-layer* decision
(``compile_program(..., n_members=M, batch="vmap"|"grid")``), not a
per-stencil rewrite — so the tests here assert the strongest property that
makes the axis trustworthy: every batched path is **bit-identical** to the
corresponding per-member loop on the same backend at the same opt level.
Covered: both lowerings (jnp vmap, Pallas member grid) over horizontal
stencils, whole-column solvers, K-blocked marching solvers, K-interface
fields and the ``index_search`` remap; the batched reference halo exchange;
the full ``make_step_ensemble`` step; and the cost-model/tuning-cache
plumbing (launch amortization, per-M cache keys).
"""

import numpy as np
import jax.numpy as jnp
import pytest

from repro.core import StencilProgram, compile_program
from repro.core.autotune import model_cost, tune_stencil
from repro.core.stencil import DomainSpec
from repro.core.stencil.schedule import Schedule, solver_k_blockable
from repro.fv3 import stencils as S
from repro.fv3.dyncore import (FV3Config, build_csw_program,
                               build_remap_program, default_params,
                               make_step_distributed, make_step_ensemble,
                               make_step_sequential)
from repro.fv3.halo import exchange_reference
from repro.fv3.state import ensemble_state, init_state

RNG = np.random.default_rng(7)


def _fvt_program(dom: DomainSpec) -> StencilProgram:
    p = StencilProgram("ens_fvt", dom)
    for f in ("q", "u", "v", "qout"):
        p.declare(f)
    for f in ("cx", "cy"):
        p.declare(f, transient=True)
    p.add(S.courant_x, {"u": "u", "cx": "cx"})
    p.add(S.courant_y, {"v": "v", "cy": "cy"})
    p.add(S.flux_divergence, {"q": "q", "fx": "cx", "fy": "cy",
                              "qout": "qout"})
    p.propagate_extents()
    return p


FVT_PARAMS = {"dtdx": 0.02, "dtdy": 0.02, "rdx": 1.0, "rdy": 1.0}


def _member_fields(names, dom: DomainSpec, M: int) -> dict:
    return {f: jnp.asarray(RNG.uniform(0.8, 1.2, (M,) + dom.padded_shape()),
                           jnp.float32) for f in names}


def _per_member(fn, fields, params, M):
    return [fn({k: v[m] for k, v in fields.items()}, params)
            for m in range(M)]


def _assert_bit_equal(batched: dict, singles: list, keys=None):
    keys = keys if keys is not None else list(batched)
    for k in keys:
        ref = np.stack([np.asarray(o[k]) for o in singles])
        got = np.asarray(batched[k])
        assert got.shape == ref.shape, (k, got.shape, ref.shape)
        assert np.array_equal(got, ref), \
            (k, float(np.abs(got - ref).max()))


# ---------------------------------------------------------------------------
# compile_program: batched lowering == per-member loop, bit for bit
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("backend,batch", [
    ("jnp", "vmap"), ("jnp", "grid"),
    ("pallas-tpu", "grid"), ("pallas-tpu", "vmap"),
])
def test_batched_fvt_matches_member_loop(backend, batch):
    dom = DomainSpec(ni=8, nj=8, nk=4, halo=6)
    p = _fvt_program(dom)
    M = 3
    fields = _member_fields(p.fields, dom, M)
    single = compile_program(p, backend)
    singles = _per_member(single, fields, FVT_PARAMS, M)
    fn = compile_program(p, backend, n_members=M, batch=batch)
    out = fn(dict(fields), FVT_PARAMS)
    _assert_bit_equal(out, singles, keys=["qout"])
    assert fn.n_kernels == single.n_kernels
    assert fn.n_members == M and fn.batch == batch


@pytest.mark.parametrize("backend", ["jnp", "pallas-tpu"])
@pytest.mark.parametrize("opt_level", [0, 3])
def test_remap_member_batch_interface_and_search(backend, opt_level):
    """The remap program exercises K-interface fields AND the
    ``index_search`` level-search construct under the member axis."""
    cfg = FV3Config(npx=6, nk=8, halo=6, n_tracers=0)
    dom = cfg.seq_dom()
    prog = build_remap_program(cfg, dom, fields=("pt",))
    params = default_params(cfg)
    M = 2
    fields = _member_fields(("delp", "pt"), dom, M)
    single = compile_program(prog, backend, opt_level=opt_level)
    singles = _per_member(single, fields, params, M)
    fn = compile_program(prog, backend, opt_level=opt_level, n_members=M,
                         batch="grid" if backend.startswith("pallas")
                         else "vmap")
    out = fn(dict(fields), params)
    _assert_bit_equal(out, singles, keys=["delp_out", "pt_out"])
    assert fn.n_kernels == single.n_kernels


def test_kblocked_marching_member_grid():
    """K-blocked vertical solver: the member grid axis sits OUTSIDE the
    sequential K-slab grid, and the scratch carry resets at each member's
    first block — no carry leaks between members."""
    cfg = FV3Config(npx=6, nk=16, halo=6, n_tracers=0)
    dom = cfg.seq_dom()
    p = StencilProgram("pe_fwd", dom)
    p.declare("delp")
    p.declare("pe")
    node = p.add(S.precompute_pe, {"delp": "delp", "pe": "pe"})
    p.propagate_extents()
    assert solver_k_blockable(node.stencil)
    sch = Schedule(block_k=4, k_as_grid=False)
    M = 3
    fields = _member_fields(("delp",), dom, M)
    params = {"ptop": 10.0}
    single = compile_program(p, "pallas-tpu",
                             schedule_overrides={"precompute_pe": sch})
    singles = _per_member(single, fields, params, M)
    fn = compile_program(p, "pallas-tpu", n_members=M, batch="grid",
                         schedule_overrides={"precompute_pe": sch})
    out = fn(dict(fields), params)
    _assert_bit_equal(out, singles, keys=["pe"])


def test_grid_kernel_count_independent_of_members():
    """Acceptance: the grid-batched Pallas path dispatches the same
    n_kernels as M=1 — one kernel per fused group, independent of M."""
    cfg = FV3Config(npx=8, nk=4, halo=6)
    p = build_csw_program(cfg, cfg.seq_dom())
    counts = {M: compile_program(p, "pallas-tpu", opt_level=3,
                                 n_members=M, batch="grid").n_kernels
              for M in (1, 4, 8)}
    assert len(set(counts.values())) == 1, counts


def test_batch_mode_validation():
    dom = DomainSpec(ni=8, nj=8, nk=4, halo=6)
    p = _fvt_program(dom)
    with pytest.raises(ValueError, match="batch"):
        compile_program(p, "jnp", n_members=2, batch="pmap")


@pytest.mark.parametrize("bad", [
    "vmap:0", "vmap:-3", "vmap:x", "vmap:2,foo", "grid:2,grid",
    "vmap:2,scan,extra", "",
])
def test_chunk_spec_validation(bad):
    """Malformed chunk specs fail loudly at parse time, never silently
    degrade — and every message names the ``batch`` argument."""
    dom = DomainSpec(ni=8, nj=8, nk=4, halo=6)
    p = _fvt_program(dom)
    with pytest.raises(ValueError, match="batch"):
        compile_program(p, "jnp", n_members=2, batch=bad)


def test_chunk_spec_tokens_round_trip():
    from repro.core import parse_batch

    for s, tok in [("vmap", "vmap"), ("grid", "grid"), ("vmap:4", "vmap:4"),
                   ("vmap:4,scan", "vmap:4"), ("vmap:4,grid", "vmap:4,grid"),
                   ("grid:4", "grid:4"), ("vmap:auto", "vmap:auto")]:
        spec = parse_batch(s)
        assert spec.token == tok
        assert parse_batch(spec.token) == spec


def test_batchspec_typed_fields_and_parse():
    import dataclasses
    from repro.core.backend.batching import BatchSpec

    sp = BatchSpec(mode="vmap", chunk=4, loop="grid")
    assert (sp.mode, sp.chunk, sp.loop) == ("vmap", 4, "grid")
    assert BatchSpec.parse("vmap:4,grid") == sp
    assert BatchSpec.parse(sp) is sp
    assert dataclasses.replace(sp, chunk=8) == BatchSpec("vmap", 8, "grid")
    assert BatchSpec() == BatchSpec(mode="vmap", chunk=0, loop="scan")
    with pytest.raises(ValueError, match="batch"):
        BatchSpec(mode="pmap")
    with pytest.raises(ValueError, match="batch"):
        BatchSpec(mode="grid", chunk=2, loop="grid")


def test_batchspec_legacy_inner_outer_kwargs_deprecated():
    from repro.core.backend.batching import BatchSpec

    with pytest.warns(DeprecationWarning, match="inner"):
        legacy = BatchSpec(inner="vmap", chunk=4)
    with pytest.warns(DeprecationWarning, match="outer"):
        legacy2 = BatchSpec(mode="vmap", chunk=4, outer="grid")
    assert legacy == BatchSpec(mode="vmap", chunk=4)
    assert legacy2 == BatchSpec(mode="vmap", chunk=4, loop="grid")
    # reading the pre-redesign field names stays silent (properties)
    import warnings
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        assert legacy2.inner == "vmap" and legacy2.outer == "grid"


# ---------------------------------------------------------------------------
# Hybrid member chunking: chunked lowering == per-member loop, bit for bit
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("backend,batch", [
    ("jnp", "vmap:2"), ("jnp", "grid:2"),
    ("pallas-tpu", "vmap:2"), ("pallas-tpu", "vmap:2,grid"),
    ("pallas-tpu", "grid:2"),
])
def test_chunked_fvt_matches_member_loop(backend, batch):
    """All three chunked lowerings (program-level scan over vmap chunks,
    scan over member-grid chunks, in-kernel grid chunk loop) are
    bit-identical to the per-member loop — including M=5 not divisible by
    C=2 (replicate-padded last chunk, pad sliced off)."""
    dom = DomainSpec(ni=8, nj=8, nk=4, halo=6)
    p = _fvt_program(dom)
    M = 5
    fields = _member_fields(p.fields, dom, M)
    single = compile_program(p, backend)
    singles = _per_member(single, fields, FVT_PARAMS, M)
    fn = compile_program(p, backend, n_members=M, batch=batch)
    out = fn(dict(fields), FVT_PARAMS)
    _assert_bit_equal(out, singles, keys=["qout"])
    # chunking restructures the launch, never the kernel set
    assert fn.n_kernels == single.n_kernels
    assert fn.member_chunk == 2 and fn.n_chunks == 3


@pytest.mark.parametrize("backend,opt_level", [
    ("jnp", 0), ("jnp", 3), ("pallas-tpu", 0), ("pallas-tpu", 3),
])
def test_chunked_remap_interface_and_search(backend, opt_level):
    """K-interface fields and the ``index_search`` remap under the chunked
    member axis (the hardest lowering: per-chunk carry reset in marching
    kernels, interface extents in C-member blocks)."""
    cfg = FV3Config(npx=6, nk=8, halo=6, n_tracers=0)
    dom = cfg.seq_dom()
    prog = build_remap_program(cfg, dom, fields=("pt",))
    params = default_params(cfg)
    M = 3
    fields = _member_fields(("delp", "pt"), dom, M)
    single = compile_program(prog, backend, opt_level=opt_level)
    singles = _per_member(single, fields, params, M)
    batch = "vmap:2,grid" if backend.startswith("pallas") else "vmap:2"
    fn = compile_program(prog, backend, opt_level=opt_level, n_members=M,
                         batch=batch)
    out = fn(dict(fields), params)
    _assert_bit_equal(out, singles, keys=["delp_out", "pt_out"])
    assert fn.n_kernels == single.n_kernels


def test_chunked_kblocked_marching_carry_reset():
    """K-blocked marching solver with C-member blocks: the scratch carry is
    (C, J, I) and resets at each chunk's first K block — no carry leaks
    between chunks or members."""
    cfg = FV3Config(npx=6, nk=16, halo=6, n_tracers=0)
    dom = cfg.seq_dom()
    p = StencilProgram("pe_fwd_chunk", dom)
    p.declare("delp")
    p.declare("pe")
    node = p.add(S.precompute_pe, {"delp": "delp", "pe": "pe"})
    p.propagate_extents()
    assert solver_k_blockable(node.stencil)
    sch = Schedule(block_k=4, k_as_grid=False)
    M = 4
    fields = _member_fields(("delp",), dom, M)
    params = {"ptop": 10.0}
    single = compile_program(p, "pallas-tpu",
                             schedule_overrides={"precompute_pe": sch})
    singles = _per_member(single, fields, params, M)
    fn = compile_program(p, "pallas-tpu", n_members=M, batch="vmap:2,grid",
                         schedule_overrides={"precompute_pe": sch})
    out = fn(dict(fields), params)
    _assert_bit_equal(out, singles, keys=["pe"])


def test_auto_chunk_resolves_through_cost_model():
    dom = DomainSpec(ni=8, nj=8, nk=4, halo=6)
    p = _fvt_program(dom)
    M = 4
    fields = _member_fields(p.fields, dom, M)
    fn = compile_program(p, "pallas-tpu", n_members=M, batch="vmap:auto")
    out = fn(dict(fields), FVT_PARAMS)
    single = compile_program(p, "pallas-tpu")
    _assert_bit_equal(out, _per_member(single, fields, FVT_PARAMS, M),
                      keys=["qout"])
    # the unresolved sentinel never reaches the backend
    assert fn.batch != "vmap:auto" and fn.batch.startswith("vmap")


def test_chunked_donation_streams_state():
    """``donate=True`` on a chunked program: donation engages exactly when
    the platform honors it (TPU/GPU), degrades to plain jit on CPU — and
    either way the chunked result stays bit-identical."""
    from repro.core import donation_supported

    dom = DomainSpec(ni=8, nj=8, nk=4, halo=6)
    p = _fvt_program(dom)
    M = 4
    fields = _member_fields(p.fields, dom, M)
    plain = compile_program(p, "jnp", n_members=M, batch="vmap:2")
    ref = plain(dict(fields), FVT_PARAMS)
    fn = compile_program(p, "jnp", n_members=M, batch="vmap:2", donate=True)
    assert fn.donated == donation_supported()
    out = fn({k: jnp.array(v) for k, v in fields.items()}, FVT_PARAMS)
    assert np.array_equal(np.asarray(out["qout"]), np.asarray(ref["qout"]))
    if not donation_supported():
        # CPU: inputs must remain readable after the call (plain jit)
        _ = [np.asarray(v) for v in fields.values()]


# ---------------------------------------------------------------------------
# Batched reference halo exchange
# ---------------------------------------------------------------------------


def test_batched_reference_exchange_matches_member_loop():
    N, h, nk, M = 8, 3, 2, 3
    shape = (M, 6, nk, N + 2 * h, N + 2 * h)
    fields = {n: jnp.asarray(RNG.standard_normal(shape), jnp.float32)
              for n in ("q", "u", "v")}
    vec = [("u", "v")]
    batched = exchange_reference(fields, h, vector_pairs=vec)
    for m in range(M):
        single = exchange_reference({k: v[m] for k, v in fields.items()},
                                    h, vector_pairs=vec)
        for k in fields:
            assert np.array_equal(np.asarray(batched[k][m]),
                                  np.asarray(single[k])), (k, m)


# ---------------------------------------------------------------------------
# Full ensemble step — the acceptance criterion
# ---------------------------------------------------------------------------


def _step_cfg():
    return FV3Config(npx=12, nk=2, halo=6, n_split=1, k_split=1,
                     n_tracers=1)


@pytest.mark.parametrize("opt_level", [0, 1, 2, 3])
def test_ensemble_step_bitmatches_member_loop_jnp(opt_level):
    cfg = _step_cfg()
    M = 4
    ens0 = ensemble_state(cfg, M)
    step_e = make_step_ensemble(cfg, M, opt_level=opt_level)
    out_e = step_e(dict(ens0))
    step_s = make_step_sequential(cfg, opt_level=opt_level)
    singles = [step_s({k: v[m] for k, v in ens0.items()}) for m in range(M)]
    _assert_bit_equal(out_e, singles)
    assert step_e.n_kernels == step_s.n_kernels


@pytest.mark.slow
@pytest.mark.parametrize("opt_level", [0, 3])
def test_ensemble_step_bitmatches_member_loop_pallas(opt_level):
    cfg = _step_cfg()
    M = 4
    ens0 = ensemble_state(cfg, M)
    step_e = make_step_ensemble(cfg, M, backend="pallas-tpu",
                                opt_level=opt_level)
    assert step_e.batch == "grid"
    out_e = step_e(dict(ens0))
    step_s = make_step_sequential(cfg, backend="pallas-tpu",
                                  opt_level=opt_level)
    singles = [step_s({k: v[m] for k, v in ens0.items()}) for m in range(M)]
    _assert_bit_equal(out_e, singles)
    # one pallas_call per fused group regardless of M
    assert step_e.n_kernels == step_s.n_kernels


@pytest.mark.parametrize("opt_level", [0, 3])
def test_chunked_ensemble_step_bitmatches_jnp(opt_level):
    """Step-level chunking: the whole step (halo exchanges, acoustic scan,
    remap) runs chunk by chunk, M=3 not divisible by C=2 — bit-identical to
    the per-member loop."""
    cfg = _step_cfg()
    M = 3
    ens0 = ensemble_state(cfg, M)
    step_e = make_step_ensemble(cfg, M, batch="vmap:2", opt_level=opt_level)
    assert step_e.member_chunk == 2 and step_e.n_chunks == 2
    out_e = step_e(dict(ens0))
    step_s = make_step_sequential(cfg, opt_level=opt_level)
    singles = [step_s({k: v[m] for k, v in ens0.items()}) for m in range(M)]
    _assert_bit_equal(out_e, singles)
    assert step_e.n_kernels == step_s.n_kernels


@pytest.mark.slow
def test_chunked_ensemble_step_bitmatches_pallas():
    """The hybrid in-kernel chunk loop (``"vmap:2,grid"``) through the full
    Pallas ensemble step."""
    cfg = _step_cfg()
    M = 4
    ens0 = ensemble_state(cfg, M)
    step_e = make_step_ensemble(cfg, M, backend="pallas-tpu",
                                batch="vmap:2,grid", opt_level=3)
    assert step_e.batch == "vmap:2,grid" and step_e.member_chunk == 2
    out_e = step_e(dict(ens0))
    step_s = make_step_sequential(cfg, backend="pallas-tpu", opt_level=3)
    singles = [step_s({k: v[m] for k, v in ens0.items()}) for m in range(M)]
    _assert_bit_equal(out_e, singles)
    assert step_e.n_kernels == step_s.n_kernels


@pytest.mark.slow
def test_chunked_member_sharded_matches_unsharded():
    """Composition: M=4 members shard over a 2-group member mesh axis AND
    chunk-batch (C=1) within each group — every member bit-matches the
    unsharded sequential step (subprocess with fake devices, same idiom as
    test_distributed)."""
    import os
    import subprocess
    import sys
    from pathlib import Path

    root = Path(__file__).resolve().parents[1]
    code = """
import numpy as np, jax
from repro.fv3.dyncore import FV3Config, make_step_sequential, make_step_distributed
from repro.fv3.state import ensemble_state, blocks_from_global, global_from_blocks
cfg = FV3Config(npx=12, nk=2, halo=6, layout=(1, 1), n_split=1, k_split=1,
                n_tracers=1)
M, D = 4, 2
ens0 = ensemble_state(cfg, M)
mesh = jax.make_mesh((D, 6, 1, 1), ("member", "tile", "y", "x"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 4)
blocks = {}
for m in range(M):
    bm = blocks_from_global({k: v[m] for k, v in ens0.items()}, cfg)
    for k, v in bm.items():
        blocks.setdefault(k, []).append(np.asarray(v))
blocks = {k: jax.numpy.asarray(np.stack(v)) for k, v in blocks.items()}
step = make_step_distributed(cfg, mesh, member_axis="member", n_members=M,
                             batch="vmap:1")
assert step.members_per_group == 2
out_b = step(blocks)
step_s = make_step_sequential(cfg)
h, N = cfg.halo, cfg.npx
I = np.s_[:, :, h:h+N, h:h+N]
for m in range(M):
    ref = step_s({k: v[m] for k, v in ens0.items()})
    got = global_from_blocks({k: np.asarray(v[m]) for k, v in out_b.items()}, cfg)
    for k in got:
        err = np.abs(np.asarray(ref[k])[I] - got[k][I]).max()
        assert err < 1e-5, (m, k, err)
print("CHUNK_SHARD_OK")
"""
    env = {**os.environ,
           "PYTHONPATH": str(root / "src"),
           "XLA_FLAGS": "--xla_force_host_platform_device_count=12"}
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=900, env=env)
    assert r.returncode == 0, f"stderr:\n{r.stderr[-3000:]}"
    assert "CHUNK_SHARD_OK" in r.stdout


def test_distributed_member_batch_validation():
    """Misconfigured sharded-ensemble requests fail before any compile:
    ``n_members`` without a member mesh axis, and M not a multiple of the
    member-axis extent."""
    import types

    cfg = _step_cfg()
    with pytest.raises(ValueError, match="member_axis"):
        make_step_distributed(cfg, None, n_members=4)
    fake_mesh = types.SimpleNamespace(shape={"member": 3})
    with pytest.raises(ValueError, match="multiple"):
        make_step_distributed(cfg, fake_mesh, member_axis="member",
                              n_members=4)


def test_ensemble_state_layout():
    cfg = _step_cfg()
    M = 3
    ens = ensemble_state(cfg, M)
    base = init_state(cfg)
    h, N = cfg.halo, cfg.npx
    for k, v in ens.items():
        assert v.shape == (M,) + base[k].shape
        # member 0 is the unperturbed control
        assert np.array_equal(np.asarray(v[0]), np.asarray(base[k]))
    # perturbations live in the pt/delp interior only
    assert not np.array_equal(np.asarray(ens["pt"][1]),
                              np.asarray(base["pt"]))
    halo_ring = np.asarray(ens["pt"][1])[:, :, :h, :]
    assert np.array_equal(halo_ring, np.asarray(base["pt"])[:, :, :h, :])
    assert np.array_equal(np.asarray(ens["u"][1]), np.asarray(base["u"]))


# ---------------------------------------------------------------------------
# Cost model + tuning cache
# ---------------------------------------------------------------------------


def test_model_cost_amortizes_launch_overhead():
    dom = DomainSpec(ni=8, nj=8, nk=4, halo=6)
    p = _fvt_program(dom)
    st = p.all_nodes()[0].stencil
    sched = Schedule(block_k=1, k_as_grid=True)
    c1 = model_cost(st, sched, dom)
    c8 = model_cost(st, sched, dom, n_members=8)
    # data scales with M, the per-call launch overhead does not: strictly
    # cheaper than eight independent launches, strictly more than one member
    assert c1 < c8 < 8 * c1


def test_model_cost_prices_member_chunk():
    """Chunk pricing: C-wide chunks walk ceil(M/C) sequential steps instead
    of M (cheaper launch pipeline), but the VMEM feasibility check scales by
    C — an infeasibly wide chunk prices to infinity."""
    from repro.core.hardware import get_hardware
    from repro.core.stencil.schedule import vmem_footprint

    dom = DomainSpec(ni=8, nj=8, nk=4, halo=6)
    st = _fvt_program(dom).all_nodes()[0].stencil
    sched = Schedule(block_k=1, k_as_grid=True)
    M = 8
    c_grid = model_cost(st, sched, dom, n_members=M)
    c_c4 = model_cost(st, sched, dom, n_members=M, member_chunk=4)
    assert c_c4 < c_grid  # 2 chunk steps vs 8 member steps
    # member_chunk=0 is exactly the pre-chunk model
    assert model_cost(st, sched, dom, n_members=M, member_chunk=0) == c_grid
    # footprint scales linearly with C ...
    hw = get_hardware("p100")  # 48 KiB shared memory
    f1 = vmem_footprint(st, sched, dom, hw=hw)
    f4 = vmem_footprint(st, sched, dom, member_chunk=4, hw=hw)
    assert f4 == 4 * f1
    # ... and a chunk wider than VMEM is infeasible (M large enough that
    # the chunk is genuine — the model clamps C to M like chunk_for does)
    too_wide = 2 * (hw.vmem_bytes // f1 + 1)
    assert model_cost(st, sched, dom, hw, n_members=2 * too_wide,
                      member_chunk=too_wide) == float("inf")


def test_tuning_cache_keys_carry_member_chunk(tmp_path):
    from repro.core.backend.cache import TuningCache

    cache = TuningCache(tmp_path / "c.json")
    dom = DomainSpec(ni=8, nj=8, nk=4, halo=6)
    st = _fvt_program(dom).all_nodes()[0].stencil
    r0 = tune_stencil(st, dom, backend="pallas-tpu", n_members=8,
                      cache=cache)
    assert not r0[0].from_cache
    r4 = tune_stencil(st, dom, backend="pallas-tpu", n_members=8,
                      member_chunk=4, cache=cache)
    assert not r4[0].from_cache  # chunk is part of the key
    r4b = tune_stencil(st, dom, backend="pallas-tpu", n_members=8,
                       member_chunk=4, cache=cache)
    assert r4b[0].from_cache


def test_tune_member_chunk_cached(tmp_path):
    from repro.core import tune_member_chunk
    from repro.core.backend.cache import TuningCache

    cache = TuningCache(tmp_path / "c.json")
    dom = DomainSpec(ni=8, nj=8, nk=4, halo=6)
    st = _fvt_program(dom).all_nodes()[0].stencil
    c = tune_member_chunk(st, dom, backend="pallas-tpu", n_members=8,
                          cache=cache)
    assert 1 <= c <= 8
    puts = cache.stats.puts
    c2 = tune_member_chunk(st, dom, backend="pallas-tpu", n_members=8,
                           cache=cache)
    assert c2 == c and cache.stats.puts == puts  # served from cache


def test_tuning_cache_keys_carry_n_members(tmp_path):
    from repro.core.backend.cache import TuningCache

    cache = TuningCache(tmp_path / "t.json")
    dom = DomainSpec(ni=8, nj=8, nk=4, halo=6)
    st = _fvt_program(dom).all_nodes()[0].stencil
    r1 = tune_stencil(st, dom, backend="pallas-tpu", cache=cache)
    assert not r1[0].from_cache
    r4 = tune_stencil(st, dom, backend="pallas-tpu", n_members=4,
                      cache=cache)
    assert not r4[0].from_cache  # different key — no stale M=1 result
    r4b = tune_stencil(st, dom, backend="pallas-tpu", n_members=4,
                       cache=cache)
    assert r4b[0].from_cache
    assert r4b[0].schedule == r4[0].schedule
