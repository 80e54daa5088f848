"""Backend subsystem tests: registry resolution, hardware-parameterized
schedule rules, jnp-vs-pallas equivalence through ``compile_program`` (incl.
the FV3 acoustic-step round-trip), and persistent tuning-cache behavior."""

import numpy as np
import pytest
import jax.numpy as jnp

from repro.core import StencilProgram
from repro.core.backend import (
    Backend,
    TuningCache,
    available_backends,
    compile_program,
    compile_stencil,
    get_backend,
    stencil_fingerprint,
)
from repro.core.hardware import P100, TPU_V5E, get_hardware, resolve_hardware
from repro.core.autotune import tune_stencil
from repro.core.stencil import DomainSpec, Field, Param, Schedule, gtstencil
from repro.core.stencil.schedule import feasible_schedules, vmem_footprint
from repro.core.transfer_tuning import tune_cutouts
from repro.fv3 import stencils as S
from repro.fv3.dyncore import FV3Config, build_csw_program, default_params


# ---------------------------------------------------------------------------
# registry resolution
# ---------------------------------------------------------------------------


def test_registry_contains_builtin_backends():
    assert {"jnp", "pallas-tpu", "pallas-gpu"} <= set(available_backends())


def test_get_backend_resolves_names_aliases_and_instances():
    be = get_backend("pallas-tpu")
    assert be.name == "pallas-tpu"
    assert get_backend("pallas").name == "pallas-tpu"  # legacy spelling
    assert get_backend(be) is be
    assert isinstance(be, Backend)


def test_unknown_backend_lists_alternatives():
    with pytest.raises(KeyError, match="pallas-tpu"):
        get_backend("no-such-target")


def test_hardware_registry():
    assert get_hardware("tpu-v5e") is TPU_V5E
    assert resolve_hardware(None) is TPU_V5E
    assert resolve_hardware("p100").kind == "gpu"
    assert get_backend("pallas-gpu").resolve_hw(None) is P100
    with pytest.raises(KeyError, match="tpu-v5e"):
        get_hardware("abacus")


# ---------------------------------------------------------------------------
# hardware-parameterized schedule rules
# ---------------------------------------------------------------------------


@gtstencil
def _lap(q: Field, out: Field):
    with computation(PARALLEL), interval(...):
        out = q[-1, 0, 0] + q[1, 0, 0] + q[0, -1, 0] + q[0, 1, 0] \
            - 4.0 * q[0, 0, 0]


def test_feasible_schedules_tpu_vs_gpu_rules():
    shape = DomainSpec(ni=512, nj=256, nk=16, halo=1)
    tpu = list(feasible_schedules(_lap, shape, hw=TPU_V5E))
    gpu = list(feasible_schedules(_lap, shape, hw=P100))
    assert tpu and gpu
    # TPU tiles align to (lane=128, sublane=8); whole-extent (0) is allowed
    assert all(s.block_i % 128 == 0 for s in tpu)
    assert all(s.block_j % 8 == 0 for s in tpu)
    assert any(s.block_i == 0 for s in tpu)
    # GPU tiles are warp multiples and must fit shared memory — the
    # whole-domain blocks TPU VMEM accommodates are infeasible on 48 KiB
    assert all(s.block_i % 32 == 0 and s.block_i > 0 for s in gpu)
    assert all(
        vmem_footprint(_lap, s, shape, hw=P100) <= P100.vmem_bytes
        for s in gpu)
    assert not any(s.block_i == 0 for s in gpu)
    assert {s.to_dict()["block_i"] for s in gpu} != \
        {s.to_dict()["block_i"] for s in tpu}


def test_backend_heuristic_schedules_differ_by_hardware():
    shape = DomainSpec(ni=128, nj=128, nk=16, halo=1)
    tpu_sched = get_backend("pallas-tpu").heuristic_schedule(_lap, shape)
    gpu_sched = get_backend("pallas-gpu").heuristic_schedule(_lap, shape)
    assert tpu_sched.block_i == 0          # full IJ for halo reuse in VMEM
    assert gpu_sched.block_i % 32 == 0 and gpu_sched.block_i > 0
    assert vmem_footprint(_lap, gpu_sched, shape, hw=P100) <= P100.vmem_bytes


@gtstencil
def _koff(q: Field, out: Field):
    with computation(PARALLEL), interval(0, -1):
        out = 0.5 * (q[0, 0, 0] + q[0, 0, 1])


def test_gpu_schedules_exist_for_k_offset_stencils():
    """K-offset stencils need whole-K blocks; the GPU rules must still
    enumerate (small IJ tiles, block_k=0), not come up empty."""
    shape = DomainSpec(ni=64, nj=64, nk=16, halo=2)
    gpu = list(feasible_schedules(_koff, shape, hw=P100))
    assert gpu, "GPU enumeration empty for k-offset stencil"
    assert all(s.block_k == 0 for s in gpu)
    tuned = tune_stencil(_koff, DomainSpec(ni=64, nj=64, nk=16, halo=2),
                         hw="p100", cache=None)
    assert tuned and tuned[0].cost != float("inf")


# ---------------------------------------------------------------------------
# numerical equivalence through compile_program
# ---------------------------------------------------------------------------


def _lap_program():
    dom = DomainSpec(ni=8, nj=6, nk=4, halo=2)
    p = StencilProgram("lap2", dom)
    p.declare("q")
    p.declare("out")
    p.declare("mid", transient=True)
    p.add(_lap, {"q": "q", "out": "mid"})
    p.add(_lap, {"q": "mid", "out": "out"})
    p.propagate_extents()
    return p, dom


@pytest.mark.parametrize("backend", ["pallas-tpu", "pallas-gpu"])
def test_compile_program_backends_match_jnp(backend):
    p, dom = _lap_program()
    rng = np.random.default_rng(0)
    fields = {f: jnp.asarray(rng.uniform(0.5, 1.5, dom.padded_shape()),
                             jnp.float32) for f in ("q", "out")}
    ref = compile_program(p, "jnp")(dict(fields))
    got = compile_program(p, backend)(dict(fields))
    np.testing.assert_allclose(np.asarray(ref["out"]), np.asarray(got["out"]),
                               rtol=1e-5, atol=1e-5)


def test_compile_program_schedule_overrides():
    p, dom = _lap_program()
    rng = np.random.default_rng(1)
    fields = {f: jnp.asarray(rng.uniform(0.5, 1.5, dom.padded_shape()),
                             jnp.float32) for f in ("q", "out")}
    ref = compile_program(p, "jnp")(dict(fields))
    got = compile_program(
        p, "pallas-tpu",
        schedule_overrides={"_lap": Schedule(block_k=2)})(dict(fields))
    np.testing.assert_allclose(np.asarray(ref["out"]), np.asarray(got["out"]),
                               rtol=1e-5, atol=1e-5)


def test_fv3_acoustic_step_roundtrips_jnp_vs_pallas():
    """Acceptance: the c_sw + riem_solver_c acoustic-step program (regions,
    K offsets, a tridiagonal vertical solver) produces identical results on
    the jnp and pallas-tpu (interpret) backends via compile_program."""
    cfg = FV3Config(npx=8, nk=4, halo=6, n_split=1, k_split=1)
    dom = cfg.seq_dom()
    p = build_csw_program(cfg, dom)
    params = default_params(cfg)
    rng = np.random.default_rng(2)
    fields = {f: jnp.asarray(rng.uniform(0.8, 1.2, dom.padded_shape()),
                             jnp.float32)
              for f in ("u", "v", "delp", "pt", "w", "cosa", "sina")}
    ref = compile_program(p, "jnp")(dict(fields), params)
    got = compile_program(p, "pallas-tpu")(dict(fields), params)
    for k in ("w", "delpc", "ptc"):
        np.testing.assert_allclose(np.asarray(ref[k]), np.asarray(got[k]),
                                   rtol=2e-5, atol=2e-5, err_msg=k)


# ---------------------------------------------------------------------------
# persistent tuning cache
# ---------------------------------------------------------------------------


def test_tune_stencil_hits_persistent_cache(tmp_path):
    dom = DomainSpec(ni=64, nj=64, nk=8, halo=2)
    cache = TuningCache(tmp_path / "tune.json")
    first = tune_stencil(_lap, dom, cache=cache, top_m=2)
    assert cache.stats.misses == 1 and cache.stats.hits == 0
    assert not first[0].from_cache

    second = tune_stencil(_lap, dom, cache=cache, top_m=2)
    assert cache.stats.hits == 1
    assert second[0].from_cache
    assert second[0].schedule == first[0].schedule
    assert second[0].cost == first[0].cost

    # a fresh cache object on the same path (≈ new process) still hits
    reloaded = TuningCache(tmp_path / "tune.json")
    third = tune_stencil(_lap, dom, cache=reloaded, top_m=2)
    assert reloaded.stats.hits == 1 and reloaded.stats.misses == 0
    assert third[0].schedule == first[0].schedule


def test_tune_stencil_cache_keys_on_hardware(tmp_path):
    dom = DomainSpec(ni=64, nj=64, nk=8, halo=2)
    cache = TuningCache(tmp_path / "tune.json")
    tpu = tune_stencil(_lap, dom, hw="tpu-v5e", cache=cache)
    gpu = tune_stencil(_lap, dom, hw="p100", cache=cache)
    assert cache.stats.misses == 2 and cache.stats.hits == 0
    assert gpu[0].schedule != tpu[0].schedule  # GPU rules pick warp tiles


def test_tune_cutouts_hits_persistent_cache(tmp_path):
    dom = DomainSpec(ni=12, nj=12, nk=4, halo=6)
    p = StencilProgram("fvt_cutout", dom)
    for f in ("q", "u", "qout"):
        p.declare(f)
    for f in ("al", "fx"):
        p.declare(f, transient=True)
    p.add(S.al_x, {"q": "q", "al": "al"})
    p.add(S.fx_ppm, {"q": "q", "al": "al", "cx": "u", "fx": "fx"})
    p.add(S.inner_x_update, {"q": "q", "fx": "fx", "qx": "qout"})
    p.propagate_extents()

    cache = TuningCache(tmp_path / "cutouts.json")
    first = tune_cutouts(p, kind="otf", top_m=2, cache=cache)
    assert cache.stats.misses == 1
    assert not first.from_cache and first.n_configs > 0

    second = tune_cutouts(p, kind="otf", top_m=2, cache=cache)
    assert cache.stats.hits == 1
    assert second.from_cache
    assert second.n_configs == first.n_configs
    assert [pt.to_dict() for pt in second.patterns] == \
        [pt.to_dict() for pt in first.patterns]

    # different transformation kind → different key
    tune_cutouts(p, kind="sgf", top_m=1, cache=cache)
    assert cache.stats.misses == 2


def test_stencil_fingerprint_is_content_addressed():
    assert stencil_fingerprint(_lap) == stencil_fingerprint(_lap)
    assert stencil_fingerprint(_lap) != stencil_fingerprint(S.al_x)


# ---------------------------------------------------------------------------
# in-process compile memo + donation gating
# ---------------------------------------------------------------------------


def test_clear_compile_cache_resets_stats():
    """Regression: clearing the runner memo must also reset the hit/miss
    counters, or benchmark harnesses report stale numbers across runs."""
    from repro.core.backend import clear_compile_cache
    from repro.core.backend.compile import compile_cache_stats

    dom = DomainSpec(ni=8, nj=8, nk=2, halo=2)
    clear_compile_cache()
    assert compile_cache_stats() == {"hits": 0, "misses": 0, "puts": 0}
    compile_stencil(_lap, dom, backend="jnp")
    compile_stencil(_lap, dom, backend="jnp")
    stats = compile_cache_stats()
    assert stats["misses"] == 1 and stats["hits"] == 1
    clear_compile_cache()
    assert compile_cache_stats() == {"hits": 0, "misses": 0, "puts": 0}
    # memo was dropped too: the next compile is a miss, not a hit
    compile_stencil(_lap, dom, backend="jnp")
    assert compile_cache_stats()["misses"] == 1


def test_donation_gated_on_platform():
    """``donate=True`` must not request donation on platforms where XLA
    ignores it (the sequential CPU path) — the flag degrades to plain jit."""
    import jax
    from repro.core.backend import donation_supported

    assert donation_supported() == (jax.default_backend() in ("gpu", "tpu"))
    p, dom = _lap_program()
    rng = np.random.default_rng(3)
    fields = {f: jnp.asarray(rng.uniform(0.5, 1.5, dom.padded_shape()),
                             jnp.float32) for f in ("q", "out")}
    fn = compile_program(p, "jnp", donate=True)
    assert fn.donated == donation_supported()
    ref = compile_program(p, "jnp")(dict(fields))
    got = fn(dict(fields))
    np.testing.assert_allclose(np.asarray(ref["out"]), np.asarray(got["out"]),
                               rtol=1e-6)
