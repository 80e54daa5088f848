"""Columns no K block divides: NOAA GFS v16's 127 levels.

Every K-blocked Pallas schedule has a ragged last block where ``block_k``
does not divide nk.  Checked here on the CPU (kernels in interpret mode):

 * the whole opt-3 Pallas step at a ragged depth, with schedules forced to
   ragged K slabs and marches, against the benchmark's plain reference
   (``bench/references/fv3lite.py``) under the cell's limits;
 * ``step.k_slabs``, the counter of the kernels that walk K in blocks;
 * the tuned schedules at C128 (no compile): every node fits VMEM at L127
   with no one-level slab where L80 uses taller ones, and the schedules at
   L80 and L64 (four members on the grid) are those recorded in
   ``tests/data/c128_schedules.json`` before blocks could be ragged.
"""

import json
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from repro.core import compile_program
from repro.core.backend import get_backend
from repro.core.hardware import Hardware, TPU_V5E
from repro.core.stencil.schedule import k_block, vmem_footprint
from repro.fv3.dyncore import (FV3Config, _build_programs,
                               make_step_sequential)

ROOT = Path(__file__).resolve().parents[1]
CELL = "c128_l127.nsplit6"
#: small enough that at npx 12 x 13 levels whole columns do not fit: the
#: tuner picks K slabs of 4 and 8 levels (last 1 and 5) and marches d_sw's
#: precompute_pe in blocks of 8 (last 5)
SMALL_VMEM = Hardware("test-800kib-vmem", peak_flops=1e12, hbm_bw=1e11,
                      link_bw=0, vmem_bytes=800 * 1024, kind="tpu")
NPX, NK = 12, 13


def _bench():
    """The benchmark's data readers, comparison and plain reference."""
    if str(ROOT / "bench") not in sys.path:
        sys.path.append(str(ROOT / "bench"))
    import check
    import spec
    import system
    import traffic
    from references import fv3lite
    return spec, check, system, traffic, fv3lite


@pytest.fixture(scope="module")
def small_step():
    spec, check, system, traffic, fv3lite = _bench()
    bench = spec.benchmark()
    cell = spec.cell(bench, CELL)
    cfg = dict(spec.config(bench, cell["config"]), npx=NPX, nk=NK)
    tr = spec.traffic(cell["traffic"])
    fcfg = system.fv3_config(cfg, tr)
    step = make_step_sequential(fcfg, backend=cfg["backend"],
                                opt_level=cfg["opt_level"],
                                hardware=SMALL_VMEM)
    return cfg, tr, spec.limits(CELL), step


def test_k_slabs_counts_ragged_blocks(small_step):
    _, _, _, step = small_step
    ks = step.k_slabs
    assert set(ks) == {p.name for p in step.programs}
    heights = set()
    for prog, entry in ks.items():
        assert entry["kernels"] == len(entry["blocks"])
        for label, b in entry["blocks"].items():
            bk, n, last = b["block_k"], b["n_blocks"], b["last"]
            assert 0 < bk < NK, label
            assert n == -(-NK // bk), label
            assert 0 < last <= bk and (n - 1) * bk + last == NK, label
            heights.add((bk, last))
    assert {(8, 5), (4, 1)} <= heights
    assert ks["d_sw"]["blocks"]["precompute_pe#4"] == {
        "block_k": 8, "n_blocks": 2, "last": 5}
    # the remap's kernels read whole columns (interface fields, searches)
    assert ks["vertical_remap"] == {"kernels": 0, "blocks": {}}


def test_ragged_step_matches_reference(small_step):
    cfg, tr, limits, step = small_step
    spec, check, system, traffic, fv3lite = _bench()
    n = limits["steps_compared"]
    halo = cfg["halo"]
    init = traffic.initial_state(cfg, tr, 2147483731)
    state = system.program_state(
        cfg, {k: jnp.copy(v) for k, v in init.items()})
    got = []
    for _ in range(n):
        state = step(state)
        got.append(check.host_interiors(jax, state, halo, False))
    ref_step = jax.jit(fv3lite.make_step(cfg, tr["namelist"],
                                         tuple(cfg["tracers"])))
    with jax.default_matmul_precision("highest"):
        refs = check.reference_states(jax, ref_step, init, n, halo)
    for i in range(n):
        err, where = check.worst_error(got[i], refs[i], 0)
        limit = limits["numbers"][f"rms_err.step{i + 1}"]["limit"]
        assert err <= limit, (i + 1, where, err, limit)


def _tuned(nk: int, members: int = 1) -> dict:
    """{program/node label: (stencil, schedule, dom)} as the C128 step
    compiles them for the v5e at opt level 3."""
    cfg = FV3Config(npx=128, nk=nk, halo=6, n_split=6, k_split=2)
    be = get_backend("pallas-tpu")
    kw = {"n_members": members, "batch": "grid"} if members > 1 else {}
    out = {}
    for p in _build_programs(cfg, cfg.seq_dom()):
        prog = compile_program(p, be, hardware=TPU_V5E, opt_level=3,
                               **kw).program
        for s in prog.states:
            for n in s.nodes:
                dom = prog.node_dom(n)
                sched = n.schedule or be.default_schedule(n.stencil, dom,
                                                          TPU_V5E)
                out[f"{p.name}/{n.label}"] = (n.stencil, sched, dom)
    return out


def test_c128_l127_schedules_fit_vmem_without_one_level_slabs():
    deep, l80 = _tuned(127), _tuned(80)
    for label, (st, sched, dom) in deep.items():
        assert vmem_footprint(st, sched, dom, hw=TPU_V5E) \
            <= TPU_V5E.vmem_bytes, (label, sched.describe())
    # d_sw's solver marches ragged blocks instead of an unfitting column
    st, sched, dom = deep["d_sw/precompute_pe#4"]
    assert k_block(st, sched, 127) == 16
    for label, (st, sched, dom) in l80.items():
        if label in deep and not st.is_vertical_solver() \
                and k_block(st, sched, 80) >= 4:
            st2, sched2, _ = deep[label]
            assert k_block(st2, sched2, 127) >= 4, (label,
                                                    sched2.describe())


@pytest.mark.parametrize("nk,members", [(80, 1), (64, 4)])
def test_c128_schedules_unchanged(nk, members):
    want = json.loads((ROOT / "tests" / "data" / "c128_schedules.json")
                      .read_text())[f"L{nk}_M{members}"]
    got = {k: sched.describe()
           for k, (_, sched, _) in _tuned(nk, members).items()}
    assert got == want
