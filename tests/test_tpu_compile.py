"""Ahead-of-time compiles of dycore kernels for a TPU v5e, no chip attached.

Interpret mode hides what the TPU's kernel compiler (Mosaic) refuses: loads
from memory it cannot read directly, dynamic slices of loaded values, and
blocks larger than the scoped VMEM a kernel may use.  These tests compile
kernels at the production width — a C128 tile padded by the halo to
140 × 140 points, 80 levels, and GFS v16's 127, which no K block divides —
for a described ``v5e`` topology, the way
the dycore does: through ``jit_program``, with the descriptor's kernel
options (XLA's VMEM memory-space assignment off, so every kernel holds
its own blocks) and its scoped-VMEM limit.  ``vmem_footprint`` counts
those blocks, and the schedule tuner keeps them within ``vmem_bytes``;
what the compiler reports beyond them is its own scratch for a
statement's values, which must fit the headroom the limit leaves.

The topology is described inside a module fixture (never at import): only
one process at a time may load the TPU compiler's library.
"""

import dataclasses
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import TPU_V5E, compile_program, compile_stencil
from repro.core.autotune import tune_stencil
from repro.core.backend import compile as compile_mod
from repro.core.backend import jit_program
from repro.core.stencil import DomainSpec
from repro.core.stencil.schedule import default_schedule, vmem_footprint
from repro.fv3 import stencils as S
from repro.fv3.dyncore import FV3Config, build_remap_program

#: one C128 tile at production depth: 128 + 2 * 6 = 140 points per side
DOM = DomainSpec(ni=128, nj=128, nk=80, halo=6)
#: production depths: the paper's 80 levels, and GFS v16's 127, where the
#: last K slab or marched block is ragged
DEPTHS = [80, 127]


def _dom(nk: int) -> DomainSpec:
    return dataclasses.replace(DOM, nk=nk)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler library on this machine
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def native(monkeypatch):
    """Lower Pallas kernels for the chip, not for the interpreter, and jit
    them with the v5e's kernel options, as on an attached v5e."""
    monkeypatch.setattr(compile_mod, "pallas_interpret", lambda: False)
    monkeypatch.setattr(compile_mod, "detect_hardware", lambda: TPU_V5E)


def _specs(stencil_or_program, sharding, lead=(), dom=DOM):
    fields = {f: jax.ShapeDtypeStruct(
        lead + dom.padded_shape(stencil_or_program.is_interface(f)),
        jnp.float32, sharding=sharding)
        for f in stencil_or_program.fields}
    params = {p: jax.ShapeDtypeStruct((), jnp.float32, sharding=sharding)
              for p in stencil_or_program.params}
    return fields, params


def _compile(stencil, sched, sharding, n_members=None, dom=DOM) -> str:
    """Compiled HLO text of ``stencil`` under ``sched`` for one chip."""
    run = compile_stencil(stencil, dom, backend="pallas-tpu", schedule=sched,
                          hardware=TPU_V5E, memoize=False,
                          n_members=n_members, batch="grid")
    lead = (n_members,) if n_members else ()
    return jit_program(run, "pallas-tpu").lower(
        *_specs(stencil, sharding, lead, dom)).compile().as_text()


def _kernel_vmem(text: str) -> list[int]:
    """Scoped VMEM bytes the compiler reports using, per Mosaic kernel of a
    compiled HLO text (``used_scoped_memory_configs`` of each call)."""
    return [int(m.group(1)) for line in text.splitlines()
            if 'custom_call_target="tpu_custom_call"' in line
            for m in [re.search(r'"used_scoped_memory_configs":\[\{[^}]*'
                                r'"size":"(\d+)"', line)] if m]


def _check(stencil, sched, sharding, n_members=None, dom=DOM) -> str:
    """Compile as the dycore does, then hold the model to the compiler:
    the modelled blocks fit the tuner's budget, and what the compiler
    reports beyond them (its scratch for a statement's values) fits the
    headroom between that budget and the kernel's scoped-VMEM limit."""
    text = _compile(stencil, sched, sharding, n_members, dom)
    # the kernel options took: XLA placed no array in VMEM (memory space 1)
    assert "S(1)" not in text
    used = _kernel_vmem(text)
    assert used, "no Mosaic kernel in the compiled text"
    fp = vmem_footprint(stencil, sched, dom, hw=TPU_V5E)
    assert fp <= TPU_V5E.vmem_bytes
    headroom = TPU_V5E.vmem_limit_bytes - TPU_V5E.vmem_bytes
    assert all(u - fp <= headroom for u in used), (used, fp)
    return text


@pytest.mark.parametrize("nk", DEPTHS)
def test_horizontal_ppm_kernel_compiles(one_chip, native, nk):
    """fx_ppm: halo reads in I, scalar-free, K slabs on the grid (at 127
    levels the last slab is a partial edge block)."""
    dom = _dom(nk)
    sched = default_schedule(S.fx_ppm, dom)
    assert sched.block_k and sched.block_k < nk
    assert (nk % sched.block_k != 0) == (nk == 127)
    _check(S.fx_ppm, sched, one_chip, dom=dom)


@pytest.mark.parametrize("nk", DEPTHS)
@pytest.mark.parametrize("stencil,tiling", [
    (S.tridiag_solve, "block_j"),   # two-direction solver: J tiles
    (S.precompute_pe, "block_k"),   # one-direction march: K blocks
])
def test_vertical_solver_tuned_schedule_compiles(one_chip, native, stencil,
                                                 tiling, nk):
    """Whole 80- or 127-level columns of a 140 x 140 tile exceed VMEM; the
    tuner picks a J-tiled or K-blocked schedule whose blocks fit, and the
    kernel compiles under it (SMEM scalars, the K-blocked carry scratch;
    at 127 levels a ragged bottom block whose march has a traced trip
    count)."""
    dom = _dom(nk)
    sched = tune_stencil(stencil, dom, backend="pallas-tpu",
                         hw=TPU_V5E)[0].schedule
    assert getattr(sched, tiling) != 0, sched
    if tiling == "block_k":
        assert (nk % sched.block_k != 0) == (nk == 127)
    _check(stencil, sched, one_chip, dom=dom)


def test_remap_index_search_compiles(one_chip, native):
    """interface_interp: the band-limited level search — the coordinate
    column's per-level extremes and each row block's target extremes
    reduced to scalar loop bounds inside the kernel, then one march per
    block over its band, reading each level from the ref at a traced
    index."""
    sched = default_schedule(S.interface_interp, DOM)
    _check(S.interface_interp, sched, one_chip)


def test_member_grid_kernel_compiles(one_chip, native):
    """Two ensemble members on the outermost grid axis (batch="grid"): one
    kernel, with the same blocks as one member, so the same footprint must
    suffice."""
    sched = default_schedule(S.fx_ppm, DOM)
    text = _check(S.fx_ppm, sched, one_chip, n_members=2)
    assert len(_kernel_vmem(text)) == 1


def test_remap_program_needs_the_raised_limit(one_chip, native):
    """The opt-3 vertical remap at C128 x 80, compiled as the dycore
    compiles it (``compile_program``, then ``jit_program``, which also
    proves the compile path takes the kernel options): its fused
    whole-column kernels hold a statement's values beside their blocks,
    more than the compiler's default 16 MiB of scoped VMEM, so they compile
    under the descriptor's raised limit and not without it."""
    cfg = FV3Config(npx=DOM.ni, nk=DOM.nk)
    prog = build_remap_program(cfg, DOM)

    def lower(hw):
        run = compile_program(prog, "pallas-tpu", hardware=hw, opt_level=3)
        fields = {f: jax.ShapeDtypeStruct(
            DOM.padded_shape(prog.fields[f].interface), jnp.float32,
            sharding=one_chip) for f in run.input_fields}
        params = {p: jax.ShapeDtypeStruct((), jnp.float32, sharding=one_chip)
                  for n in run.program.all_nodes() for p in n.stencil.params}
        return jit_program(run, "pallas-tpu").lower(fields, params)

    text = lower(TPU_V5E).compile().as_text()
    assert "S(1)" not in text
    used = _kernel_vmem(text)
    assert TPU_V5E.vmem_bytes < max(used) <= TPU_V5E.vmem_limit_bytes
    default = dataclasses.replace(TPU_V5E, name="tpu-v5e-default-limit",
                                  vmem_limit_bytes=0)
    with pytest.raises(jax.errors.JaxRuntimeError, match="scoped vmem"):
        lower(default).compile()


@pytest.mark.parametrize("members", [1, 2])
def test_step_ops_name_their_layers_and_kernels(one_chip, native, members):
    """A whole Pallas step (C12, opt 3) compiled for the chip: every op
    falls under one layer scope and, inside a program, one stencil node,
    and each Mosaic kernel's instruction is named after the node's stencil
    (what a profile of the step shows)."""
    from _hlo_scopes import check
    from repro.fv3.dyncore import (
        STEP_SCOPES, all_state_fields, make_step_ensemble,
        make_step_sequential,
    )
    from test_trace_scopes import CFG

    if members > 1:
        step = make_step_ensemble(CFG, members, backend="pallas-tpu",
                                  opt_level=3)
    else:
        step = make_step_sequential(CFG, backend="pallas-tpu", opt_level=3)
    lead = (members, 6) if members > 1 else (6,)
    shape = lead + CFG.seq_dom().padded_shape()
    state = {f: jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)
             for f in all_state_fields(CFG)}
    text = step.lower(state).compile().as_text()
    per_layer, nodes = check(text)
    assert set(per_layer) == set(STEP_SCOPES), per_layer
    kernels = re.findall(r"^\s+(?:ROOT )?%(\S+) = .*"
                         r'custom_call_target="tpu_custom_call"', text, re.M)
    assert kernels
    for name in kernels:
        stencil = nodes[name].rsplit("#", 1)[0]
        assert name.rsplit(".", 1)[0] == re.sub(r"\W", "_", stencil), name
