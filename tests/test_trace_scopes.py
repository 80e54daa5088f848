"""Every op of the compiled dycore step names its layer and its kernel.

The step wraps each program call in a named scope (``c_sw_riem``,
``d_sw``, ``tracer_2d``, ``vertical_remap``) and each halo exchange in
``halo_exchange``; ``compile_program`` wraps each stencil node's runner in
the node's label (``al_x#3``), and each kernel is jitted, and each Pallas
kernel called, under its stencil's name.  The scopes reach the compiled
program as each instruction's ``op_name`` metadata, which is how a profile
of the step attributes device time to layers and kernels.  These tests
compile small steps (C12, jnp and Pallas interpret, one member and a
2-member ensemble) and read that metadata back; ``test_tpu_compile.py``
reads it from the Mosaic step compiled for a v5e.
"""

from __future__ import annotations

import jax
import pytest

from _hlo_scopes import check, parts
from repro.fv3.dyncore import (
    STEP_SCOPES, FV3Config, make_step_ensemble, make_step_sequential,
)
from repro.fv3.state import init_state

CFG = FV3Config(npx=12, nk=4, halo=6, n_split=2, k_split=2)


def small_step(backend: str, members: int):
    """A C12 step at opt 3 and a state it takes."""
    if members > 1:
        step = make_step_ensemble(CFG, members, backend=backend, opt_level=3)
        one = init_state(CFG)
        state = {k: jax.numpy.stack([v] * members) for k, v in one.items()}
    else:
        step = make_step_sequential(CFG, backend=backend, opt_level=3)
        state = init_state(CFG)
    return step, state


CASES = [("jnp", 1), ("pallas-tpu", 1), ("jnp", 2), ("pallas-tpu", 2)]


@pytest.fixture(scope="module", params=CASES, ids=lambda c: f"{c[0]}-m{c[1]}")
def lowered(request):
    backend, members = request.param
    step, state = small_step(backend, members)
    return backend, step, state, step.lower(state)


def test_every_op_falls_under_one_layer_and_a_node(lowered):
    per_layer, nodes = check(lowered[3].compile().as_text())
    assert set(per_layer) == set(STEP_SCOPES), per_layer
    assert nodes


def test_pallas_kernels_take_their_stencils_names(lowered):
    backend, step, state, _ = lowered
    calls = []

    def walk(jaxpr, stack):
        # each nested jaxpr's name stacks start afresh at its call
        for e in jaxpr.eqns:
            at = f"{stack}/{e.source_info.name_stack}"
            if e.primitive.name == "pallas_call":
                calls.append((e.params["name"], at))
            for v in e.params.values():
                for j in (v if isinstance(v, (list, tuple)) else [v]):
                    if hasattr(j, "eqns"):
                        walk(j, at)
                    elif hasattr(getattr(j, "jaxpr", None), "eqns"):
                        walk(j.jaxpr, at)

    walk(jax.make_jaxpr(step)(state).jaxpr, "")
    assert bool(calls) == (backend != "jnp")
    for name, stack in calls:
        labels = [p for p in parts(stack) if "#" in p]
        assert labels and name == labels[-1].rsplit("#", 1)[0], (name, stack)


def test_build_seconds_cover_every_program(lowered):
    step = lowered[1]
    b = step.build_seconds
    names = {p.name for p in step.programs}
    assert set(b["programs"]) == set(b["rewrite"]) == names
    assert all(0 < b["rewrite"][n] <= b["programs"][n] for n in names)
    assert sum(b["programs"].values()) <= b["total"]
