"""Quickstart: the paper's workflow end-to-end on one stencil program.

1. declare stencils in the DSL (schedule-free, close to the math),
2. build a stencil program and let the automatic pass pipeline optimize it
   (``opt_level=3``: prune → strength-reduce → cost-model fusion → tuned
   schedules) — no manual pipeline assembly,
3. run on the jnp oracle and the Pallas backend, compare,
4. print the memory-bound performance model report (paper Fig. 10 style).

Run:  PYTHONPATH=src python examples/quickstart.py
"""

import numpy as np
import jax.numpy as jnp

from repro.core import (
    StencilProgram, compile_program, format_report, program_bytes,
    program_report,
)
from repro.core.stencil import DomainSpec, Field, Param, gtstencil


@gtstencil
def diffusive_flux(q: Field, kappa: Field, fx: Field):
    with computation(PARALLEL), interval(...):
        fx = kappa * (q[0, 0, 0] - q[-1, 0, 0])
        with horizontal(region[0, :]):
            fx = 0.0       # closed boundary on the first column


@gtstencil
def apply_flux(q: Field, fx: Field, qn: Field, dt: Param):
    with computation(PARALLEL), interval(...):
        qn = q + dt * (fx[1, 0, 0] - fx[0, 0, 0])


@gtstencil
def damping(qn: Field, out: Field, c: Param):
    with computation(PARALLEL), interval(...):
        out = qn * (1.0 + (c * qn) ** 2.0) ** 0.5


def build():
    dom = DomainSpec(ni=64, nj=64, nk=8, halo=3)
    p = StencilProgram("quickstart", dom)
    for f in ("q", "kappa", "out"):
        p.declare(f)
    for f in ("fx", "qn"):
        p.declare(f, transient=True)
    p.add(diffusive_flux, {"q": "q", "kappa": "kappa", "fx": "fx"})
    p.add(apply_flux, {"q": "q", "fx": "fx", "qn": "qn"})
    p.add(damping, {"qn": "qn", "out": "out"})
    p.propagate_extents()
    return p, dom


def main():
    p, dom = build()
    print(p)
    print(f"\nbytes moved (untransformed): {program_bytes(p):,}")

    rng = np.random.default_rng(0)
    fields = {f: jnp.asarray(rng.uniform(0.5, 1.5, dom.padded_shape()),
                             jnp.float32) for f in p.fields}
    params = {"dt": 0.1, "c": 0.2}
    # one entry point, three registered backends (jnp oracle, pallas-tpu,
    # pallas-gpu); opt_level selects the automatic pass ladder — the paper's
    # whole optimization pipeline with no per-program hand-tuning
    fn_jnp = compile_program(p, "jnp", opt_level=3)
    fn_pl = compile_program(p, "pallas-tpu", opt_level=3)
    print(f"\nopt_level=3 pipeline:\n{fn_jnp.opt_report.summary()}")

    out_jnp = fn_jnp(dict(fields), params)
    out_pl = fn_pl(dict(fields), params)
    err = np.abs(np.asarray(out_jnp["out"]) - np.asarray(out_pl["out"])).max()
    print(f"\njnp vs pallas-tpu(interpret) max err: {err:.2e}")

    opt = fn_jnp.program  # the graph the ladder actually lowered
    print(f"bytes moved (optimized): {program_bytes(opt):,}")
    print("\nmemory-bound model report (TPU v5e target):")
    print(format_report(program_report(opt)))
    print("\nsame program, P100 GPU target:")
    print(format_report(program_report(opt, hw="p100")))


if __name__ == "__main__":
    main()
