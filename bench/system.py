"""The system under test: the dycore's public step factories, the
compiled runner of each of its programs and its reference halo exchange,
built from a configuration and a traffic file."""

from __future__ import annotations

import re
import sys

import jax
import jax.numpy as jnp

from spec import ROOT


def import_program() -> None:
    """Put the program's sources on the path."""
    src = str(ROOT / "src")
    if src not in sys.path:
        sys.path.insert(0, src)


def fv3_config(cfg: dict, traffic: dict):
    from repro.fv3.dyncore import FV3Config

    nl = traffic["namelist"]
    fcfg = FV3Config(npx=cfg["npx"], nk=cfg["nk"], halo=cfg["halo"],
                     n_split=nl["n_split"], k_split=nl["k_split"],
                     n_tracers=len(cfg["tracers"]), dt=cfg["dt"],
                     beta=cfg["beta"], smag_coeff=cfg["smag_coeff"],
                     ptop=cfg["ptop"], dtype=cfg["dtype"])
    if fcfg.tracers != tuple(cfg["tracers"]):
        raise ValueError(f"the program names its tracers {fcfg.tracers}, "
                         f"the configuration {cfg['tracers']}")
    return fcfg


def make_step(cfg: dict, fcfg):
    """The compiled step as production runs it: ``state = step(state)``
    with the state donated."""
    from repro.fv3.dyncore import make_step_ensemble, make_step_sequential

    if cfg["members"] > 1:
        return make_step_ensemble(fcfg, cfg["members"], backend=cfg["backend"],
                                  opt_level=cfg["opt_level"],
                                  batch=cfg["batch"], donate=True)
    return make_step_sequential(fcfg, backend=cfg["backend"],
                                opt_level=cfg["opt_level"], donate=True)


def program_state(cfg: dict, state: dict) -> dict:
    """The generator's (members, ...) arrays in the step's layout."""
    if cfg["members"] > 1:
        return state
    return {k: v[0] for k, v in state.items()}


def assert_native(cfg: dict, lowered) -> int:
    """No kernel may run in interpret mode; a Pallas step must hold Mosaic
    kernels.  Returns the number of Mosaic kernel calls."""
    from repro.core.backend.compile import pallas_interpret

    if pallas_interpret():
        raise RuntimeError("Pallas kernels would run in interpret mode")
    n = lowered.as_text().count("tpu_custom_call")
    if cfg["backend"].startswith("pallas") and n == 0:
        raise RuntimeError(f"the {cfg['backend']} step holds no Mosaic kernel")
    return n


def slug(name: str) -> str:
    return re.sub(r"[^A-Za-z0-9_]", "_", name)


def _named(fn, name: str):
    def probe(*args):
        return fn(*args)

    probe.__name__ = probe.__qualname__ = name
    return probe


def probes(cfg: dict, fcfg, step, state: dict, counts: dict) -> dict:
    """One jitted callable per layer probed alone, with its inputs taken
    from ``state``: each program's compiled runner, vmapped over the tiles
    as in the step (member-batched as in the step), and one reference halo
    exchange of the acoustic state.  Returns {name: (fn, args)}; each fn's
    XLA module is named ``jit_bench_probe_<slug>``."""
    from repro.core import compile_program
    from repro.core.backend import jit_program
    from repro.fv3.dyncore import default_params
    from repro.fv3.halo import exchange_reference

    members = cfg["members"]
    kw = {"n_members": members, "batch": cfg["batch"]} if members > 1 else {}
    axis = 1 if members > 1 else 0
    params = default_params(fcfg)
    like = state["delp"]
    # one copy of each field for all probes: the step donates the state it
    # is given, and the ensemble's copies would not fit twice over
    copies = {}

    def field(src):
        if isinstance(src, float):
            src = str(src)
            if src not in copies:
                copies[src] = jnp.full_like(like, float(src))
        elif src not in copies:
            copies[src] = jnp.copy(state[src])
        return copies[src]

    out = {}
    for prog in step.programs:
        run = compile_program(prog, cfg["backend"],
                              opt_level=cfg["opt_level"], **kw)
        tile = jax.vmap(run, in_axes=(axis, None), out_axes=axis)
        fn = jit_program(_named(tile, "bench_probe_" + slug(prog.name)),
                         cfg["backend"])
        fields = {}
        for f, c in counts[prog.name]["fields"].items():
            if not c.get("input", c["read"]):
                continue
            fields[f] = field(c.get("probe_input", f))
        out[prog.name] = (fn, (fields, params))
    halo = counts["halo_exchange"]
    vec = [tuple(halo["vector_pair"])]
    ex = jax.jit(_named(
        lambda fs: exchange_reference(fs, fcfg.halo, vector_pairs=vec),
        "bench_probe_halo_exchange"))
    out["halo_exchange"] = (ex, ({f: field(f) for f in halo["fields"]},))
    return out
