"""Smoke run of the FV3-lite dycore on one TPU chip, at C128 x 80 levels.

Drives the main path through its public entry points — ``init_state``,
``make_step_sequential`` / ``make_step_ensemble`` → ``compile_program`` —
on a whole cubed sphere of six 128 x 128 tiles with 80 levels, halo 6 and
4 tracers (98,304 columns on one chip), and checks what comes out:

* phase A: two physics steps with the jnp backend at opt level 0 (the
  oracle), the jnp backend at opt level 3, and the Pallas TPU backend at opt
  level 3, each compared with the oracle on the tile interiors;
* phase B: one step of a 2-member ensemble on the Pallas backend's member
  grid, each member compared with the sequential Pallas step on it.

Each phase prints its trace-and-lower and compile seconds, a smoke step
time (median over the
steps run, under ``block_until_ready`` — not a benchmark), the process's
peak device bytes, that every field is finite, the mass drift, and the
largest interior error against its reference with the tolerance and why.
No Pallas kernel may run in interpret mode: the resolver must say so, and
the lowered Pallas steps must hold Mosaic kernels (``tpu_custom_call``).

Any failed check raises, so the script exits non-zero; so does a machine
whose first JAX device is not a TPU.  The last line printed is
``{"ok": true, "device": {...}}``.  Schedules are tuned from committed
code only (an empty tuning cache of the run's own), and compiled programs
go to JAX's persistent cache (``$JAX_COMPILATION_CACHE_DIR``, else
``.jax_cache`` in the checkout), so a second run compiles faster.

Run from the root of the checkout:  python chip_smoke.py [--npx N]
"""

from __future__ import annotations

import argparse
import json
import statistics
import sys
import tempfile
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

ROOT = Path(__file__).resolve().parent

#: physics steps each phase-A configuration runs before it is compared
A_STEPS = 2
#: extra steps run only to time a phase (its compared states are kept)
TIMED_EXTRA = 2
#: (phase, reference) -> limit on the largest per-field interior error
#: (RMS of the difference over the reference field's standard deviation)
#: after the compared steps, and why.  This dycore grows a rounding
#: difference several times over per physics step, so the limit sits
#: between sound readings and planted faults (a 1% error in the flux
#: coefficient dtdx, a dropped pt halo exchange), measured at C12 on the
#: CPU: sound 1.2e-4 after 2 steps, faults 4.1e-3 and 8.6e-2.
CHECKS = {
    ("jnp opt3", "jnp opt0"): (
        1e-3, "opt 3 strength-reduces x**2.0 and x**0.5 to x*x and sqrt, "
              "which round differently from pow; grown over 2 steps"),
    ("pallas-tpu opt3", "jnp opt0"): (
        1e-3, "as for jnp opt3 against jnp opt0"),
    ("pallas-tpu opt3", "jnp opt3"): (
        1e-5, "the same rewritten program; Mosaic and XLA may round "
              "division and sqrt an ulp apart (bitwise equal at C64 on a "
              "TPU v5e)"),
    ("ensemble", "pallas-tpu opt3"): (
        1e-5, "the member grid runs the sequential step's kernels "
              "(bitwise equal at C64 on a TPU v5e); XLA may fuse the "
              "batched glue code differently"),
}


def _device_line() -> dict:
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def _peak_bytes() -> int | None:
    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def _interior(cfg, v):
    h, n = cfg.halo, cfg.npx
    return np.asarray(v)[..., h:h + n, h:h + n]


def _rel_error(cfg, got: dict, ref: dict) -> tuple[float, str]:
    """Largest per-field interior error over all fields: the RMS of
    ``got - ref`` over the field's standard deviation in ``ref``, so a
    field's constant offset (pt and delp sit near 1) hides nothing, and
    rounding differences that a limiter flips at a few points weigh less
    than an error spread over the sphere; and the field."""
    worst, worst_f = 0.0, ""
    for f, r in ref.items():
        r = _interior(cfg, r)
        d = _interior(cfg, got[f]) - r
        err = float(np.sqrt(np.mean(d * d))) / (float(r.std()) or 1.0)
        if err > worst or not worst_f:
            worst, worst_f = err, f
    return worst, worst_f


def assert_native(lowered, backend: str) -> int:
    """No kernel may run in interpret mode: the resolver must pick native
    kernels, and a Pallas step must lower to Mosaic kernels.  Returns the
    number of Mosaic kernel calls in the lowered step."""
    from repro.core.backend.compile import pallas_interpret

    if pallas_interpret():
        raise AssertionError("Pallas would run in interpret mode")
    n = lowered.as_text().count("tpu_custom_call")
    if backend.startswith("pallas") and n == 0:
        raise AssertionError(f"{backend} step holds no Mosaic kernel")
    return n


def run_steps(step, state, n_compared: int, n_timed_extra: int):
    """Compile ``step`` ahead of time, run ``n_compared`` steps (their
    states kept), then ``n_timed_extra`` more for timing only.  Returns the
    kept states, the lowered and the compiled step, (trace-and-lower,
    compile) seconds — a compile-cache hit shortens only the second — and
    step times."""
    t0 = time.perf_counter()
    lowered = step.lower(state)
    t1 = time.perf_counter()
    compiled = lowered.compile()
    compile_s = (t1 - t0, time.perf_counter() - t1)
    times, kept, s = [], [], state
    for i in range(n_compared + n_timed_extra):
        t = time.perf_counter()
        s = jax.block_until_ready(compiled(s))
        times.append(time.perf_counter() - t)
        if i < n_compared:
            kept.append(s)
    return kept, lowered, compiled, compile_s, times


def report(label: str, name: str, cfg, outs: list, refs: dict,
           mass0: float, compile_s: tuple, times, n_mosaic: int):
    """Print one phase's line and raise on any failed check.  ``outs`` are
    the phase's states after each compared step; ``refs`` maps a CHECKS
    reference name to its states after the same steps."""
    from repro.fv3.state import total_mass

    out = outs[-1]
    finite = all(bool(jnp.isfinite(v).all()) for v in out.values())
    drift = total_mass(out, cfg) / mass0 - 1.0
    print(f"[{label}] {name}: lower_s={compile_s[0]:.1f} "
          f"compile_s={compile_s[1]:.1f} "
          f"smoke_step_ms={1e3 * statistics.median(times):.1f} "
          f"(median of {len(times)}, smoke run, not a benchmark) "
          f"peak_bytes_in_use={_peak_bytes()} finite={finite} "
          f"mass_drift={drift:.3e} mosaic_kernel_calls={n_mosaic}",
          flush=True)
    failed = [] if finite else ["non-finite fields"]
    for ref_name, ref_states in refs.items():
        tol, why = CHECKS[(name, ref_name)]
        errs = [_rel_error(cfg, o, r) for o, r in zip(outs, ref_states)]
        steps = " ".join(f"step{i + 1}={e:.3e}({f})"
                         for i, (e, f) in enumerate(errs))
        print(f"    rms_err/std vs {ref_name}: {steps} "
              f"tol={tol:g}: {why}", flush=True)
        if not errs[-1][0] <= tol:
            failed.append(f"error {errs[-1][0]:.3e} vs {ref_name}")
    if failed:
        raise AssertionError(f"[{label}] {name}: {'; '.join(failed)}")


def phase_a(cfg, state0, mass0):
    """Two physics steps on each backend against the jnp opt-0 oracle (and
    Pallas against jnp at the same opt level); returns the compiled Pallas
    opt-3 step (phase B's reference)."""
    from repro.fv3.dyncore import make_step_sequential

    results, pallas = {}, None
    for backend, opt in (("jnp", 0), ("jnp", 3), ("pallas-tpu", 3)):
        name = f"{backend} opt{opt}"
        step = make_step_sequential(cfg, backend=backend, opt_level=opt)
        outs, lowered, compiled, compile_s, times = run_steps(
            step, state0, A_STEPS, TIMED_EXTRA)
        n = assert_native(lowered, backend)
        refs = {r: results[r] for (p, r) in CHECKS if p == name}
        report("A", name, cfg, outs, refs, mass0, compile_s, times, n)
        results[name] = outs
        if backend == "pallas-tpu":
            pallas = compiled
    return pallas


def phase_b(cfg, seq_compiled, n_members: int = 2):
    """One ensemble step on the member grid against the compiled
    sequential Pallas step on each member."""
    from repro.fv3.dyncore import make_step_ensemble
    from repro.fv3.state import ensemble_state, total_mass

    ens0 = ensemble_state(cfg, n_members)
    step = make_step_ensemble(cfg, n_members, backend="pallas-tpu",
                              opt_level=3)
    outs, lowered, _, compile_s, times = run_steps(step, ens0, 1,
                                                   TIMED_EXTRA)
    n = assert_native(lowered, "pallas-tpu")
    for m in range(n_members):
        member0 = {k: v[m] for k, v in ens0.items()}
        ref = jax.block_until_ready(seq_compiled(member0))
        report(f"B member {m}", "ensemble", cfg,
               [{k: v[m] for k, v in outs[0].items()}],
               {"pallas-tpu opt3": [ref]}, total_mass(member0, cfg),
               compile_s, times, n)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--npx", type=int, default=128,
                    help="cells per tile edge (default 128: C128)")
    args = ap.parse_args()
    dev = _device_line()
    print(f"jax {jax.__version__}  device_kind={dev['kind']!r}  "
          f"platform={dev['platform']}  device_count={dev['count']}",
          flush=True)
    if dev["platform"] != "tpu":
        print("chip_smoke: the first JAX device is not a TPU; this smoke "
              "run has no CPU fallback", file=sys.stderr)
        return 2
    if not (ROOT / "src" / "repro").is_dir():
        print(f"chip_smoke: no repro package under {ROOT / 'src'}; run from "
              "the root of a checkout", file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    from repro.core import TuningCache, enable_compile_cache, \
        set_default_cache
    from repro.core.hardware import resolve_hardware
    from repro.fv3.dyncore import FV3Config
    from repro.fv3.state import init_state, total_mass

    print(f"compile cache: {enable_compile_cache()}", flush=True)
    cfg = FV3Config(npx=args.npx, nk=80)
    print(f"config: C{cfg.npx} x {cfg.nk} levels, halo {cfg.halo}, "
          f"{cfg.n_tracers} tracers, n_split={cfg.n_split}, "
          f"k_split={cfg.k_split}; hardware descriptor "
          f"{resolve_hardware(None).name}", flush=True)
    with tempfile.TemporaryDirectory() as tuning_dir:
        set_default_cache(TuningCache(tuning_dir))
        t0 = time.perf_counter()
        state0 = jax.block_until_ready(init_state(cfg))
        print(f"init_state: {time.perf_counter() - t0:.1f}s", flush=True)
        mass0 = total_mass(state0, cfg)
        pallas_step = phase_a(cfg, state0, mass0)
        phase_b(cfg, pallas_step)
    print(json.dumps({"ok": True, "device": dev}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
