"""Readings that the limits of a cell's check are set from, in one process.

    python3 bench/calibrate.py --workload <cell> --seeds 1-12 \
        --control-seeds 1-3 [--out FILE]

For each seed it drives the cell's compiled step (built once, as a run
builds it) through the compared steps from the seed's initial state, then
steps the plain reference from the same state and prints the worst
per-field error of each compared step: the sound readings.  For each
control seed it also steps the reference computed in bfloat16, the
precision below the configuration's float32, and reads it against the
float32 reference: the control's readings, which a limit must fail.  It
also reads the initial state against the reference, a step that returns
its state unchanged.  Needs the cell's chips, as a run does.
"""

from __future__ import annotations

import argparse
import json
import sys
import time

import check
import spec
import system
import traffic as gen
from run import device_line, log


def seeds(text: str) -> list[int]:
    out = []
    for part in text.split(","):
        a, _, b = part.partition("-")
        out.extend(range(int(a), int(b) + 1) if b else [int(a)])
    return out


def calibrate(cell, cfg, traffic, n_checked, sound, control, *,
              require_accelerator=True, log_fn=log):
    import jax
    import jax.numpy as jnp

    device_line(jax, cell["chips"], require_accelerator)
    from references import fv3lite

    system.import_program()
    from repro.core import enable_compile_cache

    enable_compile_cache()
    fcfg = system.fv3_config(cfg, traffic)
    members, halo = cfg["members"], cfg["halo"]
    step = system.make_step(cfg, fcfg)
    ref_step = jax.jit(fv3lite.make_step(cfg, traffic["namelist"],
                                         tuple(cfg["tracers"])))
    rows = []
    for seed in sorted(set(sound) | set(control)):
        t = time.perf_counter()
        init = gen.initial_state(cfg, traffic, seed)
        row = {"seed": seed}
        ref = check.reference_states(jax, ref_step, init, n_checked, halo)
        if seed in sound:
            state = system.program_state(
                cfg, {k: jnp.copy(v) for k, v in init.items()})
            for i in range(n_checked):
                state = jax.block_until_ready(step(state))
                got = check.host_interiors(jax, state, halo, members > 1)
                row[f"sound.step{i + 1}"] = check.worst_error(got, ref[i], 0)
            del state
        if seed in control:
            low = check.reference_states(jax, ref_step, init, n_checked,
                                         halo, dtype=jnp.bfloat16)
            for i in range(n_checked):
                row[f"control.step{i + 1}"] = check.worst_error(
                    low[i], ref[i], 0)
            start = check.host_interiors(jax, init, halo, True)
            for i in range(n_checked):
                row[f"unchanged.step{i + 1}"] = check.worst_error(
                    start, ref[i], 0)
        rows.append(row)
        log_fn(f"seed {seed} ({time.perf_counter() - t:.1f}s): "
               + json.dumps({k: v for k, v in row.items() if k != "seed"}))
    summary = {}
    for key in sorted({k for r in rows for k in r if k != "seed"}):
        vals = [r[key][0] for r in rows if key in r]
        summary[key] = {"n": len(vals), "min": min(vals), "max": max(vals)}
    return {"rows": rows, "summary": summary}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, type=seeds)
    ap.add_argument("--control-seeds", default="", type=lambda s:
                    seeds(s) if s else [])
    ap.add_argument("--out")
    args = ap.parse_args(argv)
    bench = spec.benchmark()
    cell = spec.cell(bench, args.workload)
    cfg = spec.config(bench, cell["config"])
    out = calibrate(cell, cfg, spec.traffic(cell["traffic"]),
                    spec.limits(cell["name"])["steps_compared"], args.seeds,
                    args.control_seeds)
    text = json.dumps(out, indent=1)
    if args.out:
        with open(args.out, "w") as f:
            f.write(text)
    print(json.dumps(out["summary"]))
    return 0


if __name__ == "__main__":
    sys.exit(main())
