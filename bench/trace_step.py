"""Profile a few whole steps of one cell and reduce the trace by the step's
own scopes; time the same steps unprofiled too, in the same process, for
what tracing costs.

    python3 bench/trace_step.py --workload <cell> --seed <n> [--steps 3]
        [--out FILE.json] [--fixture FILE.json.gz]

Run from the root of a checkout, on the chip (a machine whose first device
is not a TPU exits 3).  The cell's step is built, lowered and compiled as
``run.py`` builds it; the compiled step's HLO text gives the scope map
(``scopes.scope_map``).  After one warm step it runs ``--steps`` steps
unprofiled, as many under the profiler (each in a ``bench.step`` span, as
``run.py`` traces them), and as many unprofiled again, each awaited.  The
last line of standard output is one JSON object: the set-up seconds (the
step factory's own ``build_seconds`` among them), the host milliseconds of
each step profiled and not, ``tracing.reduce``'s keys, and
``scopes.reduce``'s breakdown.  ``--out`` also writes it to a file, with the
HLO of the costliest unscoped instructions, and
``--fixture`` writes the trace's events with the scope map and the
reductions' results, for the benchmark's tests.
"""

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import gzip  # noqa: E402
import json  # noqa: E402
import re  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

import scopes  # noqa: E402
import spec  # noqa: E402
import tracing  # noqa: E402

def timed(jax, step, state, n: int):
    """``n`` steps, each awaited; their host milliseconds."""
    ms = []
    for _ in range(n):
        t = time.perf_counter()
        state = jax.block_until_ready(step(state))
        ms.append(1e3 * (time.perf_counter() - t))
    return state, ms


def write(path: str, obj) -> None:
    with gzip.open(path, "wt") as f:
        json.dump(obj, f)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--steps", type=int, default=3)
    ap.add_argument("--out", metavar="FILE.json")
    ap.add_argument("--fixture", metavar="FILE.json.gz")
    args = ap.parse_args(argv)
    bench = spec.benchmark()
    cell = spec.cell(bench, args.workload)
    cfg = spec.config(bench, cell["config"])
    traffic = spec.traffic(cell["traffic"])
    if 1 + 3 * args.steps > traffic["forecast_steps"]:
        raise ValueError("the steps must fit one forecast")
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"trace_step: needs a TPU; JAX sees {dev.platform}",
              file=sys.stderr)
        return 3
    import system
    import traffic as gen

    system.import_program()
    from repro.core import enable_compile_cache

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    fcfg = system.fv3_config(cfg, traffic)
    state = system.program_state(cfg, jax.block_until_ready(
        gen.initial_state(cfg, traffic, args.seed)))
    host = {}
    t = time.perf_counter()
    step = system.make_step(cfg, fcfg)
    host["factory_s"] = time.perf_counter() - t
    host["build_seconds"] = step.build_seconds
    t = time.perf_counter()
    lowered = step.lower(state)
    host["lower_s"] = time.perf_counter() - t
    t = time.perf_counter()
    hlo = lowered.compile().as_text()
    host["compile_s"] = time.perf_counter() - t
    scope_map = scopes.scope_map(hlo)
    state = jax.block_until_ready(step(state))
    host["setup_s"] = time.perf_counter() - T0
    state, before = timed(jax, step, state, args.steps)
    with tempfile.TemporaryDirectory(prefix="bench-trace-") as tdir:
        traced = []
        with jax.profiler.trace(tdir):
            for _ in range(args.steps):
                t = time.perf_counter()
                with jax.profiler.TraceAnnotation("bench.step"):
                    state = jax.block_until_ready(step(state))
                traced.append(1e3 * (time.perf_counter() - t))
        events = scopes.read_events(tdir)
    state, after = timed(jax, step, state, args.steps)
    untraced = before + after
    if args.fixture:    # the trace first, should a reduction fail
        write(args.fixture, {**events, "scopes": scope_map})
    red = tracing.reduce(events, args.steps)
    brk = scopes.reduce(events, scope_map, args.steps)
    result = {
        "workload": cell["name"], "seed": args.seed,
        "device": {"platform": dev.platform, "kind": dev.device_kind},
        "host": host,
        "host_ms": {"untraced": untraced, "traced": traced,
                    "tracing_cost_ms": statistics.median(traced)
                    - statistics.median(untraced)},
        "trace": {k: red[k] for k in ("window_s", "busy_s", "mosaic_s",
                                      "xla_s", "steps")},
        "scopes": brk,
        "scoped_instructions": len(scope_map),
        "step_spans": scopes.step_spans(events),
    }
    if args.out:
        lines = {name: line.strip()[:400] for name, line in re.findall(
            r"^\s+(?:ROOT )?%(\S+) = (.*)$", hlo, re.M)}
        other = {o[0]: lines.get(o[0]) for o in brk["other_ops"]}
        with open(args.out, "w") as f:
            json.dump({**result, "other_ops_hlo": other}, f, indent=1)
    if args.fixture:
        write(args.fixture, {**events, "scopes": scope_map,
                             "expected": {**result["trace"],
                                          "breakdown": brk}})
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
