"""Benchmark of the FV3-lite dycore on one accelerator: one cell a run.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout.  Everything about a cell comes from data
found by name: the cell in ``BENCHMARK.json``, its configuration file, its
traffic (``bench/workloads/<traffic>.json``), the limits of its check
(``bench/limits/<cell>.json``), the byte counts (``bench/counts``), the
peaks (``bench/peaks.json``) and one reader per metric
(``bench/metrics/<metric>.py``).

A run makes the initial state from the seed on the device, builds the
step through the program's public factory (donated, as a production loop
runs it), lowers and compiles it, and drives it through the first steps
that the check compares; all of that is set-up.  Untraced, it then
dispatches steps for ``--seconds``, a few seconds of them ahead of the one
it waits for, waits until all it sent have finished, and reports the
cell's end-to-end metrics over all of that work and time.  Traced, it
profiles a few whole steps and then each program and the halo exchange
called alone, and reports the per-layer metrics.  Either way it then
frees the program's state and steps the plain reference from the same
initial state, and ``correct`` says whether the compared steps agree
within the cell's limits.  The last line of standard output is one JSON
object; the last lines of standard error list each compared number with
its limit.  A machine whose first device is not a TPU, or that has fewer
chips than the cell asks for, exits 3 and prints no result.
"""

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import collections  # noqa: E402
import gc  # noqa: E402
import gzip  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

import numpy as np  # noqa: E402

import check  # noqa: E402
import spec  # noqa: E402

#: whole steps profiled in a traced run, and calls of each probe
TRACED_STEPS = 3
PROBE_CALLS = 3
#: seconds of steps dispatched ahead of the one the window waits for
LEAD_S = 4.0


class NoAccelerator(RuntimeError):
    pass


def log(msg: str) -> None:
    print(f"[bench {time.perf_counter() - T0:7.1f}s] {msg}", file=sys.stderr,
          flush=True)


def device_line(jax, chips: int, require: bool) -> dict:
    devs = jax.devices()
    if require and (devs[0].platform != "tpu" or len(devs) < chips):
        raise NoAccelerator(
            f"needs {chips} TPU chip(s); JAX sees {len(devs)} "
            f"{devs[0].platform} device(s) ({devs[0].device_kind})")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def run(cell: dict, cfg: dict, traffic: dict, limits: dict, seed: int,
        seconds: float, traced: bool, *, peaks: dict | None = None,
        require_accelerator: bool = True, dump_trace: str | None = None
        ) -> dict:
    """One run of one cell; returns the result object (without metrics'
    readers applied: ``record`` holds what they read)."""
    import jax

    device = device_line(jax, cell["chips"], require_accelerator)
    if peaks is None:
        peaks = spec.peaks(device["kind"])
    import system
    import traffic as gen
    from references import fv3lite

    system.import_program()
    from repro.core import enable_compile_cache

    log(f"compile cache {enable_compile_cache()}")
    # every program of a run, however quick to compile, comes from the
    # cache after a checkout's first run
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    fcfg = system.fv3_config(cfg, traffic)
    members, halo = cfg["members"], cfg["halo"]
    n_checked = limits["steps_compared"]
    forecast_steps = traffic["forecast_steps"]
    if not n_checked + TRACED_STEPS <= forecast_steps:
        raise ValueError("a forecast must hold the compared and traced steps")

    init = system.program_state(cfg, jax.block_until_ready(
        gen.initial_state(cfg, traffic, seed)))
    # each forecast starts from a fresh copy of the initial state (the step
    # donates the state it is given)
    fresh = jax.jit(lambda s: jax.tree.map(jax.numpy.copy, s))
    state = jax.block_until_ready(fresh(init))
    log("initial state made")
    step = system.make_step(cfg, fcfg)
    t = time.perf_counter()
    lowered = step.lower(state)
    t1 = time.perf_counter()
    lowered.compile()
    host = {"lower_time": t1 - t,
            "compile_time": time.perf_counter() - t1}
    n_mosaic = system.assert_native(cfg, lowered) if require_accelerator \
        else 0
    log(f"lowered in {host['lower_time']:.2f}s, compiled in "
        f"{host['compile_time']:.2f}s, {n_mosaic} Mosaic kernel calls")
    kept = []
    for _ in range(n_checked):
        t = time.perf_counter()
        state = jax.block_until_ready(step(state))
        step_estimate = time.perf_counter() - t
        kept.append(check.host_interiors(jax, state, halo, members > 1))
    record = {"host": host, "trace": None, "peaks": peaks,
              "bytes": system_bytes(cfg, traffic)}
    if traced:
        import tracing

        del init        # no forecast restarts: free its device memory
        probes = system.probes(cfg, fcfg, step, state, spec.counts())
        for fn, args in probes.values():       # compile outside the trace
            jax.block_until_ready(fn(*args))
        host["setup_time"] = time.perf_counter() - T0
        with tempfile.TemporaryDirectory(prefix="bench-trace-") as tdir:
            with jax.profiler.trace(tdir):
                for _ in range(TRACED_STEPS):
                    with jax.profiler.TraceAnnotation("bench.step"):
                        state = jax.block_until_ready(step(state))
                for name, (fn, args) in probes.items():
                    with jax.profiler.TraceAnnotation("bench.probe." + name):
                        for _ in range(PROBE_CALLS):
                            jax.block_until_ready(fn(*args))
            events = tracing.read_events(tdir)
        if dump_trace:
            with gzip.open(dump_trace, "wt") as f:
                json.dump(events, f)
        record["trace"] = tracing.reduce(events, TRACED_STEPS)
        del probes
        steps_run = TRACED_STEPS
    else:
        # the state is donated, so each step's completion is awaited through
        # a one-element read of its output
        mark = jax.jit(lambda s: s["pt"][(0,) * s["pt"].ndim])
        jax.block_until_ready(mark(state))
        lead = max(2, math.ceil(LEAD_S / step_estimate))
        host["setup_time"] = time.perf_counter() - T0
        # steps are dispatched up to ``lead`` ahead of the one waited for, so
        # that a stall of the host does not idle the device; once the time is
        # up nothing more is sent, and the window closes when all that was
        # sent has finished
        marks, waits = collections.deque(), []
        steps_run, in_forecast = 0, n_checked
        t = time.perf_counter()
        while time.perf_counter() - t < seconds:
            if in_forecast == forecast_steps:
                state, in_forecast = fresh(init), 0
            state = step(state)
            in_forecast += 1
            steps_run += 1
            marks.append(mark(state))
            if len(marks) > lead:
                marks.popleft().block_until_ready()
                waits.append(time.perf_counter())
        jax.block_until_ready((state, list(marks)))
        host["window_s"] = time.perf_counter() - t
        del init, marks
        host["steps"] = steps_run
        host["lead"] = lead
        host["step_times_s"] = list(np.diff(waits))
    log(f"{steps_run} steps after set-up of {host['setup_time']:.1f}s")
    finite = all(bool(jax.numpy.isfinite(v).all()) for v in state.values())
    stats = jax.devices()[0].memory_stats() or {}
    device["memory_peak_bytes"] = stats.get("peak_bytes_in_use")
    if record["trace"] is not None:
        device["busy_s"] = record["trace"]["busy_s"]
        device["window_s"] = record["trace"]["window_s"]
    del state, step, lowered
    gc.collect()

    # the plain reference, member by member, from the same initial state
    t = time.perf_counter()
    with jax.default_matmul_precision("highest"):
        ref_step = jax.jit(fv3lite.make_step(cfg, traffic["namelist"],
                                             tuple(cfg["tracers"])))
        refs = check.reference_states(
            jax, ref_step, gen.initial_state(cfg, traffic, seed), n_checked,
            halo)
    numbers = {}
    for i in range(n_checked):
        err, where = check.worst_error(kept[i], refs[i], 0)
        numbers[f"rms_err.step{i + 1}"] = err
        log(f"step {i + 1}: worst field {where}")
    numbers["nonfinite_end_state"] = 0.0 if finite else 1.0
    log(f"reference and comparison took {time.perf_counter() - t:.1f}s")
    correct, checks = check.judge(numbers, limits["numbers"])
    return {"correct": correct, "attempted": steps_run,
            "failed": 0 if finite else steps_run, "record": record,
            "device": device, "checks": checks}


def system_bytes(cfg: dict, traffic: dict) -> dict:
    import yardstick

    return yardstick.lower_bound_bytes(cfg, traffic, spec.counts())


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--dump-trace", metavar="FILE.json.gz",
                    help="with --trace 1, also write the trace's device and "
                         "bench.* events (the input of the reduction)")
    args = ap.parse_args(argv)
    bench = spec.benchmark()
    cell = spec.cell(bench, args.workload)
    cfg = spec.config(bench, cell["config"])
    traffic = spec.traffic(cell["traffic"])
    limits = spec.limits(cell["name"])
    wanted = spec.metrics(bench, cell["name"], bool(args.trace))
    try:
        out = run(cell, cfg, traffic, limits, args.seed, args.seconds,
                  bool(args.trace), dump_trace=args.dump_trace)
    except NoAccelerator as e:
        print(f"bench: {e}; no result", file=sys.stderr)
        return 3
    record = out.pop("record")
    metrics = {}
    for m in wanted:
        value = spec.reader(m["name"])(record)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    host = record["host"]
    if host.get("step_times_s"):
        q = np.quantile(np.array(host["step_times_s"]) * 1e3,
                        [0, 0.1, 0.5, 0.9, 1])
        print(f"window steps {host['steps']} in {host['window_s']:.3f}s, "
              f"{host['lead']} dispatched ahead; ms between completions "
              f"waited for: min {q[0]:.3f} p10 {q[1]:.3f} median {q[2]:.3f} "
              f"p90 {q[3]:.3f} max {q[4]:.3f}")
    if record["trace"] is not None:
        import yardstick

        opb = yardstick.ops_per_byte(cfg, spec.counts())
        print("ops/byte (hand-counted ops over lower-bound bytes): "
              + ", ".join(f"{k} {v:.2f}" for k, v in sorted(opb.items())))
    result = {"correct": out["correct"], "attempted": out["attempted"],
              "failed": out["failed"], "metrics": metrics,
              "device": out["device"]}
    if record["trace"] is not None:
        result["breakdown"] = record["trace"]["breakdown"]
    result["checks"] = out["checks"]
    for name, c in out["checks"].items():
        print(f"check {name} = {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
