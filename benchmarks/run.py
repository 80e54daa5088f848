"""Benchmark harness — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV lines (plus derived key=value
annotations).  ``python -m benchmarks.run [--only tableX] [--smoke]``.

``--smoke`` is the CI fast mode: it skips the heavy measurement modules and
instead runs the LoC accounting plus a backend round-trip check (jnp vs
pallas-tpu interpret through ``compile_program`` on a small FVT program),
finishing in well under a minute.

Every unfiltered run (smoke included; ``--only`` skips it) also emits
``BENCH_opt_ladder.json``: per ``opt_level`` wall time, kernel count, and
modeled HBM traffic of the FV3 C-grid program through the automatic pass
pipeline, a ``step_dispatch`` section comparing the scan-rolled single-jit
model step against the old unrolled multi-dispatch loop, an
``nk_sweep`` section tracking vertical-remap IR size / trace time / wall
time over production column depths (nk ∈ {8, 32, 80}), and an
``ensemble_throughput`` section (members/sec vs M, vmap-vs-grid kernel
A/B) — CI archives it so the perf trajectory of the optimizer is tracked
from PR 2 onward, and ``benchmarks/check_regression.py`` gates every build
on its deterministic metrics against ``benchmarks/baseline.json``.
"""

from __future__ import annotations

import argparse
import importlib
import json
import sys
import time
import traceback


MODULES = [
    ("table1_loc", "benchmarks.table1_loc"),
    ("table2_modules", "benchmarks.table2_modules"),
    ("table3_opt_ladder", "benchmarks.table3_opt_ladder"),
    ("fig10_kernel_bounds", "benchmarks.fig10_kernel_bounds"),
    ("fig11_weak_scaling", "benchmarks.fig11_weak_scaling"),
    ("transfer_stats", "benchmarks.transfer_stats"),
]

SMOKE_MODULES = [
    ("table1_loc", "benchmarks.table1_loc"),
]


def smoke_backend_roundtrip() -> list[str]:
    """Fast end-to-end check of the compilation pipeline: build a small FVT
    program and require jnp / pallas-tpu(interpret) agreement."""
    import numpy as np
    import jax.numpy as jnp
    from repro.core import available_backends, compile_program
    from repro.core.stencil import DomainSpec
    from repro.fv3 import stencils as S

    from repro.core import StencilProgram

    dom = DomainSpec(ni=8, nj=8, nk=4, halo=6)
    p = StencilProgram("smoke_fvt", dom)
    for f in ("q", "u", "v", "qout"):
        p.declare(f)
    for f in ("cx", "cy"):
        p.declare(f, transient=True)
    p.add(S.courant_x, {"u": "u", "cx": "cx"})
    p.add(S.courant_y, {"v": "v", "cy": "cy"})
    p.add(S.flux_divergence, {"q": "q", "fx": "cx", "fy": "cy", "qout": "qout"})
    p.propagate_extents()

    rng = np.random.default_rng(0)
    fields = {f: jnp.asarray(rng.uniform(0.8, 1.2, dom.padded_shape()),
                             jnp.float32) for f in p.fields}
    params = {"dtdx": 0.02, "dtdy": 0.02, "rdx": 1.0, "rdy": 1.0}
    ref = compile_program(p, "jnp")(dict(fields), params)
    out = compile_program(p, "pallas-tpu")(dict(fields), params)
    err = float(np.abs(np.asarray(ref["qout"]) - np.asarray(out["qout"])).max())
    assert err < 1e-5, f"backend mismatch: {err}"
    return [f"smoke/backend_roundtrip,0,max_err={err:.2e};"
            f"backends={'|'.join(available_backends())}"]


def opt_ladder_json(path: str = "BENCH_opt_ladder.json",
                    smoke: bool = False) -> list[str]:
    """Run the FV3 C-grid program through every opt level; write per-level
    wall time, kernel count and cost-model HBM traffic to ``path``.

    Wall time is the step time of the compiled callable itself — one
    dispatch per kernel, the granularity whose launch overhead fusion
    exists to remove (inside a whole-program ``jax.jit``, XLA:CPU re-fuses
    and DCEs either variant, hiding exactly the effect being measured).
    Levels are timed *interleaved* so machine-load drift between phases
    cannot flip the comparison.  Two noise-robust estimators are reported:
    the global min over all repeats (``wall_us``) and the *min of per-group
    medians* (``wall_us_median``) — a plain median over too few repeats is
    what made opt-3 appear slower than opt-2 in earlier runs of this file;
    the repeat counts are recorded in the JSON so the estimator is
    reproducible.
    """
    import jax
    import numpy as np
    import jax.numpy as jnp
    from repro.core import OPT_LADDERS, compile_program, program_bytes
    from repro.fv3.dyncore import (FV3Config, build_csw_program,
                                   default_params)

    # pattern rewrites that must fire on the C-grid program at their level —
    # a 0 count means the rule regressed to a no-op (gated by
    # check_regression via required_rule_misses == 0)
    required_rules = {4: ("stencil_combine", "cross_cse")}

    npx, nk = (16, 4) if smoke else (32, 8)
    cfg = FV3Config(npx=npx, nk=nk, halo=6)
    dom = cfg.seq_dom()
    p = build_csw_program(cfg, dom)
    params = default_params(cfg)
    rng = np.random.default_rng(0)
    fields = {f: jnp.asarray(rng.uniform(0.8, 1.2, dom.padded_shape()),
                             jnp.float32)
              for f in ("u", "v", "delp", "pt", "w", "cosa", "sina")}

    lvls = sorted(OPT_LADDERS)
    fns = {}
    for lvl in lvls:
        # verify="full": the static verifier runs on the input program and
        # after every pass — its wall time and violation count (always 0 on
        # a green build; check_regression gates on it) land in the JSON
        fn = compile_program(p, "jnp", opt_level=lvl, verify="full")
        jax.block_until_ready(fn(dict(fields), params))  # compile + warm
        fns[lvl] = fn
    n_groups, per_group = (3, 5) if smoke else (5, 12)
    ts: dict[int, list[float]] = {lvl: [] for lvl in lvls}
    for _ in range(n_groups * per_group):
        for lvl in lvls:
            t0 = time.perf_counter()
            jax.block_until_ready(fns[lvl](dict(fields), params))
            ts[lvl].append(time.perf_counter() - t0)

    def min_of_medians(samples: list[float]) -> float:
        groups = [samples[g * per_group:(g + 1) * per_group]
                  for g in range(n_groups)]
        return float(min(np.median(g) for g in groups))

    levels = []
    for lvl in lvls:
        fn = fns[lvl]
        rep = fn.opt_report
        if rep is not None:
            verify = {
                "mode": rep.verify_mode,
                "violations": rep.total_verify_violations,
                "input_seconds": rep.input_verify_seconds,
                "per_pass_seconds": {ps.name: ps.verify_seconds
                                     for ps in rep.passes},
                "total_seconds": rep.total_verify_seconds,
            }
        else:
            # opt 0 has no pass pipeline: compile_program verified the
            # input program directly (it would have raised on violations)
            verify = {"mode": fn.verify_mode, "violations": 0,
                      "input_seconds": None, "per_pass_seconds": {},
                      "total_seconds": None}
        rules = dict(rep.rules) if rep is not None else {}
        levels.append({
            "opt_level": lvl,
            "passes": list(OPT_LADDERS[lvl]),
            "kernels": fn.n_kernels,
            "hbm_bytes_model": (rep.hbm_bytes_after if rep is not None
                                else program_bytes(p)),
            "transient_hbm_inputs": len(fn.transient_inputs),
            "rule_rewrites": rules,
            "required_rule_misses": sum(
                1 for r in required_rules.get(lvl, ()) if not rules.get(r)),
            "wall_us": float(np.min(ts[lvl])) * 1e6,
            "wall_us_median": min_of_medians(ts[lvl]) * 1e6,
            "verify": verify,
        })
    payload = {
        "program": p.name,
        "config": {"npx": npx, "nk": nk, "halo": cfg.halo, "smoke": smoke},
        "measurement": ("per-kernel dispatch, interleaved; wall_us = global "
                        "min, wall_us_median = min of per-group medians"),
        "repeats": {"groups": n_groups, "per_group": per_group,
                    "total": n_groups * per_group},
        "levels": levels,
    }
    with open(path, "w") as f:
        json.dump(payload, f, indent=2)
    base, top = levels[0], levels[-1]
    return [
        f"opt_ladder/opt{lv['opt_level']},{lv['wall_us']:.0f},"
        f"kernels={lv['kernels']};hbm_model={lv['hbm_bytes_model']};"
        f"transient_inputs={lv['transient_hbm_inputs']}"
        for lv in levels
    ] + [f"opt_ladder/speedup,0,"
         f"wall={base['wall_us'] / max(top['wall_us'], 1e-9):.2f}x;"
         f"kernels={base['kernels']}->{top['kernels']};json={path}"]


def nk_sweep_json(path: str = "BENCH_opt_ladder.json",
                  smoke: bool = False) -> list[str]:
    """Vertical-remap scaling sweep over column depths — the sequential-K
    compilation trajectory.

    For nk ∈ {8, 32, 80} (smoke: {8, 32}) build the remap program on the
    ``index_search`` level-search construct and record program IR node
    count, kernel count, trace+compile time of the first call, and
    steady-state wall time.  At nk ≤ 32 the pre-construct *unrolled*
    interpolation (O(nk²) IR) is traced alongside for the A/B ratio — at
    nk = 80 the unrolled variant is the wall this construct removes, so it
    is skipped by design (and recorded as such).  Results merge into
    ``path`` under ``"nk_sweep"``; CI archives the file.
    """
    import jax
    import numpy as np
    import jax.numpy as jnp
    from repro.core import compile_program
    from repro.core.backend import clear_compile_cache
    from repro.fv3.dyncore import FV3Config, build_remap_program, default_params

    nks = (8, 32) if smoke else (8, 32, 80)
    unrolled_max_nk = 8 if smoke else 32
    reps = 3 if smoke else 8
    entries = []
    for nk in nks:
        cfg = FV3Config(npx=8, nk=nk, halo=6, n_tracers=0)
        dom = cfg.seq_dom()
        params = default_params(cfg)
        rng = np.random.default_rng(0)
        ins = {"delp": jnp.asarray(rng.uniform(0.8, 1.2, dom.padded_shape()),
                                   jnp.float32),
               "pt": jnp.asarray(rng.uniform(0.9, 1.1, dom.padded_shape()),
                                 jnp.float32)}

        def trace_and_time(unrolled: bool):
            prog = build_remap_program(cfg, dom, fields=("pt",),
                                       unrolled_interp=unrolled)
            clear_compile_cache()
            t0 = time.perf_counter()
            fn = compile_program(prog, "jnp")
            jax.block_until_ready(fn(dict(ins), params))
            trace_s = time.perf_counter() - t0
            ts = []
            for _ in range(reps):
                t0 = time.perf_counter()
                jax.block_until_ready(fn(dict(ins), params))
                ts.append(time.perf_counter() - t0)
            return {"ir_nodes": prog.ir_node_count(),
                    "kernels": fn.n_kernels,
                    "trace_compile_s": trace_s,
                    "wall_us": float(np.min(ts)) * 1e6}

        entry = {"nk": nk, **trace_and_time(unrolled=False)}
        if nk <= unrolled_max_nk:
            entry["unrolled"] = trace_and_time(unrolled=True)
            entry["trace_speedup_vs_unrolled"] = (
                entry["unrolled"]["trace_compile_s"]
                / max(entry["trace_compile_s"], 1e-9))
        else:
            entry["unrolled"] = "skipped: O(nk^2) unrolling is the wall " \
                                "the index_search construct removes"
        entries.append(entry)
    try:
        with open(path) as f:
            payload = json.load(f)
    except (OSError, ValueError):
        payload = {}
    payload["nk_sweep"] = {
        "config": {"npx": 8, "halo": 6, "fields": ["pt"], "backend": "jnp",
                   "opt_level": 0, "smoke": smoke, "repeats": reps},
        "entries": entries,
    }
    with open(path, "w") as f:
        json.dump(payload, f, indent=2)
    lines = []
    for e in entries:
        extra = ""
        if isinstance(e.get("unrolled"), dict):
            extra = (f";unrolled_ir={e['unrolled']['ir_nodes']}"
                     f";trace_speedup={e['trace_speedup_vs_unrolled']:.1f}x")
        lines.append(
            f"nk_sweep/nk{e['nk']},{e['wall_us']:.0f},"
            f"ir_nodes={e['ir_nodes']};kernels={e['kernels']};"
            f"trace_s={e['trace_compile_s']:.2f}{extra}")
    return lines


def step_dispatch_metric(path: str = "BENCH_opt_ladder.json",
                         smoke: bool = False) -> list[str]:
    """Full-model-step dispatch benchmark: the scan-rolled single-jit step
    vs the old unrolled Python loop, at opt_level 3.

    Reports wall time, trace+compile time, Python-level kernel dispatches
    issued while tracing (the scan path traces each program once; the
    unrolled path re-traces per substep) and acoustic-body trace counts.
    Results are merged into ``path`` under ``"step_dispatch"`` so CI
    archives the single-dispatch trajectory next to the opt ladder.
    """
    import jax
    import numpy as np
    from repro.core.backend import clear_compile_cache
    from repro.fv3.dyncore import FV3Config, make_step_sequential
    from repro.fv3.state import init_state

    npx, nk = (8, 4) if smoke else (16, 8)
    cfg = FV3Config(npx=npx, nk=nk, halo=6, n_split=2, k_split=1,
                    n_tracers=1)
    reps = 3 if smoke else 10
    modes = {}
    for mode, unroll in (("unrolled", True), ("scan", False)):
        # cold in-process compile memo per mode: the first mode must not
        # donate its runner-cache warmth to the second's trace_compile_s
        clear_compile_cache()
        step = make_step_sequential(cfg, opt_level=3, unroll=unroll,
                                    donate=True)
        # donation invalidates the input where the platform honors it, so
        # each call feeds the previous call's output (fresh initial state
        # per mode keeps the two variants comparable)
        state = init_state(cfg)
        t0 = time.perf_counter()
        state = step(state)                          # trace + compile + run
        jax.block_until_ready(state)
        trace_s = time.perf_counter() - t0
        ts = []
        for _ in range(reps):
            t0 = time.perf_counter()
            state = step(state)
            jax.block_until_ready(state)
            ts.append(time.perf_counter() - t0)
        modes[mode] = {
            "wall_us": float(np.min(ts)) * 1e6,
            "trace_compile_s": trace_s,
            "kernel_dispatches_per_trace":
                step.counters["runner_dispatches"],
            "acoustic_body_traces": step.counters["acoustic_traces"],
            "n_kernels": step.n_kernels,
        }
    try:
        with open(path) as f:
            payload = json.load(f)
    except (OSError, ValueError):
        payload = {}
    payload["step_dispatch"] = {
        "config": {"npx": npx, "nk": nk, "n_split": cfg.n_split,
                   "k_split": cfg.k_split, "smoke": smoke, "opt_level": 3},
        "modes": modes,
    }
    with open(path, "w") as f:
        json.dump(payload, f, indent=2)
    lines = [
        f"step_dispatch/{mode},{m['wall_us']:.0f},"
        f"dispatches={m['kernel_dispatches_per_trace']};"
        f"acoustic_traces={m['acoustic_body_traces']};"
        f"trace_s={m['trace_compile_s']:.2f}"
        for mode, m in modes.items()
    ]
    old, new = modes["unrolled"], modes["scan"]
    lines.append(
        f"step_dispatch/summary,0,"
        f"wall={old['wall_us'] / max(new['wall_us'], 1e-9):.2f}x;"
        f"dispatches={old['kernel_dispatches_per_trace']}->"
        f"{new['kernel_dispatches_per_trace']};json={path}")
    return lines


def _peak_memory_bytes():
    """Peak/live device memory and the accounting method used.

    Real accelerators expose ``device.memory_stats()['peak_bytes_in_use']``;
    the CPU backend does not, so fall back to summing the bytes of every
    live ``jax.Array`` — a *live-set* proxy (it misses XLA temporaries but
    tracks exactly the state/transient footprint chunking is meant to
    bound).  The method string is recorded next to every number so the two
    are never compared across machines."""
    import jax
    import numpy as np

    dev = jax.devices()[0]
    stats = None
    try:
        stats = dev.memory_stats()
    except (AttributeError, RuntimeError, NotImplementedError):
        pass
    if stats and "peak_bytes_in_use" in stats:
        return int(stats["peak_bytes_in_use"]), \
            "device_memory_stats.peak_bytes_in_use"
    live = sum(int(np.prod(a.shape)) * a.dtype.itemsize
               for a in jax.live_arrays())
    return int(live), "live_buffer_accounting"


def ensemble_throughput_json(path: str = "BENCH_opt_ladder.json",
                             smoke: bool = False) -> list[str]:
    """Large-ensemble scaling: members/sec of the batched step vs M with a
    chunked-vs-vmap-vs-sequential A/B, peak-memory accounting, and the
    memory-pressure-vs-dispatch-overhead diagnosis.

    Wall time comes from ``make_step_ensemble`` on the jnp backend — the
    only backend with native CPU execution here (Pallas interpret-mode wall
    time measures the interpreter, not the kernel).  Per M the batch specs
    measured are ``"vmap"`` (one batch over all M — the memory-pressure
    pole), ``"vmap:1"`` (a pure member scan — the dispatch/loop-overhead
    pole) and the hybrid chunks ``"vmap:2"`` / ``"vmap:4"`` in between.
    The chunked-step runners compile once per C (the compile memo keys on
    the chunk, not on M), so the sweep grows by compile cost O(|C|), not
    O(|M|·|C|).

    The deterministic half: the Pallas grid AND in-kernel-chunked lowerings
    of the C-grid program must report the same ``n_kernels`` at every M
    (chunking restructures the launch, never the kernel set), and the
    program-level chunk scan must report exactly ceil(M/C) chunks.  Both
    feed the CI regression gate; the wall-clock columns are informational.
    """
    import jax
    import numpy as np
    from repro.core import compile_program
    from repro.fv3.dyncore import (FV3Config, build_csw_program,
                                   make_step_ensemble)
    from repro.fv3.state import ensemble_state

    Ms = (1, 2, 4) if smoke else (1, 2, 4, 8, 16, 32, 64)
    npx, nk = (8, 4) if smoke else (16, 8)
    cfg = FV3Config(npx=npx, nk=nk, halo=6, n_split=1, k_split=1,
                    n_tracers=1)
    csw = build_csw_program(cfg, cfg.seq_dom())
    entries = []
    for M in Ms:
        reps = 3 if (smoke or M >= 16) else 6
        specs = ["vmap"]
        if M >= 4:
            specs += ["vmap:1", "vmap:2"]
        if M >= 8:
            specs += ["vmap:4"]
        runs = {}
        for spec in specs:
            step = make_step_ensemble(cfg, M, batch=spec, opt_level=3,
                                      donate=True)
            state = ensemble_state(cfg, M)
            state = step(state)                   # trace + compile + warm
            jax.block_until_ready(state)
            ts = []
            for _ in range(reps):
                t0 = time.perf_counter()
                state = step(state)
                jax.block_until_ready(state)
                ts.append(time.perf_counter() - t0)
            wall = float(np.min(ts))
            peak, method = _peak_memory_bytes()
            runs[spec] = {
                "wall_us": wall * 1e6,
                "members_per_sec": M / wall,
                "peak_memory_bytes": peak,
                "peak_memory_method": method,
                "member_chunk": step.member_chunk,
                "n_chunks": step.n_chunks,
                "step_kernels": step.n_kernels,
            }
            del state, step
        chunked = {s: r for s, r in runs.items() if ":" in s}
        best_spec = max(runs, key=lambda s: runs[s]["members_per_sec"])
        best_chunk = (max(chunked, key=lambda s: chunked[s]["members_per_sec"])
                      if chunked else None)
        # deterministic invariants (Pallas lowerings, no wall clock)
        grid_fn = compile_program(csw, "pallas-tpu", opt_level=3,
                                  n_members=M, batch="grid")
        cgrid_fn = compile_program(csw, "pallas-tpu", opt_level=3,
                                   n_members=M, batch="vmap:2,grid")
        cscan_fn = compile_program(csw, "jnp", opt_level=3,
                                   n_members=M, batch="vmap:2")
        entries.append({
            "members": M,
            "runs": runs,
            "best_batch": best_spec,
            "best_chunked_batch": best_chunk,
            "wall_us": runs[best_spec]["wall_us"],
            "members_per_sec": runs[best_spec]["members_per_sec"],
            "members_per_sec_vmap": runs["vmap"]["members_per_sec"],
            "step_kernels": runs["vmap"]["step_kernels"],
            "csw_kernels_pallas_grid": grid_fn.n_kernels,
            "csw_kernels_pallas_chunked": cgrid_fn.n_kernels,
            "chunk_scan_n_chunks": cscan_fn.n_chunks,
            "chunk_scan_n_chunks_expected": -(-M // 2) if M > 2 else None,
        })
    # -- diagnosis: which pole loses where, from the measured numbers ------
    by_m = {e["members"]: e for e in entries}

    def mps(M, spec):
        e = by_m.get(M)
        return e["runs"][spec]["members_per_sec"] if e and spec in e["runs"] \
            else None

    diagnosis = {
        "memory_pressure": {
            "claim": "full-vmap per-member throughput decays as the inner "
                     "batch widens: the working set of one fused batch "
                     "scales with M and falls out of fast memory",
            "members_per_sec_vmap_by_m": {
                str(e["members"]): round(e["members_per_sec_vmap"], 1)
                for e in entries},
        },
        "dispatch_overhead": {
            "claim": "the pure member scan (vmap:1) pays the chunk-loop "
                     "iteration overhead M times — the opposite pole also "
                     "loses, so neither extreme is the answer",
            "members_per_sec_scan_by_m": {
                str(M): round(v, 1) for M in by_m
                if (v := mps(M, "vmap:1")) is not None},
        },
        "hybrid": {
            "claim": "chunked batching (C members per scan step) bounds the "
                     "live working set at C while amortizing loop overhead "
                     "across C members",
            "best_chunked_by_m": {
                str(e["members"]): e["best_chunked_batch"]
                for e in entries if e["best_chunked_batch"]},
        },
        "kernel_count_m_invariant": all(
            e["csw_kernels_pallas_grid"] == entries[0]["csw_kernels_pallas_grid"]
            and e["csw_kernels_pallas_chunked"] == e["csw_kernels_pallas_grid"]
            for e in entries),
    }
    try:
        with open(path) as f:
            payload = json.load(f)
    except (OSError, ValueError):
        payload = {}
    payload["ensemble_throughput"] = {
        "config": {"npx": npx, "nk": nk, "n_split": cfg.n_split,
                   "k_split": cfg.k_split, "smoke": smoke, "opt_level": 3,
                   "backend_wall": "jnp"},
        "entries": entries,
        "diagnosis": diagnosis,
    }
    with open(path, "w") as f:
        json.dump(payload, f, indent=2)
    base = entries[0]
    lines = [
        f"ensemble/m{e['members']},{e['wall_us']:.0f},"
        f"members_per_sec={e['members_per_sec']:.1f};"
        f"vmap={e['members_per_sec_vmap']:.1f};best={e['best_batch']};"
        f"kernels_grid={e['csw_kernels_pallas_grid']};"
        f"kernels_chunked={e['csw_kernels_pallas_chunked']}"
        for e in entries
    ]
    top = entries[-1]
    lines.append(
        f"ensemble/scaling,0,"
        f"throughput={top['members_per_sec'] / base['members_per_sec']:.2f}x"
        f"@M={top['members']};kernels_const="
        f"{diagnosis['kernel_count_m_invariant']}")
    return lines


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None)
    ap.add_argument("--smoke", action="store_true",
                    help="fast CI mode: LoC table + backend round-trip only")
    ap.add_argument("--ladder-json", default="BENCH_opt_ladder.json",
                    help="output path for the opt-ladder perf JSON")
    args = ap.parse_args()
    failures = 0
    modules = SMOKE_MODULES if args.smoke else MODULES
    for name, modpath in modules:
        if args.only and args.only not in name:
            continue
        try:
            mod = importlib.import_module(modpath)
            for line in mod.run():
                print(line)
        except Exception:
            failures += 1
            print(f"{name}/ERROR,0,{traceback.format_exc()[-300:]!r}",
                  file=sys.stderr)
    if args.smoke and not args.only:
        try:
            for line in smoke_backend_roundtrip():
                print(line)
        except Exception:
            failures += 1
            print(f"smoke/ERROR,0,{traceback.format_exc()[-300:]!r}",
                  file=sys.stderr)
    if not args.only:
        try:
            for line in opt_ladder_json(args.ladder_json, smoke=args.smoke):
                print(line)
        except Exception:
            failures += 1
            print(f"opt_ladder/ERROR,0,{traceback.format_exc()[-300:]!r}",
                  file=sys.stderr)
        try:
            for line in step_dispatch_metric(args.ladder_json,
                                             smoke=args.smoke):
                print(line)
        except Exception:
            failures += 1
            print(f"step_dispatch/ERROR,0,{traceback.format_exc()[-300:]!r}",
                  file=sys.stderr)
        try:
            for line in nk_sweep_json(args.ladder_json, smoke=args.smoke):
                print(line)
        except Exception:
            failures += 1
            print(f"nk_sweep/ERROR,0,{traceback.format_exc()[-300:]!r}",
                  file=sys.stderr)
        try:
            for line in ensemble_throughput_json(args.ladder_json,
                                                 smoke=args.smoke):
                print(line)
        except Exception:
            failures += 1
            print(f"ensemble/ERROR,0,{traceback.format_exc()[-300:]!r}",
                  file=sys.stderr)
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
