"""End-to-end FV3-lite driver (the paper's kind of workload).

Initializes the baroclinic-style test case on the cubed sphere, runs
physics steps with the orchestrated dycore, checkpoints atomically every
few steps, and demonstrates crash-restart (restore + deterministic resume).

``--members M`` (M > 1) switches to the canonical NWP production workload:
an M-member perturbed ensemble stepped as ONE batched program
(``make_step_ensemble`` — member axis through the compiler, batched halo
exchange, one jitted dispatch for the whole ensemble), with the ensemble
spread printed alongside the control member's diagnostics.

``--batch`` picks the member lowering (chunk-spec grammar, e.g. ``vmap``,
``vmap:4``, ``vmap:4,grid``, ``vmap:auto``): large ensembles stream through
the step C members at a time instead of materializing one M-wide batch,
and the driver prints the chunk plan plus per-chunk live memory and
throughput.

Run:  PYTHONPATH=src python examples/fv3_simulation.py [--steps 6] \\
          [--members 16] [--batch vmap:4,grid]
"""

import argparse
import time

import numpy as np
import jax

from repro.core import enable_compile_cache
from repro.fv3.dyncore import FV3Config, make_step_ensemble, make_step_sequential
from repro.fv3.state import ensemble_state, init_state, total_mass
from repro.train.checkpoint import (latest_step, restore_checkpoint,
                                    save_checkpoint)


def diagnostics(state, cfg, step, m0):
    h, N = cfg.halo, cfg.npx
    members = None
    if np.asarray(state["u"]).ndim == 5:      # (M, 6, nk, J, I) ensemble
        members = state
        state = {k: v[0] for k, v in state.items()}   # control member
    I = np.s_[:, :, h:h + N, h:h + N]
    u = np.asarray(state["u"])[I]
    w = np.asarray(state["w"])[I]
    m = total_mass(state, cfg)
    line = (f"step {step:3d}  |u|max={np.abs(u).max():.4f}  "
            f"|w|max={np.abs(w).max():.4f}  mass drift={abs(m - m0) / m0:.2e}")
    if members is not None:
        pt = np.asarray(members["pt"])[:, :, :, h:h + N, h:h + N]
        spread = pt.std(axis=0).max()
        line += f"  ens spread(pt)={spread:.2e} (M={pt.shape[0]})"
    print(line)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=6)
    ap.add_argument("--npx", type=int, default=24)
    ap.add_argument("--nk", type=int, default=8)
    ap.add_argument("--opt-level", type=int, default=3,
                    help="automatic optimization ladder (0-4)")
    ap.add_argument("--members", type=int, default=1,
                    help="ensemble members (>1: batched ensemble step)")
    ap.add_argument("--batch", default=None,
                    help="member batch spec for --members>1 (chunk-spec "
                         "grammar: vmap | grid | vmap:C | vmap:C,grid | "
                         "grid:C | vmap:auto); default: backend's choice")
    ap.add_argument("--ckpt", default="/tmp/fv3_ckpt")
    args = ap.parse_args()
    enable_compile_cache()

    cfg = FV3Config(npx=args.npx, nk=args.nk, halo=6, n_split=2, k_split=1)
    # donate=True: this driver only ever chains state = step_fn(state), the
    # donation-safe steady-state pattern (a no-op on CPU)
    if args.members > 1:
        kw = {"batch": args.batch} if args.batch else {}
        step_fn = make_step_ensemble(cfg, args.members,
                                     opt_level=args.opt_level, donate=True,
                                     **kw)
        state = ensemble_state(cfg, args.members)
        m0 = total_mass({k: v[0] for k, v in state.items()}, cfg)
        ens = f", {args.members}-member ensemble (batch={step_fn.batch})"
        if step_fn.member_chunk:
            n_chunks = step_fn.n_chunks or -(-args.members
                                             // step_fn.member_chunk)
            ens += (f", chunked {step_fn.member_chunk} members/chunk × "
                    f"{n_chunks} chunks")
    else:
        step_fn = make_step_sequential(cfg, opt_level=args.opt_level,
                                      donate=True)
        state = init_state(cfg)
        m0 = total_mass(state, cfg)
        ens = ""
    print(f"FV3-lite: c{cfg.npx} × {cfg.nk} levels, 6 tiles, "
          f"n_split={cfg.n_split}, k_split={cfg.k_split}{ens}")
    # the whole step (acoustic scan + tracer + compiled vertical remap) is
    # one jitted dispatch; opt_report covers every program in the ladder
    for name, rep in step_fn.opt_report.items():
        kerns = (f"{rep.kernels_before}->{rep.kernels_after}"
                 if rep is not None else "untransformed")
        print(f"  {name:16s} kernels {kerns}")
    print(f"  single-dispatch step: {step_fn.n_kernels} compiled kernels "
          f"behind one jit")

    t0 = time.perf_counter()
    for i in range(args.steps // 2):
        state = step_fn(state)
        diagnostics(state, cfg, i + 1, m0)
        if (i + 1) % 2 == 0:
            save_checkpoint(args.ckpt, i + 1, state, async_mode=True)

    # simulate a crash → restore from the latest checkpoint and resume
    last = latest_step(args.ckpt)
    if last is not None:
        print(f"-- simulated restart from checkpoint step {last} --")
        state, manifest = restore_checkpoint(args.ckpt, state)
    for i in range(args.steps // 2, args.steps):
        state = step_fn(state)
        diagnostics(state, cfg, i + 1, m0)
    dt = time.perf_counter() - t0
    dev = jax.devices()[0]
    print(f"done: {args.steps} physics steps in {dt:.1f}s "
          f"({dt / args.steps * 1e3:.0f} ms/step on {dev.platform} "
          f"{dev.device_kind!r}, compile and checkpoints included)")
    if args.members > 1:
        # chunk-plan report: live state bytes, the per-chunk working set the
        # chunked lowering bounds, and ensemble throughput.  Real
        # accelerators report device_memory_stats(); the CPU backend falls
        # back to live-buffer accounting over the ensemble state.
        state_bytes = sum(int(np.prod(v.shape)) * v.dtype.itemsize
                          for v in jax.tree_util.tree_leaves(state))
        C = step_fn.member_chunk or args.members
        n_chunks = step_fn.n_chunks or 1
        per_chunk = state_bytes * C // args.members
        print(f"ensemble: {args.members / (dt / args.steps):.1f} members/sec"
              f"  state={state_bytes / 2**20:.1f} MiB"
              f"  per-chunk working set={per_chunk / 2**20:.1f} MiB"
              f"  ({C} members/chunk × {n_chunks} chunks)")


if __name__ == "__main__":
    main()
