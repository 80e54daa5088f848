"""Distributed tests run in subprocesses with fake devices (the main pytest
process keeps 1 device per the dry-run isolation rule)."""

import json
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]


def run_sub(code: str, devices: int = 24, timeout: int = 900) -> str:
    env = {"PYTHONPATH": str(ROOT / "src"),
           "XLA_FLAGS": f"--xla_force_host_platform_device_count={devices}",
           "PATH": "/usr/bin:/bin:/usr/local/bin"}
    import os
    env = {**os.environ, **env}
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=timeout, env=env)
    assert r.returncode == 0, f"stderr:\n{r.stderr[-3000:]}"
    return r.stdout


@pytest.mark.slow
def test_halo_distributed_matches_reference():
    out = run_sub("""
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from repro.fv3.topology import Decomposition
from repro.fv3.halo import exchange_reference, make_halo_exchanger
N, h, nk = 8, 3, 2
dec = Decomposition(layout=(2, 2), n_local=N // 2, halo=h)
mesh = jax.make_mesh((6, 2, 2), ("tile", "y", "x"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 3)
ex = make_halo_exchanger(dec)
rng = np.random.default_rng(0)
glob = rng.standard_normal((6, nk, N + 2 * h, N + 2 * h)).astype(np.float32)
glob[:, :, :h] = glob[:, :, -h:] = 0
glob[:, :, :, :h] = glob[:, :, :, -h:] = 0
nl = dec.n_local
blocks = np.zeros((6, 2, 2, nk, nl + 2 * h, nl + 2 * h), np.float32)
for f in range(6):
    for y in range(2):
        for x in range(2):
            blocks[f, y, x] = glob[f, :, y*nl:y*nl+nl+2*h, x*nl:x*nl+nl+2*h]
def run(b):
    def inner(lb):
        lb = lb.reshape(nk, nl + 2 * h, nl + 2 * h)
        return ex({"q": lb})["q"].reshape(1, 1, 1, nk, nl+2*h, nl+2*h)
    return jax.shard_map(inner, mesh=mesh, in_specs=P("tile", "y", "x"),
                     out_specs=P("tile", "y", "x"))(b)
res = np.asarray(jax.jit(run)(jnp.asarray(blocks)))
refg = np.asarray(exchange_reference({"q": jnp.asarray(glob)}, h)["q"])
refb = np.zeros_like(blocks)
for f in range(6):
    for y in range(2):
        for x in range(2):
            refb[f, y, x] = refg[f, :, y*nl:y*nl+nl+2*h, x*nl:x*nl+nl+2*h]
err = np.abs(res - refb).max()
assert err < 1e-6, err
print("HALO_OK", err)
""")
    assert "HALO_OK" in out


@pytest.mark.slow
def test_dycore_distributed_matches_sequential():
    out = run_sub("""
import numpy as np, jax
from repro.fv3.dyncore import FV3Config, make_step_sequential, make_step_distributed
from repro.fv3.state import init_state, blocks_from_global, global_from_blocks
cfg = FV3Config(npx=12, nk=2, halo=6, layout=(2, 2), n_split=1, k_split=1,
                n_tracers=1)
state = init_state(cfg)
s_seq = make_step_sequential(cfg)(state)
mesh = jax.make_mesh((6, 2, 2), ("tile", "y", "x"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 3)
blocks = blocks_from_global(state, cfg)
b = make_step_distributed(cfg, mesh)(blocks)
s_dist = global_from_blocks({k: np.asarray(v) for k, v in b.items()}, cfg)
h, N = cfg.halo, cfg.npx
I = np.s_[:, :, h:h+N, h:h+N]
for k in s_dist:
    err = np.abs(np.asarray(s_seq[k])[I] - s_dist[k][I]).max()
    assert err < 1e-5, (k, err)
print("DIST_OK")
""")
    assert "DIST_OK" in out


@pytest.mark.slow
def test_dycore_distributed_opt4_drops_delpc_exchange_bitwise():
    """opt_level=4's recompute-vs-exchange rewrite widens c_sw so delpc is
    valid on a one-cell rim and drops the per-substep delpc exchange —
    bit-identical to the opt_level=3 step, with the step reporting the
    rewrite applied."""
    out = run_sub("""
import numpy as np, jax
from repro.fv3.dyncore import FV3Config, make_step_distributed
from repro.fv3.state import init_state, blocks_from_global
cfg = FV3Config(npx=12, nk=2, halo=6, layout=(2, 2), n_split=2, k_split=1,
                n_tracers=1)
mesh = jax.make_mesh((6, 2, 2), ("tile", "y", "x"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 3)
blocks = blocks_from_global(init_state(cfg), cfg)
step3 = make_step_distributed(cfg, mesh, overlap=False, opt_level=3)
step4 = make_step_distributed(cfg, mesh, overlap=False, opt_level=4)
assert step3.delpc_exchange_skipped is False
assert step4.delpc_exchange_skipped is True
b3, b4 = step3(blocks), step4(blocks)
for k in b3:
    assert np.array_equal(np.asarray(b3[k]), np.asarray(b4[k])), k
print("OPT4_DIST_OK")
""")
    assert "OPT4_DIST_OK" in out


@pytest.mark.slow
def test_halo_exchanger_carries_leading_member_dim():
    """The ppermute rounds are leading-dim agnostic: a batched exchange of
    (M, nk, nl+2h, nl+2h) local blocks is bit-identical to M per-member
    exchanges — the property the batched ensemble step rests on."""
    out = run_sub("""
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from repro.fv3.topology import Decomposition
from repro.fv3.halo import make_halo_exchanger
N, h, nk, M = 8, 3, 2, 3
dec = Decomposition(layout=(2, 2), n_local=N // 2, halo=h)
mesh = jax.make_mesh((6, 2, 2), ("tile", "y", "x"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 3)
ex = make_halo_exchanger(dec)
nl = dec.n_local
rng = np.random.default_rng(0)
blocks = rng.standard_normal((M, 6, 2, 2, nk, nl+2*h, nl+2*h)).astype(np.float32)
def run_batched(b):
    def inner(lb):
        lb = lb.reshape(M, nk, nl+2*h, nl+2*h)
        return ex({"q": lb})["q"].reshape(1, 1, 1, M, nk, nl+2*h, nl+2*h)
    return jax.shard_map(inner, mesh=mesh, in_specs=P(None, "tile", "y", "x"),
                     out_specs=P("tile", "y", "x", None))(b)
def run_single(b):
    def inner(lb):
        lb = lb.reshape(nk, nl+2*h, nl+2*h)
        return ex({"q": lb})["q"].reshape(1, 1, 1, nk, nl+2*h, nl+2*h)
    return jax.shard_map(inner, mesh=mesh, in_specs=P("tile", "y", "x"),
                     out_specs=P("tile", "y", "x"))(b)
res_b = np.moveaxis(np.asarray(jax.jit(run_batched)(jnp.asarray(blocks))), 3, 0)
res_s = np.stack([np.asarray(jax.jit(run_single)(jnp.asarray(blocks[m])))
                  for m in range(M)])
assert np.array_equal(res_b, res_s)
print("BATCHED_HALO_OK")
""")
    assert "BATCHED_HALO_OK" in out


@pytest.mark.slow
def test_member_sharded_matches_unsharded():
    """Ensembles shard across devices on a leading "member" mesh axis,
    orthogonally to the tile/y/x decomposition: every member of the
    member-sharded distributed step must match the unsharded sequential
    step on that member's initial state."""
    out = run_sub("""
import numpy as np, jax
from repro.fv3.dyncore import FV3Config, make_step_sequential, make_step_distributed
from repro.fv3.state import ensemble_state, blocks_from_global, global_from_blocks
cfg = FV3Config(npx=12, nk=2, halo=6, layout=(1, 1), n_split=1, k_split=1,
                n_tracers=1)
M = 2
ens0 = ensemble_state(cfg, M)
mesh = jax.make_mesh((M, 6, 1, 1), ("member", "tile", "y", "x"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 4)
blocks = {}
for m in range(M):
    bm = blocks_from_global({k: v[m] for k, v in ens0.items()}, cfg)
    for k, v in bm.items():
        blocks.setdefault(k, []).append(np.asarray(v))
blocks = {k: jax.numpy.asarray(np.stack(v)) for k, v in blocks.items()}
out_b = make_step_distributed(cfg, mesh, member_axis="member")(blocks)
step_s = make_step_sequential(cfg)
h, N = cfg.halo, cfg.npx
I = np.s_[:, :, h:h+N, h:h+N]
for m in range(M):
    ref = step_s({k: v[m] for k, v in ens0.items()})
    got = global_from_blocks({k: np.asarray(v[m]) for k, v in out_b.items()}, cfg)
    for k in got:
        err = np.abs(np.asarray(ref[k])[I] - got[k][I]).max()
        assert err < 1e-5, (m, k, err)
print("MEMBER_SHARD_OK")
""", devices=12)
    assert "MEMBER_SHARD_OK" in out


@pytest.mark.slow
def test_lm_sharded_loss_matches_single_device():
    """Distributed loss (8 fake devices, (2,4)=data×model mesh) must equal
    the single-device loss — sharding is layout, not math."""
    code = """
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.configs import smoke_config
from repro.models import transformer as T
from repro.parallel.sharding import init_params, param_shardings
cfg = smoke_config("granite_8b")
defs = T.model_pdefs(cfg)
params = init_params(defs, jax.random.PRNGKey(0))
tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 64), 0, cfg.vocab)
labels = jax.random.randint(jax.random.PRNGKey(2), (4, 64), 0, cfg.vocab)
l_single = float(T.loss_fn(params, tokens, labels, cfg, dtype=jnp.float32))
mesh = jax.make_mesh((2, 4), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
shards = param_shardings(defs, mesh)
p_sh = jax.device_put(params, shards)
t_sh = jax.device_put(tokens, NamedSharding(mesh, P("data", None)))
y_sh = jax.device_put(labels, NamedSharding(mesh, P("data", None)))
with mesh:
    l_dist = float(jax.jit(
        lambda p, t, y: T.loss_fn(p, t, y, cfg, dtype=jnp.float32)
    )(p_sh, t_sh, y_sh))
assert abs(l_single - l_dist) < 1e-3, (l_single, l_dist)
print("LOSS_OK", l_single, l_dist)
"""
    out = run_sub(code, devices=8)
    assert "LOSS_OK" in out
