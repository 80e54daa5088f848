"""Initial conditions from a traffic file and a seed, made on the device.

The arithmetic of the dycore's zonal-flow test case (solid-body rotation
projected on each face, a stratified temperature and thickness with a
pole-to-equator gradient, a Gaussian bump, Gaussian tracer blobs), with
every coefficient read from the traffic file, plus a perturbation of the
named fields' tile interiors drawn from the seed: one draw per member.
"""

from __future__ import annotations

import functools
import json

import jax
import jax.numpy as jnp
import numpy as np

from references.fv3lite import FACES


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from all 64 low bits of ``seed`` (jax.random.key keeps
    only 32 of them)."""
    s = int(seed) % (1 << 64)
    return jax.random.wrap_key_data(
        np.array([s >> 32, s & 0xFFFFFFFF], np.uint32))


def _centers(f: int, i, j, n: int):
    """Unit-sphere centres of cells (i, j) of face f."""
    nrm, ex, ey = (jnp.asarray(x, jnp.float32) for x in FACES[f])
    a = (jnp.asarray(i, jnp.float32) + 0.5) / n - 0.5
    b = (jnp.asarray(j, jnp.float32) + 0.5) / n - 0.5
    p = 0.5 * nrm + a[..., None] * ex + b[..., None] * ey
    return p / jnp.linalg.norm(p, axis=-1, keepdims=True)


def _member(cfg: dict, ic: dict, tracers: tuple):
    n, h, nk = cfg["npx"], cfg["halo"], cfg["nk"]
    omega = np.asarray(ic["rotation_axis"], np.float64)
    omega = jnp.asarray(ic["rotation_speed"] * omega / np.linalg.norm(omega),
                        jnp.float32)
    jj, ii = jnp.meshgrid(jnp.arange(n), jnp.arange(n), indexing="ij")
    kprof = ((jnp.arange(nk, dtype=jnp.float32) + 0.5) / nk)[:, None, None]
    bump = ic["bump"]
    bump_c = _centers(bump["tile"], n // 2, n // 2, n)
    fields = {k: [] for k in ("delp", "pt", "w", "u", "v", *tracers)}
    for f in range(6):
        _, ex, ey = (jnp.asarray(x, jnp.float32) for x in FACES[f])
        p = _centers(f, ii, jj, n)                     # (j, i, 3)
        vel = jnp.cross(jnp.broadcast_to(omega, p.shape), p)
        z = p[..., 2]
        pt0 = 1.0 + ic["pt_pole_gradient"] * z ** 2
        delp0 = 1.0 + ic["delp_pole_gradient"] * (1.0 - z ** 2)
        d2 = ((p - bump_c) ** 2).sum(-1)
        hump = bump["amplitude"] * jnp.exp(-d2 / bump["width"])
        ones = jnp.ones((nk, 1, 1), jnp.float32)
        fields["u"].append(ones * (vel @ ex))
        fields["v"].append(ones * (vel @ ey))
        fields["w"].append(jnp.zeros((nk, n, n), jnp.float32))
        fields["pt"].append(pt0 * (1.0 + ic["pt_lapse"] * kprof) + hump)
        fields["delp"].append(delp0 * (ic["delp_base"] + ic["delp_lapse"] * kprof))
        for t, q in enumerate(tracers):
            c = _centers(t % 6, n // 3, n // 3, n)
            d2q = ((p - c) ** 2).sum(-1)
            fields[q].append(ones * jnp.exp(-d2q / ic["tracer_width"]))
    pad = ((0, 0), (0, 0), (h, h), (h, h))
    return {k: jnp.pad(jnp.stack(v), pad) for k, v in fields.items()}


@functools.partial(jax.jit, static_argnums=(0, 1, 2, 3))
def _initial(cfg_items, ic_json, tracers, members, key):
    cfg, ic = dict(cfg_items), json.loads(ic_json)
    base = _member(cfg, ic, tracers)
    h, n = cfg["halo"], cfg["npx"]
    mask = np.zeros(base["delp"].shape, np.float32)
    mask[..., h:h + n, h:h + n] = 1.0
    out = {}
    for i, (k, v) in enumerate(sorted(base.items())):
        v = jnp.broadcast_to(v, (members,) + v.shape)
        pert = ic["perturbation"]
        if k in pert["fields"]:
            noise = jax.random.normal(jax.random.fold_in(key, i), v.shape,
                                      jnp.float32)
            v = v + pert["amplitude"] * noise * mask
        out[k] = v.astype(cfg["dtype"])
    return out


def initial_state(cfg: dict, traffic: dict, seed: int) -> dict:
    """State dict of ``(members, 6, nk, npx+2h, npx+2h)`` arrays in one
    jitted call; halos zero (the first step's exchange fills them)."""
    keys = ("npx", "nk", "halo", "dtype")
    return _initial(tuple((k, cfg[k]) for k in keys),
                    json.dumps(traffic["initial_condition"], sort_keys=True),
                    tuple(cfg["tracers"]), int(cfg["members"]),
                    seed_key(seed))
