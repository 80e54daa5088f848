"""The comparison that decides ``correct``: the states the timed step
produced in its first steps against the plain reference stepped from the
same initial state, field by field on the tile interiors."""

from __future__ import annotations

import numpy as np


def interior(a, halo: int) -> np.ndarray:
    n = a.shape[-1] - 2 * halo
    return np.asarray(a)[..., halo:halo + n, halo:halo + n]


def field_error(got: np.ndarray, ref: np.ndarray) -> float:
    """RMS of ``got - ref`` over the standard deviation of ``ref``: a
    field's constant offset hides nothing, and an error spread over the
    sphere weighs more than a few flipped limiter points.  Non-finite
    values read infinite."""
    got = got.astype(np.float64)
    ref = ref.astype(np.float64)
    if not (np.isfinite(got).all() and np.isfinite(ref).all()):
        return float("inf")
    d = got - ref
    return float(np.sqrt(np.mean(d * d)) / (ref.std() or 1.0))


def worst_error(got: dict, ref: dict, halo: int) -> tuple[float, str]:
    """Largest per-field, per-member error; ``got``/``ref`` hold
    (members, 6, nk, P, P) arrays.  Returns (error, "field[member]")."""
    worst, where = -1.0, ""
    for f in sorted(ref):
        for m in range(ref[f].shape[0]):
            e = field_error(interior(got[f][m], halo),
                            interior(ref[f][m], halo))
            if e > worst:
                worst, where = e, f"{f}[{m}]"
    return worst, where


def judge(numbers: dict, limits: dict) -> tuple[bool, dict]:
    """Each compared number beside its limit; correct when every number
    is finite and at most its limit, and no limit is missing."""
    checks, ok = {}, True
    for name, value in numbers.items():
        limit = limits.get(name, {}).get("limit")
        good = limit is not None and np.isfinite(value) and value <= limit
        ok = ok and good
        checks[name] = {"value": value, "limit": limit}
    return ok, checks


def host_interiors(jax, state: dict, halo: int, has_members: bool) -> dict:
    """A host copy of every field's tile interiors, with a leading member
    axis (added where the arrays have none)."""
    out = {}
    for k, v in state.items():
        a = jax.device_get(v)
        a = a if has_members else a[None]
        out[k] = a[..., halo:a.shape[-1] - halo, halo:a.shape[-1] - halo]
    return out


def reference_states(jax, ref_step, init: dict, n: int, halo: int,
                     dtype=None) -> list[dict]:
    """Host interiors after each of ``n`` reference steps from ``init``
    ((members, ...) arrays), stepped member by member so that one member's
    working set is on the device at a time; computed in ``dtype`` where
    given (the control), read back as float32."""
    members = next(iter(init.values())).shape[0]
    out = [{} for _ in range(n)]
    for m in range(members):
        s = {k: v[m] if dtype is None else v[m].astype(dtype)
             for k, v in init.items()}
        for i in range(n):
            s = ref_step(s)
            for k, v in host_interiors(jax, s, halo, False).items():
                out[i].setdefault(k, []).append(v[0].astype(np.float32))
    return [{k: np.stack(v) for k, v in r.items()} for r in out]
