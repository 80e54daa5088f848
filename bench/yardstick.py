"""Lower bounds on the HBM bytes a program call, a halo exchange and a
whole physics step must move, from the byte counts in ``bench/counts``.

A program must read each boundary field it reads and write each one it
writes, at least once, over the tile interiors: ``6 * nk * npx**2``
elements a member (``nk + 1`` levels for a K-interface field).  A halo
exchange must read each ghost cell's source and write the ghost cell.
Transient fields, which fusion may keep on chip, count nothing."""

from __future__ import annotations

import numpy as np


def _field_elems(cfg: dict, interface: bool) -> int:
    return 6 * (cfg["nk"] + int(interface)) * cfg["npx"] ** 2


def expand(names, cfg: dict) -> list[str]:
    """Field names with ``tracers`` standing for the configuration's."""
    out = []
    for n in names:
        out.extend(cfg["tracers"] if n == "tracers" else [n])
    return out


def program_bytes(cfg: dict, count: dict) -> int:
    item = np.dtype(cfg["dtype"]).itemsize
    total = 0
    for f, c in count["fields"].items():
        moves = int(c["read"]) + int(c["write"])
        total += moves * _field_elems(cfg, c.get("interface", False))
    return total * item * cfg["members"]


def exchange_bytes(cfg: dict, n_fields: int) -> int:
    """Ghost cells of ``n_fields`` fields, each read at its source and
    written once."""
    n, h = cfg["npx"], cfg["halo"]
    ghosts = 6 * cfg["nk"] * ((n + 2 * h) ** 2 - n ** 2)
    item = np.dtype(cfg["dtype"]).itemsize
    return 2 * ghosts * n_fields * item * cfg["members"]


def lower_bound_bytes(cfg: dict, traffic: dict, counts: dict) -> dict:
    """{"per_call": {program or "halo_exchange": bytes}, "per_step": bytes}
    for the step's structure in ``bench/counts/step.json`` and the
    traffic's splitting namelist."""
    per_call = {name: program_bytes(cfg, c) for name, c in counts.items()
                if "fields" in c and isinstance(c["fields"], dict)}
    halo = counts["halo_exchange"]
    per_call["halo_exchange"] = exchange_bytes(cfg, len(halo["fields"]))
    nl = traffic["namelist"]
    step = counts["step"]
    total = 0
    for loop, repeat in (("acoustic_substep", nl["n_split"] * nl["k_split"]),
                         ("remap_iteration", nl["k_split"])):
        part = step[loop]
        one = sum(per_call[p] for p in part["programs"])
        one += exchange_bytes(cfg, len(expand(part["exchanged"], cfg)))
        total += repeat * one
    return {"per_call": per_call, "per_step": total}


def ops_per_byte(cfg: dict, counts: dict) -> dict:
    """Hand-counted operations of one call over its lower-bound bytes."""
    out = {}
    for name, c in counts.items():
        if "ops_per_point" in c:
            ops = c["ops_per_point"] * _field_elems(cfg, False) \
                * cfg["members"]
            out[name] = ops / program_bytes(cfg, c)
    return out
