"""Persistent compilation/tuning cache (paper §VI-B workflow support).

Schedule search is pure function of (stencil IR, domain, backend, hardware),
so its results are cached on disk and survive process restarts: a second
``autotune.tune_stencil`` or ``transfer_tuning.tune_cutouts`` run with the
same inputs skips the search entirely.  Keys are content hashes —
``(stencil fingerprint, schedule, backend name, hardware name)`` — never
object identities, so entries are valid across runs and machines.  Writes
re-read and merge the on-disk state first, so concurrent processes append
rather than clobber (last writer wins only on the same key).

The store is a single JSON file (default ``.repro_cache/tuning.json`` in
the checkout, overridable via ``$REPRO_CACHE_DIR`` or
``set_default_cache``), written atomically.  Hit/miss counters make cache
behavior observable in tests and benchmarks.

:func:`enable_compile_cache` turns on JAX's persistent compilation cache at
a fixed path of the same checkout.
"""

from __future__ import annotations

import dataclasses
import hashlib
import json
import os
import tempfile
from pathlib import Path
from typing import Any

from ..stencil.ir import Stencil
from ..stencil.schedule import Schedule

_CACHE_VERSION = 1

#: root of the checkout this package is imported from (``src/repro/core/
#: backend/`` → root): both caches live there, wherever the process runs
CHECKOUT = Path(__file__).resolve().parents[4]

#: Version of the analytical cost/schedule model.  Folded into every tuning
#: key by tune_stencil / tune_cutouts — bump it whenever ``model_cost``,
#: ``node_bound_seconds``, schedule enumeration or the fusion transforms
#: change behavior, so persisted results from the old model are never
#: served for the new one.  (v4: K-interface fields — per-field extents in
#: vmem_footprint/node_bytes and whole-K-only schedules for staggered
#: stencils.  v5: sequential-K — K-blocked marching schedules for vertical
#: solvers with carry-plane footprints, whole-column VMEM feasibility
#: enforced in model_cost, and level-search marching FLOPs in node_flops.
#: v6: ensemble axis — model_cost takes n_members and amortizes the
#: per-launch overhead across the member grid dimension; tuning keys carry
#: n_members.  v7: hybrid member chunking — model_cost/vmem_footprint take
#: member_chunk, launch terms count ceil(M/C) chunk steps instead of M,
#: feasibility prices C-member blocks, and tuning keys carry the chunk.
#: v8: rewrite engine — opt_level 4 rewrites (stencil-combine, cross-
#: computation CSE) reshape stencil bodies before tuning, so fingerprints
#: of tuned stencils and the footprints the model prices both change.
#: v9: VMEM footprints count the blocks the Pallas lowering allocates —
#: halo-padded planes rounded to (sublane, lane), double-buffered
#: pipelined blocks — and J-tiled whole-column schedules.)
COST_MODEL_VERSION = 9


def stencil_fingerprint(stencil: Stencil) -> str:
    """Content hash of a stencil's IR (name, signature, computations).

    All IR nodes have deterministic reprs (frozen dataclasses / custom
    ``__repr__``), so the repr of the computation tuple is a canonical
    serialization of the algorithm.
    """
    payload = "|".join([
        stencil.name,
        ",".join(stencil.fields),
        ",".join(stencil.outputs),
        ",".join(stencil.params),
        ",".join(stencil.interface_fields),
        repr(stencil.computations),
    ])
    return hashlib.sha256(payload.encode()).hexdigest()[:32]


def make_key(*parts: Any) -> str:
    """Stable hash of arbitrary JSON-encodable key parts."""
    def norm(p):
        if isinstance(p, Stencil):
            return stencil_fingerprint(p)
        if isinstance(p, Schedule):
            return p.to_dict()
        if dataclasses.is_dataclass(p) and not isinstance(p, type):
            return dataclasses.asdict(p)
        if isinstance(p, (tuple, list)):
            return [norm(x) for x in p]
        return p

    blob = json.dumps([norm(p) for p in parts], sort_keys=True, default=str)
    return hashlib.sha256(blob.encode()).hexdigest()[:32]


@dataclasses.dataclass
class CacheStats:
    hits: int = 0
    misses: int = 0
    puts: int = 0

    def as_dict(self) -> dict:
        return dataclasses.asdict(self)

    def reset(self) -> None:
        self.hits = self.misses = self.puts = 0


class TuningCache:
    """On-disk key→JSON store with hit/miss accounting."""

    def __init__(self, path: str | os.PathLike | None = None):
        if path is None:
            root = os.environ.get("REPRO_CACHE_DIR",
                                  CHECKOUT / ".repro_cache")
            path = os.path.join(root, "tuning.json")
        self.path = Path(path)
        if self.path.is_dir():
            self.path = self.path / "tuning.json"
        self.stats = CacheStats()
        self._data: dict[str, Any] | None = None

    # -- persistence ---------------------------------------------------------
    def _read_disk(self) -> dict[str, Any]:
        try:
            raw = json.loads(self.path.read_text())
            if raw.get("version") == _CACHE_VERSION:
                return raw.get("entries", {})
        except (OSError, ValueError):
            pass
        return {}

    def _load(self) -> dict[str, Any]:
        if self._data is None:
            self._data = self._read_disk()
        return self._data

    def _persist(self) -> None:
        # the cache is a pure optimization: any write failure (read-only
        # checkout, unwritable $REPRO_CACHE_DIR) degrades to uncached
        tmp = None
        try:
            self.path.parent.mkdir(parents=True, exist_ok=True)
            # merge over the latest on-disk state: another process may have
            # added entries since we loaded; don't clobber them
            merged = self._read_disk()
            merged.update(self._data or {})
            self._data = merged
            blob = json.dumps({"version": _CACHE_VERSION, "entries": merged},
                              indent=0)
            fd, tmp = tempfile.mkstemp(dir=self.path.parent,
                                       prefix=self.path.name, suffix=".tmp")
            with os.fdopen(fd, "w") as f:
                f.write(blob)
            os.replace(tmp, self.path)
        except OSError:
            if tmp is not None:
                try:
                    os.unlink(tmp)
                except OSError:
                    pass

    # -- API -----------------------------------------------------------------
    def get(self, key: str) -> Any | None:
        val = self._load().get(key)
        if val is None:
            self.stats.misses += 1
        else:
            self.stats.hits += 1
        return val

    def put(self, key: str, value: Any) -> None:
        self._load()[key] = value
        self.stats.puts += 1
        self._persist()

    def clear(self) -> None:
        self._data = {}
        try:
            self.path.unlink()
        except OSError:
            pass

    def __len__(self) -> int:
        return len(self._load())


_default_cache: TuningCache | None = None


def default_cache() -> TuningCache:
    global _default_cache
    if _default_cache is None:
        _default_cache = TuningCache()
    return _default_cache


def set_default_cache(cache: TuningCache | None) -> None:
    """Swap the process-wide cache (tests point it at a tmp path)."""
    global _default_cache
    _default_cache = cache


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache before the first compile
    and return its directory.  Where ``$JAX_COMPILATION_CACHE_DIR`` is set,
    JAX already reads it and nothing is set here; otherwise the cache is
    ``.jax_cache`` in the checkout — a fixed path, since the path is part
    of the cache's key."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    path = str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
