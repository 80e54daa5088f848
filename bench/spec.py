"""Everything the harness reads from data: the benchmark file, the cell,
its configuration and traffic, the limits of its check, the byte counts,
the table of peaks, and the metric readers, each found by name."""

from __future__ import annotations

import importlib.util
import json
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


class SpecError(Exception):
    """The benchmark's data does not name what was asked for."""


def _load(path: Path) -> dict:
    try:
        return json.loads(path.read_text())
    except FileNotFoundError:
        raise SpecError(f"missing {path.relative_to(ROOT)}") from None


def benchmark() -> dict:
    return _load(ROOT / "BENCHMARK.json")


def cell(spec: dict, name: str) -> dict:
    for w in spec["workloads"]:
        if w["name"] == name:
            return w
    raise SpecError(f"no workload {name!r} in BENCHMARK.json")


def config(spec: dict, name: str) -> dict:
    for c in spec["configs"]:
        if c["name"] == name:
            return _load(ROOT / c["file"])
    raise SpecError(f"no config {name!r} in BENCHMARK.json")


def traffic(name: str) -> dict:
    return _load(BENCH / "workloads" / f"{name}.json")


def limits(cell_name: str) -> dict:
    """Limits of the numbers the check compares, for this cell."""
    return _load(BENCH / "limits" / f"{cell_name}.json")


def counts() -> dict:
    """Byte counts of each program, keyed by the program's own name, and
    the step's structure (``bench/counts/step.json``)."""
    out = {}
    for p in sorted((BENCH / "counts").glob("*.json")):
        d = _load(p)
        out[d["program"]] = d
    return out


def peaks(device_kind: str) -> dict:
    table = _load(BENCH / "peaks.json")
    if device_kind not in table["devices"]:
        raise SpecError(f"no peaks for device kind {device_kind!r} in "
                        "bench/peaks.json")
    return table["devices"][device_kind]


def metrics(spec: dict, cell_name: str, traced: bool) -> list[dict]:
    """The metrics this cell reports: its end-to-end metrics untraced, its
    per-layer metrics traced (a metric with ``workloads`` only in those)."""
    group = spec["per_layer" if traced else "end_to_end"]
    return [m for m in group
            if cell_name in m.get("workloads", [cell_name])]


def reader(name: str):
    """The ``read(record)`` function of ``bench/metrics/<name>.py``."""
    path = BENCH / "metrics" / f"{name}.py"
    if not path.is_file():
        raise SpecError(f"no reader bench/metrics/{name}.py")
    mod_spec = importlib.util.spec_from_file_location(
        "metric_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod.read
