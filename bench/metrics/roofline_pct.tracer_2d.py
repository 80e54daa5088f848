"""Roofline share of the program ``tracer_2d`` called alone (device trace)."""

import importlib.util
from pathlib import Path

_spec = importlib.util.spec_from_file_location(
    "metric_roofline", Path(__file__).with_name("_roofline.py"))
_mod = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_mod)


def read(record):
    return _mod.roofline_pct(record, "tracer_2d")
