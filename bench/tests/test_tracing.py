"""The reduction from trace events to the per-layer numbers: busy union,
Mosaic against XLA time, probe modules, on a hand-made trace and on a
trace recorded on the chip."""

import gzip
import json
from pathlib import Path

import pytest

import tracing

FIXTURE = Path(__file__).with_name("fixtures") / "trace_c128_l80.json.gz"


def _events():
    mods = [["jit__inner(1)", 0, 100, ""], ["jit__inner(1)", 120, 100, ""],
            ["jit_bench_probe_d_sw(2)", 300, 10, ""],
            ["jit_bench_probe_d_sw(2)", 320, 14, ""]]
    ops = [["while.4", 0, 90, ""],                    # encloses the next
           ["fusion.1", 0, 30, ""],
           ["run.3", 20, 40, "mosaic"],                # overlaps fusion.1
           ["copy.2", 70, 20, ""],
           ["run.3", 120, 100, "mosaic"],
           ["fusion.9", 300, 10, ""]]
    return {"devices": {"/device:TPU:0": {"XLA Modules": mods,
                                          "XLA Ops": ops}},
            "host": []}


def test_leaves_drop_enclosing_ops():
    ops = [["while", 0, 100, ""], ["a", 0, 10, ""], ["b", 20, 10, ""],
           ["c", 95, 10, ""]]
    assert [o[0] for o in tracing.leaves(ops)] == ["a", "b", "c"]


def test_union_and_subtract():
    assert tracing.union([(5, 9), (0, 3), (2, 4)]) == [(0, 4), (5, 9)]
    assert tracing.subtract([(0, 10), (20, 30)], [(2, 4), (8, 25)]) == \
        [(0, 2), (4, 8), (25, 30)]


def test_reduce_hand_made_trace():
    r = tracing.reduce(_events(), steps=2)
    assert r["window_s"] == pytest.approx(220e-9)
    # busy: [0, 60) + [70, 90) + [120, 220)
    assert r["busy_s"] == pytest.approx(180e-9)
    assert r["mosaic_s"] == pytest.approx(140e-9)
    assert r["xla_s"] == pytest.approx(40e-9)
    assert r["probes"] == {"d_sw": {"calls": 2, "device_s":
                                    pytest.approx(24e-9)}}
    gaps = dict((n, t) for n, t in r["breakdown"]["idle_gaps"])
    assert gaps["inside step 1"] == pytest.approx(10e-9)
    assert gaps["between steps"] == pytest.approx(30e-9)


@pytest.mark.skipif(not FIXTURE.exists(), reason="no recorded trace")
def test_reduce_recorded_trace():
    with gzip.open(FIXTURE, "rt") as f:
        events = json.load(f)
    expected = events.pop("expected")
    r = tracing.reduce(events, steps=expected["steps"])
    for key in ("window_s", "busy_s", "mosaic_s", "xla_s"):
        assert r[key] == pytest.approx(expected[key], rel=1e-9), key
    assert r["busy_s"] <= r["window_s"]
    assert r["mosaic_s"] + r["xla_s"] == pytest.approx(r["busy_s"])
    assert set(r["probes"]) == set(expected["probes"])
