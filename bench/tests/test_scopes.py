"""The reduction by the step's scopes: the scope map read from compiled
HLO text, each layer's busy time, ``other`` and overlap, kernels by node,
and idle gaps labelled by host span, on a hand-made trace and on one
recorded on the chip."""

import gzip
import json
from pathlib import Path

import pytest

import scopes
import tracing

FIXTURE = Path(__file__).with_name("fixtures") / \
    "trace_scopes_c128_l80.json.gz"


def _instruction(name: str, body: str, op_name: str = "") -> str:
    meta = f', metadata={{op_name="{op_name}" stack_frame_id=3}}' \
        if op_name else ""
    return f"  %{name} = {body}{meta}"


LOOP = "jit(_inner)/while/body/closed_call"
HLO = "\n".join([
    "ENTRY %main.9 (p: f32[4]) -> f32[4] {",
    _instruction("p", "f32[4]{0} parameter(0)", "state[\\'u\\']"),
    _instruction("copy.1", "f32[4]{0} copy(%p)"),
    _instruction("fusion.2", "f32[4]{0} fusion(%copy.1), kind=kLoop",
                 f"{LOOP}/halo_exchange/scatter"),
    _instruction("al_x.3", "f32[4]{0} custom-call(%fusion.2), "
                 'custom_call_target="tpu_custom_call"',
                 f"{LOOP}/d_sw/vmap(al_x#3)/jit(al_x)/al_x"),
    _instruction("transpose.4", "f32[4]{0} transpose(%al_x.3)",
                 f"{LOOP}/tracer_2d/vmap()/transpose"),
    _instruction("add.5", "s32[] add(%c, %d)", "jit(_inner)/while/body/add"),
    "}"])


def test_scope_map_reads_layer_and_node():
    m = scopes.scope_map(HLO)
    assert m == {"fusion.2": ["halo_exchange", ""],
                 "al_x.3": ["d_sw", "al_x#3"],
                 "transpose.4": ["tracer_2d", ""]}


def _events():
    mods = [["jit__inner(1)", 0, 100, ""], ["jit__inner(1)", 120, 100, ""]]
    ops = [["while.4", 0, 90, ""],                    # encloses the rest
           ["fusion.2", 0, 30, ""],                    # halo
           ["al_x.3", 20, 40, "mosaic"],               # d_sw, overlaps
           ["copy.1", 70, 20, ""],                     # unscoped
           ["al_x.3", 120, 60, "mosaic"],
           ["fusion.2", 190, 30, ""]]
    host = [["bench.step", -5, 98], ["repro.step", -4, 3],
            ["bench.step", 110, 115]]
    return {"devices": {"/device:TPU:0": {"XLA Modules": mods,
                                          "XLA Ops": ops}},
            "host": host}


def test_reduce_by_scope_on_hand_made_trace():
    r = scopes.reduce(_events(), scopes.scope_map(HLO), steps=2)
    ms = 1e-6 / 2
    assert r["window_ms"] == pytest.approx(220 * ms)
    # busy: [0, 60) + [70, 90) + [120, 180) + [190, 220)
    assert r["busy_ms"] == pytest.approx(170 * ms)
    got = r["in_step_ms"]
    assert got["halo_exchange"] == pytest.approx(60 * ms)
    assert got["d_sw"] == pytest.approx(100 * ms)
    assert got["other"] == pytest.approx(20 * ms)
    assert got["c_sw_riem"] == got["tracer_2d"] == 0
    assert r["overlap_ms"] == pytest.approx(10 * ms)
    assert sum(got.values()) == pytest.approx(r["busy_ms"] + r["overlap_ms"])
    assert dict(r["kernels_ms"]) == pytest.approx(
        {"d_sw/al_x": 100 * ms, "halo_exchange": 60 * ms, "other": 20 * ms})


def test_idle_gaps_take_the_innermost_host_span():
    r = scopes.reduce(_events(), scopes.scope_map(HLO), steps=2)
    gaps = sorted(r["idle_gaps"], key=lambda g: g[1])
    # [60, 70) and [90, 120) start inside the first bench.step, which ends
    # at 93; [180, 190) inside the second
    assert [g[0] for g in gaps] == ["bench.step"] * 3
    assert [g[1] for g in gaps] == pytest.approx([10e-9, 10e-9, 30e-9])
    assert scopes.innermost(_events()["host"], -3) == "repro.step"
    assert scopes.innermost(_events()["host"], 100) == "none"
    assert r["idle_by_span_ms"] == pytest.approx({"bench.step": 25e-6})


def test_step_spans_beside_step_modules():
    spans = scopes.step_spans(_events())
    assert [s["inside"] for s in spans] == pytest.approx([0.93, 1.0])
    assert [s["start_lead_ms"] for s in spans] == pytest.approx([-5e-6,
                                                                 -10e-6])
    assert [s["end_lead_ms"] for s in spans] == pytest.approx([-7e-6, 5e-6])


@pytest.mark.skipif(not FIXTURE.exists(), reason="no recorded trace")
def test_recorded_trace_by_scope():
    with gzip.open(FIXTURE, "rt") as f:
        events = json.load(f)
    expected = events.pop("expected")
    smap = events.pop("scopes")
    steps = expected["steps"]
    # the harness's own keys reduce as recorded
    r = tracing.reduce(events, steps)
    for key in ("window_s", "busy_s", "mosaic_s", "xla_s"):
        assert r[key] == pytest.approx(expected[key], rel=1e-9), key
    b = scopes.reduce(events, smap, steps)
    assert b["in_step_ms"] == pytest.approx(
        expected["breakdown"]["in_step_ms"], rel=1e-9)
    # the layers add up to the busy time, up to their overlap
    assert sum(b["in_step_ms"].values()) == pytest.approx(
        b["busy_ms"] + b["overlap_ms"], rel=1e-9)
    assert b["busy_ms"] == pytest.approx(1e3 * r["busy_s"] / steps)
    assert all(b["in_step_ms"][k] > 0 for k in scopes.LAYERS)
    # device and host timelines share the profile's clock, up to a skew
    # of about a millisecond: each step module lies in its bench.step span
    for s in scopes.step_spans(events):
        assert s["inside"] > 0.99 and abs(s["start_lead_ms"]) < 2, s
    assert all(g[0] != "none" for g in b["idle_gaps"])
