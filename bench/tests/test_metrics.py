"""Every metric in BENCHMARK.json has a reader; a reader that finds
nothing to read returns None; the readers' arithmetic on a made-up
record."""

import pytest

import spec

RECORD = {
    "host": {"setup_time": 80.0, "lower_time": 30.0,
             "compile_time": 2.0,
             "window_s": 20.0, "steps": 40},
    "peaks": {"hbm_bytes_per_s": 1e9},
    "bytes": {"per_call": {"c_sw+riem": 1e6, "d_sw": 1e6, "tracer_2d": 1e6,
                           "vertical_remap": 1e6, "halo_exchange": 1e6},
              "per_step": 5e7},
    "trace": {"window_s": 1.5, "busy_s": 1.47, "mosaic_s": 1.0,
              "xla_s": 0.47, "steps": 3,
              "probes": {"c_sw_riem": {"calls": 3, "device_s": 0.03},
                         "d_sw": {"calls": 3, "device_s": 0.06},
                         "tracer_2d": {"calls": 3, "device_s": 0.09},
                         "vertical_remap": {"calls": 3, "device_s": 0.3},
                         "halo_exchange": {"calls": 3, "device_s": 0.036}}},
}

EXPECTED = {
    "step_ms": 500.0, "setup_s": 80.0, "lower_s": 30.0, "compile_s": 2.0,
    "roofline_pct.c_sw_riem": 10.0, "roofline_pct.d_sw": 5.0,
    "roofline_pct.tracer_2d": 100.0 / 30, "roofline_pct.vertical_remap": 1.0,
    "halo_exchange_ms": 12.0, "xla_ops_ms": 470.0 / 3,
    "device_idle_pct": 2.0, "step_mfu": 10.0,
}


def _names():
    bench = spec.benchmark()
    return [m["name"] for m in bench["end_to_end"] + bench["per_layer"]]


@pytest.mark.parametrize("name", _names())
def test_reader(name):
    read = spec.reader(name)
    assert read(RECORD) == pytest.approx(EXPECTED[name])
    if name not in ("setup_s", "lower_s", "compile_s"):
        empty = dict(RECORD, trace=None, host={"setup_time": 1.0})
        assert read(empty) is None
