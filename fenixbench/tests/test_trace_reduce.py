"""The trace reduction, on a small trace recorded on the CPU
(``data/cpu_trace.xplane.pb``: three rounds of the harness's host spans
around a jitted matmul, ``jax.profiler`` with the Python tracer off) and
on hand-made events."""

import os

import pytest

from fenixbench import trace_reduce as tr

TRACE = os.path.join(os.path.dirname(__file__), "data",
                     "cpu_trace.xplane.pb")


def cpu_lines(plane, lines):
    # the CPU client's executor threads stand in for a device's op line
    if plane != "/host:CPU":
        return []
    return [n for n in lines if n.startswith("tf_XLAPjRtCpuClient")]


def test_recorded_trace_busy_plus_idle_is_window():
    devs, spans = tr.load(TRACE, device_lines=cpu_lines)
    assert {n for n, _, _ in spans} == {"bench.stage", "bench.run_trace",
                                        "bench.fetch"}
    red = tr.reduce(devs, spans)
    assert red["devices"] >= 1
    assert red["busy_s"] > 0
    assert red["busy_s"] + red["idle_s"] == pytest.approx(red["window_s"])
    assert red["busy_s"] <= red["window_s"]
    assert tr.kernel_seconds(red, "dot_general") > 0
    assert tr.kernel_seconds(red, "dot") == 0
    assert 0 < len(red["idle_gaps"]) <= 10
    assert all(name.startswith("bench.") or name == "none"
               for name, _ in red["idle_gaps"])


def test_default_selector_finds_no_device_on_cpu_trace():
    devs, spans = tr.load(TRACE)
    assert devs == {}
    assert spans


def test_overlapping_ops_count_once():
    spans = [("bench.run_trace", 0.0, 100.0)]
    devs = {"/device:TPU:0": [("a", 10.0, 50.0), ("b", 20.0, 40.0),
                              ("c", 45.0, 70.0), ("d", 90.0, 120.0)]}
    red = tr.reduce(devs, spans)
    # union [10, 70] + [90, 100] (clipped to the window) = 70 ns
    assert red["busy_s"] == pytest.approx(70e-9)
    assert red["idle_s"] == pytest.approx(30e-9)
    assert red["op_s"]["d"] == pytest.approx(10e-9)
    assert red["idle_gaps"][0] == ["bench.run_trace",
                                   pytest.approx(20e-9)]


def test_busy_is_mean_over_devices_and_gaps_are_named():
    spans = [("bench.stage", 0.0, 50.0), ("bench.run_trace", 50.0, 100.0)]
    devs = {"/device:TPU:0": [("k", 0.0, 45.0), ("k", 50.0, 100.0)],
            "/device:TPU:1": [("k", 0.0, 40.0), ("k", 60.0, 100.0)]}
    red = tr.reduce(devs, spans)
    assert red["busy_s"] == pytest.approx((95 + 80) / 2 * 1e-9)
    assert red["op_s"]["k"] == pytest.approx(175e-9)
    # gaps [40, 60] (midpoint 50, in bench.run_trace) and [45, 50]
    assert red["idle_gaps"] == [["bench.run_trace", pytest.approx(20e-9)],
                                ["bench.stage", pytest.approx(5e-9)]]


def test_no_spans_is_an_error():
    with pytest.raises(ValueError):
        tr.reduce({}, [])


def test_hlo_names_and_containers():
    assert tr.op_name("%fenix_int8_gemm.50 = s8[9216,256]{1,0} "
                      "custom-call(s8[9216,384] %a)") == "fenix_int8_gemm.50"
    spans = [("bench.run_trace", 0.0, 100.0)]
    devs = {"/device:TPU:0": [("while.5", 0.0, 90.0),
                              ("fenix_int8_gemm.50", 10.0, 30.0),
                              ("fenix_int8_gemm.51", 40.0, 45.0),
                              ("fenix_int8_gemm_x", 50.0, 55.0)]}
    red = tr.reduce(devs, spans)
    assert red["busy_s"] == pytest.approx(90e-9)
    assert "while.5" not in red["op_s"]
    assert tr.kernel_seconds(red, "fenix_int8_gemm") == pytest.approx(25e-9)
