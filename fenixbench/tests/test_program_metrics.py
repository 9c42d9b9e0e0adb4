"""The per-layer metrics that read the program's own tracing
(``repro.tracing``), on hand-made contexts, span records and stage
maps."""

import importlib

import pytest

from fenixbench import spans
from repro import tracing

STAGE_OF = {"fusion.1": "flow_table", "sort.2": "sort_segment",
            "fusion.3": "feature_ring", "copy.4": "vio_enqueue",
            "fusion.5": "vio_dequeue", "fenix_int8_gemm.6": "infer",
            "fusion.7": "other", "fenix_fused_gate.8": "gate"}
OP_S = {"fusion.1": 2e-3, "sort.2": 1e-3, "fusion.3": 4e-3, "copy.4": 1e-3,
        "fusion.5": 0.5e-3, "fenix_int8_gemm.6": 8e-3, "fusion.7": 5e-3,
        "fenix_fused_gate.8": 1e-3,
        # an op of another program: in no stage
        "reshape.9": 1.0}


def _read(name, ctx):
    return importlib.import_module(f"fenixbench.metrics.{name}").read(ctx)


def _span(name, call, t0_ms, t1_ms, **attrs):
    return tracing.Span(name, call, None if name == "fenix.run_trace"
                        else "fenix.run_trace", int(t0_ms * 1e6),
                        int(t1_ms * 1e6), attrs)


# three calls; the window timed the last two
RING = [
    _span("fenix.stage_in", 1, 0, 9),
    _span("fenix.run_trace", 1, 0, 40, served=1024, lanes=1024),
    _span("fenix.stage_in", 2, 40, 42),
    _span("fenix.wait", 2, 42, 70),
    _span("fenix.fetch_out", 2, 70, 71),
    _span("fenix.run_trace", 2, 40, 80, served=300, lanes=1024),
    _span("fenix.stage_in", 3, 80, 84),
    _span("fenix.fetch_out", 3, 110, 113),
    _span("fenix.run_trace", 3, 80, 120, served=212, lanes=1024),
]


def _ctx(trace=True, calls=2):
    return {"window": {"latencies_s": [0.04] * calls},
            "counters": {"steps": 10},
            "trace": {"op_s": dict(OP_S)} if trace else None}


@pytest.fixture
def program(monkeypatch):
    monkeypatch.setattr(tracing, "spans", lambda: list(RING))
    monkeypatch.setattr(tracing, "stage_of_ops", lambda: dict(STAGE_OF))


@pytest.mark.parametrize("name,want", [
    ("flow_tracker_us", 300.0),       # (2 + 1) ms over 10 steps
    ("feature_ring_us", 400.0),
    ("vector_io_us", 150.0),
    ("inference_us", 800.0),
])
def test_stage_metrics(program, name, want):
    assert _read(name, _ctx()) == pytest.approx(want)
    assert _read(name, _ctx(trace=False)) is None


def test_stage_without_time_reads_nothing(program, monkeypatch):
    monkeypatch.setattr(tracing, "stage_of_ops", lambda: {
        k: v for k, v in STAGE_OF.items() if v != "infer"})
    assert _read("inference_us", _ctx()) is None
    assert _read("feature_ring_us", _ctx()) == pytest.approx(400.0)


@pytest.mark.parametrize("name,want", [
    ("stage_in_ms", (2 + 4) / 2),     # call 1 is outside the window
    ("fetch_out_ms", (1 + 3) / 2),
])
def test_phase_metrics_average_the_window_calls(program, name, want):
    assert _read(name, _ctx()) == pytest.approx(want)


def test_lane_occupancy_over_the_window_calls(program):
    assert _read("lane_occupancy", _ctx()) == pytest.approx(
        100.0 * (300 + 212) / 2048)
    assert _read("lane_occupancy", _ctx(calls=3)) == pytest.approx(
        100.0 * (1024 + 300 + 212) / 3072)


def test_more_window_calls_than_spans_read_nothing(program):
    for name in ("stage_in_ms", "fetch_out_ms", "lane_occupancy"):
        assert _read(name, _ctx(calls=4)) is None


ALL = ["flow_tracker_us", "feature_ring_us", "vector_io_us", "inference_us",
       "stage_in_ms", "fetch_out_ms", "lane_occupancy"]


@pytest.mark.parametrize("name", ALL)
def test_program_without_tracing_reads_nothing(monkeypatch, name):
    # a program older than these metrics has no repro.tracing
    monkeypatch.setattr(spans, "_tracing", lambda: None)
    assert _read(name, _ctx()) is None
