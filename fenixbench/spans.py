"""What the per-layer metrics read from the program's own tracing
(``repro.tracing``): the window's ``fenix.run_trace`` calls with their
counters and host phases, and the device time of the scan's stage
scopes.

A program without ``repro.tracing`` (older than its per-layer metrics)
gives nothing: every function here then returns None.
"""

from __future__ import annotations

from typing import Dict, List, Optional


def _tracing():
    try:
        from repro import tracing
    except ImportError:
        return None
    return tracing


def window_calls(ctx) -> Optional[List]:
    """The window's ``fenix.run_trace`` spans: the last as many as the
    window timed calls."""
    tracing = _tracing()
    if tracing is None:
        return None
    n = len(ctx["window"]["latencies_s"])
    calls = [s for s in tracing.spans() if s.name == "fenix.run_trace"]
    if n == 0 or len(calls) < n:
        return None
    return calls[-n:]


def phase_ms(ctx, name: str) -> Optional[float]:
    """Mean milliseconds per window call spent in host span ``name``."""
    calls = window_calls(ctx)
    if calls is None:
        return None
    ids = {c.call_id for c in calls}
    ns = sum(s.t1_ns - s.t0_ns for s in _tracing().spans()
             if s.name == name and s.call_id in ids)
    if ns <= 0:
        return None
    return ns / len(calls) * 1e-6


def call_sum(ctx, attr: str) -> Optional[int]:
    """Sum of counter ``attr`` over the window's calls."""
    calls = window_calls(ctx)
    if calls is None or any(c.attrs.get(attr) is None for c in calls):
        return None
    return sum(c.attrs[attr] for c in calls)


def stage_seconds(ctx) -> Optional[Dict[str, float]]:
    """Device seconds of the traced window by scan stage (``"other"``
    for ops outside every stage scope; ops of no noted program left
    out)."""
    tracing, tr = _tracing(), ctx["trace"]
    if tracing is None or tr is None:
        return None
    stage_of = tracing.stage_of_ops()
    out: Dict[str, float] = {}
    for op, s in tr["op_s"].items():
        st = stage_of.get(op)
        if st is not None:
            out[st] = out.get(st, 0.0) + s
    return out


def stage_us(ctx, stages) -> Optional[float]:
    """Device microseconds per scan step of the window in ``stages``."""
    by = stage_seconds(ctx)
    steps = ctx["counters"]["steps"]
    if by is None or steps == 0:
        return None
    t = sum(by.get(s, 0.0) for s in stages)
    if t <= 0:
        return None
    return t / steps * 1e6
