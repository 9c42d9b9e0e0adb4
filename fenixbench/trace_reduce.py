"""Reduce a JAX profiler trace (``.xplane.pb``) to the benchmark's
per-layer quantities.

* device busy time: the union of the intervals in which any operation
  ran on a device, inside the traced window, so overlapping or nested
  events count once; idle = window - busy;
* per-operation device time by name (summed over devices), from which a
  kernel's time is the sum over its instances (``fenix_int8_gemm.<n>``);
* the longest idle gaps of each device, each named by the harness's host
  span (``bench.*``) it fell in.

The traced window runs from the start of the first host span to the end
of the last one.  Device operations come from each ``/device:`` plane's
``XLA Ops`` line (every line of the plane where that one is absent),
named by their HLO instruction (``%fenix_int8_gemm.50 = s8[...] ...``
reads ``fenix_int8_gemm.50``).  Control-flow operations (the scan's
``while``, a ``conditional``) enclose the operations they run: they count
towards busy time but not per operation.
"""

from __future__ import annotations

import glob
import os
from typing import Callable, Dict, List, Optional, Tuple

Event = Tuple[str, float, float]            # (name, start_ns, end_ns)

SPAN_PREFIX = "bench."
CONTAINERS = ("while", "conditional", "call")


def op_name(name: str) -> str:
    """The HLO instruction name of a trace event's name."""
    return name.split(" = ", 1)[0].lstrip("%")


def _default_device_lines(plane_name: str, line_names: List[str]
                          ) -> List[str]:
    if not plane_name.startswith("/device:"):
        return []
    if "XLA Ops" in line_names:
        return ["XLA Ops"]
    return [n for n in line_names if n not in ("XLA Modules", "Steps")]


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def load(path: str, device_lines: Callable = _default_device_lines
         ) -> Tuple[Dict[str, List[Event]], List[Event]]:
    """-> ({device: [op events]}, [host ``bench.*`` spans])."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    devices: Dict[str, List[Event]] = {}
    spans: List[Event] = []
    for plane in pd.planes:
        lines = list(plane.lines)
        chosen = set(device_lines(plane.name, [ln.name for ln in lines]))
        for ln in lines:
            key = plane.name if ln.name in chosen else None
            for ev in ln.events:
                s, d = float(ev.start_ns), float(ev.duration_ns)
                if key is not None and d > 0:
                    devices.setdefault(key, []).append(
                        (op_name(ev.name), s, s + d))
                elif ev.name.startswith(SPAN_PREFIX):
                    spans.append((ev.name, s, s + d))
    return devices, spans


def union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """Merge overlapping or touching intervals."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _span_at(spans: List[Event], t: float) -> str:
    """Innermost host span covering time ``t`` ("none" outside all)."""
    best: Optional[Event] = None
    for sp in spans:
        if sp[1] <= t < sp[2] and (best is None or sp[1] >= best[1]):
            best = sp
    return best[0] if best else "none"


def reduce(devices: Dict[str, List[Event]], spans: List[Event],
           top: int = 10) -> Dict:
    """Busy/idle, per-op time, top ops and top idle gaps in the window."""
    if not spans:
        raise ValueError("no bench.* host spans in the trace")
    t0 = min(s for _, s, _ in spans)
    t1 = max(e for _, _, e in spans)
    window_s = (t1 - t0) * 1e-9
    op_s: Dict[str, float] = {}
    busy = []
    gaps: List[Tuple[float, float]] = []
    for evs in devices.values():
        clipped = [(n, max(s, t0), min(e, t1)) for n, s, e in evs
                   if e > t0 and s < t1]
        for n, s, e in clipped:
            if n.split(".")[0] not in CONTAINERS:
                op_s[n] = op_s.get(n, 0.0) + (e - s) * 1e-9
        merged = union([(s, e) for _, s, e in clipped])
        busy.append(sum(e - s for s, e in merged) * 1e-9)
        edges = [t0] + [x for iv in merged for x in iv] + [t1]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b > a:
                gaps.append(((b - a) * 1e-9, (a + b) / 2))
    n_dev = max(len(devices), 1)
    busy_s = sum(busy) / n_dev
    gaps.sort(reverse=True)
    return {
        "window_s": window_s,
        "busy_s": busy_s,
        "idle_s": window_s - busy_s,
        "devices": len(devices),
        "op_s": op_s,
        "device_ops": sorted(([n, s] for n, s in op_s.items()),
                             key=lambda x: -x[1])[:top],
        "idle_gaps": [[_span_at(spans, mid), s] for s, mid in gaps[:top]],
    }


def kernel_seconds(reduced: Dict, kernel: str) -> float:
    """Device seconds of every operation named ``kernel`` or
    ``kernel.<n>``."""
    return sum(s for n, s in reduced["op_s"].items()
               if n == kernel or n.startswith(kernel + "."))
