"""Operations and bytes that the benchmark's rooflines count, computed
by each model kind's module from a configuration's own unpadded shapes,
and the table of peaks.

These are the yardstick: kept with the benchmark so that a change to the
program cannot move them.
"""

from __future__ import annotations

import json
import os
from typing import Dict, List, Tuple

from fenixbench import models

PEAKS = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     "peaks.json")

# the fused admission gate reads three int32 columns per packet (time
# since the flow's backlog anchor, backlog count, timestamp) and writes
# one int32 grant, besides its per-call LUT and scalars
GATE_BYTES_PER_PKT = 3 * 4 + 4


def peaks(device_kind: str) -> Dict[str, float]:
    """Peaks of one chip of ``device_kind``; an unknown kind is an error."""
    with open(PEAKS) as f:
        table = json.load(f)
    if device_kind not in table or device_kind == "source":
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS}")
    return table[device_kind]


def gemms(mcfg: Dict) -> List[Tuple[int, int, int, int]]:
    """The model's ``fenix_int8_gemm`` calls per inference, as its kind
    module counts them: (rows, K, N, out_bytes)."""
    return models.get(mcfg["kind"]).gemms(mcfg)


def gemm_macs_per_inference(mcfg: Dict) -> int:
    """Multiply-accumulates of one feature window inside the GEMMs."""
    return sum(r * k * n for r, k, n, _ in gemms(mcfg))


def macs_per_inference(mcfg: Dict) -> int:
    """Multiply-accumulates of one feature window, the whole model."""
    return models.get(mcfg["kind"]).macs_per_inference(mcfg)


def gemm_weight_bytes(mcfg: Dict) -> int:
    """int8 weight bytes every GEMM call reads once per service step."""
    return sum(k * n for _, k, n, _ in gemms(mcfg))


def gemm_act_bytes_per_inference(mcfg: Dict) -> int:
    """int8 activations in plus outputs out, per inference."""
    return sum(r * k + r * n * ob for r, k, n, ob in gemms(mcfg))
