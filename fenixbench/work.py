"""Operations and bytes that the benchmark's rooflines count, computed
from a configuration's own unpadded shapes, and the table of peaks.

These are the yardstick: kept with the benchmark so that a change to the
program cannot move them.
"""

from __future__ import annotations

import json
import os
from typing import Dict, List, Tuple

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
    """The model's GEMM calls per inference: (rows, K, N, out_bytes),
    rows being the GEMM rows one inference contributes; each listed call
    runs ``repeat`` times where a layer repeats (the RNN's timesteps are
    listed one by one)."""
    d_in = 2 * mcfg["embed_dim"]
    t = mcfg["seq_len"]
    out = []
    if mcfg["kind"] == "cnn":
        c_prev = d_in
        for ch in mcfg["conv_filters"]:
            out.append((t, mcfg["conv_kernel"] * c_prev, ch, 1))
            c_prev = ch
        for fc in mcfg["fc_dims"]:
            out.append((1, c_prev, fc, 1))
            c_prev = fc
        out.append((1, c_prev, mcfg["num_classes"], 4))
    else:
        u = mcfg["rnn_units"]
        for _ in range(t):
            out.append((1, d_in, u, 4))
            out.append((1, u, u, 4))
        out.append((1, u, mcfg["num_classes"], 4))
    return out


def macs_per_inference(mcfg: Dict) -> int:
    """Multiply-accumulates of one feature window."""
    return sum(r * k * n for r, k, n, _ in gemms(mcfg))


def gemm_weight_bytes(mcfg: Dict) -> int:
    """int8 weight bytes every GEMM call reads once per service step."""
    return sum(k * n for _, k, n, _ in gemms(mcfg))


def gemm_act_bytes_per_inference(mcfg: Dict) -> int:
    """int8 activations in plus outputs out, per inference."""
    return sum(r * k + r * n * ob for r, k, n, ob in gemms(mcfg))
