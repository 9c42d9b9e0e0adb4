"""Fixed-point arithmetic shared by the INT8 model kinds, in the paper's
scheme (§6): power-of-two scales, int8 weights and activations, int32
accumulators, round-half-up right-shift requantization, relu as a clip,
and an embedding of the bucketized (pkt_len, ipd) features.

The reference side is exact: float64 products of int8 values sum exactly
far below 2**53.  ``grid`` rounds an int8 tensor onto a coarser grid of
the same scale, which is how ``forward(..., bits=4)`` builds the
precision control.  Nothing here imports the program.
"""

from __future__ import annotations

from typing import Dict, Tuple

import numpy as np


def shift_for(absmax: float) -> int:
    """Largest s with absmax * 2**s <= 127, at most 12."""
    absmax = max(float(absmax), 1e-8)
    return min(int(np.floor(np.log2(127.0 / absmax))), 12)


def q(x: np.ndarray, shift: int, dtype=np.int8) -> np.ndarray:
    lim = 127 if dtype == np.int8 else 2**31 - 1
    return np.clip(np.round(np.asarray(x, np.float64) * 2.0 ** shift),
                   -lim, lim).astype(dtype)


def absmax(x: np.ndarray) -> float:
    return float(np.max(np.abs(x)))


def bucketize(payload: np.ndarray, mcfg: Dict) -> np.ndarray:
    """[..., T, 2] (pkt_len, ipd_us) -> embedding ids: len >> 5 and
    2 * floor(log2(1 + ipd)), each clipped to its table."""
    p = np.asarray(payload, np.int64)
    ln = np.clip(p[..., 0] >> 5, 0, mcfg["len_buckets"] - 1)
    lg = np.frexp((1 + np.maximum(p[..., 1], 0)).astype(np.float64))[1] - 1
    ip = np.clip(2 * lg, 0, mcfg["ipd_buckets"] - 1)
    return np.stack([ln, ip], axis=-1)


def grid(x: np.ndarray, bits: int) -> np.ndarray:
    """Round an int8-grid tensor onto a ``bits``-bit grid of the same
    scale (identity at 8 bits)."""
    if bits >= 8:
        return np.asarray(x, np.int64)
    step = 1 << (8 - bits)
    lim = (1 << (bits - 1)) - 1
    return np.clip(np.round(np.asarray(x, np.float64) / step),
                   -lim, lim).astype(np.int64) * step


def mm(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    """Exact integer matmul through float64 BLAS."""
    return np.rint(a.astype(np.float64) @ b.astype(np.float64)).astype(
        np.int64)


def requant(acc: np.ndarray, shift: int) -> np.ndarray:
    if shift > 0:
        acc = (acc + (1 << (shift - 1))) >> shift
    return np.clip(acc, -127, 127)


# ---------------------------------------------------------------------------
# weights from the seed: the embedding tables are drawn first, then each
# kind's layers in its own order, from the same generator
# ---------------------------------------------------------------------------


def draw_embed(mcfg: Dict, seed: int) -> Tuple[np.random.Generator, Dict]:
    """The seed's generator and the float embedding tables."""
    rng = np.random.default_rng([int(seed), 2])
    e = mcfg["embed_dim"]
    w = {"embed_len": rng.normal(0, 0.5, (mcfg["len_buckets"], e)),
         "embed_ipd": rng.normal(0, 0.5, (mcfg["ipd_buckets"], e))}
    return rng, w


def draw_dense(rng: np.random.Generator, w: Dict, name: str, fin: int,
               fout: int, lead: tuple = ()) -> None:
    """N(0, fan_in^-1/2) weights and N(0, 0.05) biases of one layer."""
    w[f"{name}/w"] = rng.normal(0, (fin * int(np.prod(lead))) ** -0.5,
                                lead + (fin, fout))
    w[f"{name}/b"] = rng.normal(0, 0.05, (fout,))


def embed_float(w: Dict, mcfg: Dict, payload: np.ndarray) -> np.ndarray:
    """Float embedding of payload windows: [..., T, 2 * embed_dim]."""
    ids = bucketize(payload, mcfg)
    return np.concatenate([w["embed_len"][ids[..., 0]],
                           w["embed_ipd"][ids[..., 1]]], -1)


def quantize_embed(w: Dict, se: int) -> Dict:
    """The integer model's embedding tables at activation shift ``se``."""
    return {"embed_len/table": q(w["embed_len"], se),
            "embed_ipd/table": q(w["embed_ipd"], se)}


def quantize_dense(w: Dict, qp: Dict, name: str, sa_in: int,
                   sa_out: int) -> int:
    """Quantize layer ``name`` into ``qp``; its output's shift."""
    sw = shift_for(absmax(w[f"{name}/w"]))
    sa_out = min(sa_out, sa_in + sw)
    qp[f"{name}/w"] = q(w[f"{name}/w"], sw)
    qp[f"{name}/b"] = q(w[f"{name}/b"], sa_in + sw, np.int32)
    qp[f"{name}/shift"] = np.int32(sa_in + sw - sa_out)
    return sa_out


# ---------------------------------------------------------------------------
# the reference forward pass's shared start
# ---------------------------------------------------------------------------


def on_grid(qp: Dict, bits: int) -> Dict:
    """Every int8 tensor of ``qp`` on the ``bits``-bit grid, every other
    one as int64."""
    return {k: (grid(v, bits) if np.asarray(v).dtype == np.int8 else
                np.asarray(v, np.int64)) for k, v in qp.items()}


def embed_int(g: Dict, mcfg: Dict, payload: np.ndarray) -> np.ndarray:
    """Integer embedding of payload windows: [N, T, 2 * embed_dim]."""
    ids = bucketize(payload, mcfg)
    return np.concatenate([g["embed_len/table"][ids[..., 0]],
                           g["embed_ipd/table"][ids[..., 1]]], axis=-1)
