"""The benchmark's INT8 traffic classifier: weights from the seed, and the
plain reference forward pass that decides ``correct``.

The configuration states an INT8 model in the paper's fixed-point scheme
(§6): power-of-two scales, int8 weights and activations, int32
accumulators, round-half-up right-shift requantization, relu as a clip,
an integer mean pool (``(sum * round(2^15 / T)) >> 15``), and for the RNN
a 512-entry int8 tanh table indexed by the pre-activation's high bits.

``make_int8_model`` draws float weights from the seed, calibrates the
activation scales with a float64 forward pass over payload windows of the
cell's own traffic, and returns the integer model in the layout the
program's ``EngineModel`` serves.  ``forward`` is the plain reference:
numpy only, exact (float64 products of int8 values sum exactly far below
2**53).  ``forward(..., bits=4)`` is the same model with every weight,
table and activation rounded to a 4-bit grid: the precision control.
Nothing here imports the program.
"""

from __future__ import annotations

from typing import Dict

import numpy as np


def _shift_for(absmax: float) -> int:
    """Largest s with absmax * 2**s <= 127, at most 12."""
    absmax = max(float(absmax), 1e-8)
    return min(int(np.floor(np.log2(127.0 / absmax))), 12)


def _q(x: np.ndarray, shift: int, dtype=np.int8) -> np.ndarray:
    lim = 127 if dtype == np.int8 else 2**31 - 1
    return np.clip(np.round(np.asarray(x, np.float64) * 2.0 ** shift),
                   -lim, lim).astype(dtype)


def bucketize(payload: np.ndarray, mcfg: Dict) -> np.ndarray:
    """[..., T, 2] (pkt_len, ipd_us) -> embedding ids: len >> 5 and
    2 * floor(log2(1 + ipd)), each clipped to its table."""
    p = np.asarray(payload, np.int64)
    ln = np.clip(p[..., 0] >> 5, 0, mcfg["len_buckets"] - 1)
    lg = np.frexp((1 + np.maximum(p[..., 1], 0)).astype(np.float64))[1] - 1
    ip = np.clip(2 * lg, 0, mcfg["ipd_buckets"] - 1)
    return np.stack([ln, ip], axis=-1)


def _im2col(x: np.ndarray, k: int) -> np.ndarray:
    """'same'-padded conv1d windows: [B, S, C] -> [B*S, k*C]."""
    pad = k // 2
    b, s, c = x.shape
    xp = np.pad(x, ((0, 0), (pad, k - 1 - pad), (0, 0)))
    cols = np.stack([xp[:, i:i + s] for i in range(k)], axis=2)
    return cols.reshape(b * s, k * c)


def _float_sites(w: Dict, mcfg: Dict, payload: np.ndarray) -> Dict:
    """Float64 forward of the unquantized weights: absmax per site."""
    sites = {}

    def rec(name, x):
        sites[name] = float(np.max(np.abs(x)))
        return x

    ids = bucketize(payload, mcfg)
    x = rec("embed", np.concatenate([w["embed_len"][ids[..., 0]],
                                     w["embed_ipd"][ids[..., 1]]], -1))
    b, t, _ = x.shape
    if mcfg["kind"] == "cnn":
        k = mcfg["conv_kernel"]
        for i in range(len(mcfg["conv_filters"])):
            y = _im2col(x, k) @ w[f"conv{i}/w"].reshape(-1, w[f"conv{i}/w"]
                                                        .shape[-1])
            x = rec(f"conv{i}", np.maximum(y + w[f"conv{i}/b"], 0)
                    .reshape(b, t, -1))
        x = x.mean(axis=1)
        for i in range(len(mcfg["fc_dims"])):
            x = rec(f"fc{i}", np.maximum(x @ w[f"fc{i}/w"] + w[f"fc{i}/b"],
                                         0))
        rec("head", x @ w["head/w"] + w["head/b"])
    else:
        h = np.zeros((b, mcfg["rnn_units"]))
        pres = []
        for s in range(t):
            pre = x[:, s] @ w["cell/wx"] + h @ w["cell/wh"] + w["cell/b"]
            pres.append(pre)
            h = np.tanh(pre)
        rec("cell_pre", np.stack(pres))
        rec("cell", h)
        rec("head", h @ w["head/w"] + w["head/b"])
    return sites


def make_int8_model(mcfg: Dict, seed: int, calib: np.ndarray) -> Dict:
    """Integer model from the seed, calibrated on ``calib`` payloads
    ([N, T, 2] int32 windows of the cell's traffic)."""
    rng = np.random.default_rng([int(seed), 2])
    e = mcfg["embed_dim"]
    w = {"embed_len": rng.normal(0, 0.5, (mcfg["len_buckets"], e)),
         "embed_ipd": rng.normal(0, 0.5, (mcfg["ipd_buckets"], e))}
    nc = mcfg["num_classes"]

    def dense(name, fin, fout, lead=()):
        w[f"{name}/w"] = rng.normal(0, (fin * int(np.prod(lead))) ** -0.5,
                                    lead + (fin, fout))
        w[f"{name}/b"] = rng.normal(0, 0.05, (fout,))

    if mcfg["kind"] == "cnn":
        c_prev = 2 * e
        for i, ch in enumerate(mcfg["conv_filters"]):
            dense(f"conv{i}", c_prev, ch, (mcfg["conv_kernel"],))
            c_prev = ch
        for i, fc in enumerate(mcfg["fc_dims"]):
            dense(f"fc{i}", c_prev, fc)
            c_prev = fc
        dense("head", c_prev, nc)
    else:
        u = mcfg["rnn_units"]
        w["cell/wx"] = rng.normal(0, (2 * e) ** -0.5, (2 * e, u))
        w["cell/wh"] = rng.normal(0, u ** -0.5, (u, u))
        w["cell/b"] = rng.normal(0, 0.05, (u,))
        dense("head", u, nc)
    sa = {k: _shift_for(v) for k, v in _float_sites(w, mcfg, calib).items()}
    qp: Dict = {}
    se = sa["embed"]
    qp["embed_len/table"] = _q(w["embed_len"], se)
    qp["embed_ipd/table"] = _q(w["embed_ipd"], se)

    def qlayer(name, sa_in, sa_out):
        sw = _shift_for(np.max(np.abs(w[f"{name}/w"])))
        sa_out = min(sa_out, sa_in + sw)
        qp[f"{name}/w"] = _q(w[f"{name}/w"], sw)
        qp[f"{name}/b"] = _q(w[f"{name}/b"], sa_in + sw, np.int32)
        qp[f"{name}/shift"] = np.int32(sa_in + sw - sa_out)
        return sa_out

    if mcfg["kind"] == "cnn":
        prev = se
        for i in range(len(mcfg["conv_filters"])):
            prev = qlayer(f"conv{i}", prev, sa[f"conv{i}"])
        qp["pool/mult"] = np.int32(round((1 << 15) / mcfg["seq_len"]))
        for i in range(len(mcfg["fc_dims"])):
            prev = qlayer(f"fc{i}", prev, sa[f"fc{i}"])
        qlayer("head", prev, max(sa["head"], 0))
    else:
        sh = sa["cell"]
        swx = _shift_for(np.max(np.abs(w["cell/wx"])))
        swh = _shift_for(np.max(np.abs(w["cell/wh"])))
        sa_pre = min(max(sa["cell_pre"], 4), se + swx, sh + swh)
        qp["cell/wx"] = _q(w["cell/wx"], swx)
        qp["cell/wh"] = _q(w["cell/wh"], swh)
        qp["cell/b"] = _q(w["cell/b"], se + swx, np.int32)
        qp["cell/shift_x"] = np.int32(se + swx - sa_pre)
        qp["cell/shift_h"] = np.int32(sh + swh - sa_pre)
        qp["tanh_lut"] = _q(np.tanh(np.arange(-256, 256) / 16.0), sh)
        qp["cell/lut_preshift"] = np.int32(sa_pre - 4)
        qlayer("head", sh, max(sa["head"], 0))
    return qp


# ---------------------------------------------------------------------------
# reference forward pass (exact integer arithmetic) and its 4-bit control
# ---------------------------------------------------------------------------


def _grid(x: np.ndarray, bits: int) -> np.ndarray:
    """Round an int8-grid tensor onto a ``bits``-bit grid of the same
    scale (identity at 8 bits)."""
    if bits >= 8:
        return np.asarray(x, np.int64)
    step = 1 << (8 - bits)
    lim = (1 << (bits - 1)) - 1
    return np.clip(np.round(np.asarray(x, np.float64) / step),
                   -lim, lim).astype(np.int64) * step


def _mm(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    """Exact integer matmul through float64 BLAS."""
    return np.rint(a.astype(np.float64) @ b.astype(np.float64)).astype(
        np.int64)


def _requant(acc: np.ndarray, shift: int) -> np.ndarray:
    if shift > 0:
        acc = (acc + (1 << (shift - 1))) >> shift
    return np.clip(acc, -127, 127)


def forward(qp: Dict, mcfg: Dict, payload: np.ndarray,
            bits: int = 8) -> np.ndarray:
    """[N, T, 2] payload windows -> [N, classes] int64 logits."""
    g = {k: (_grid(v, bits) if np.asarray(v).dtype == np.int8 else
             np.asarray(v, np.int64)) for k, v in qp.items()}
    ids = bucketize(payload, mcfg)
    x = np.concatenate([g["embed_len/table"][ids[..., 0]],
                        g["embed_ipd/table"][ids[..., 1]]], axis=-1)
    b, t, _ = x.shape
    if mcfg["kind"] == "cnn":
        k = mcfg["conv_kernel"]
        for i in range(len(mcfg["conv_filters"])):
            wq = g[f"conv{i}/w"]
            acc = _mm(_im2col(x, k), wq.reshape(-1, wq.shape[-1])) \
                + g[f"conv{i}/b"]
            x = _grid(np.maximum(_requant(acc, int(g[f"conv{i}/shift"])),
                                 0), bits).reshape(b, t, -1)
        x = ((x.sum(axis=1) * int(g["pool/mult"])) >> 15).astype(
            np.int8).astype(np.int64)
        x = _grid(x, bits)
        for i in range(len(mcfg["fc_dims"])):
            acc = _mm(x, g[f"fc{i}/w"]) + g[f"fc{i}/b"]
            x = _grid(np.maximum(_requant(acc, int(g[f"fc{i}/shift"])), 0),
                      bits)
        return _mm(x, g["head/w"]) + g["head/b"]
    sx, sh = int(g["cell/shift_x"]), int(g["cell/shift_h"])
    pre_shift = int(g["cell/lut_preshift"])
    h = np.zeros((b, mcfg["rnn_units"]), np.int64)
    for s in range(t):
        accx = _mm(x[:, s], g["cell/wx"]) + g["cell/b"]
        acch = _mm(h, g["cell/wh"])
        pre = (accx >> sx) + (acch >> sh)
        lidx = np.clip(pre >> pre_shift, -256, 255)
        h = g["tanh_lut"][lidx + 256]
    return _mm(h, g["head/w"]) + g["head/b"]


def classify(qp: Dict, mcfg: Dict, payload: np.ndarray,
             bits: int = 8, block: int = 4096) -> np.ndarray:
    """Logits of ``forward`` in blocks of rows (bounded host memory)."""
    out = [forward(qp, mcfg, payload[i:i + block], bits)
           for i in range(0, len(payload), block)]
    return (np.concatenate(out) if out
            else np.zeros((0, mcfg["num_classes"]), np.int64))
