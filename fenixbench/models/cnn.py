"""FENIX-CNN (paper §7.1): the embedded window through 'same'-padded
conv1d layers with relu, an integer mean pool over the window
(``(sum * round(2^15 / T)) >> 15``), relu FC layers and a linear head."""

from __future__ import annotations

from typing import Dict

import numpy as np

from fenixbench.models import fixed_point as fp

# the program's logits and the reference's are exact integers on the same
# grid, so a sound run's verdicts lie 0 below the reference's best
VERDICT_GAP_LIMIT = 0


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
        sites[name] = fp.absmax(x)
        return x

    x = rec("embed", fp.embed_float(w, mcfg, payload))
    b, t, _ = x.shape
    k = mcfg["conv_kernel"]
    for i in range(len(mcfg["conv_filters"])):
        y = _im2col(x, k) @ w[f"conv{i}/w"].reshape(-1, w[f"conv{i}/w"]
                                                    .shape[-1])
        x = rec(f"conv{i}", np.maximum(y + w[f"conv{i}/b"], 0)
                .reshape(b, t, -1))
    x = x.mean(axis=1)
    for i in range(len(mcfg["fc_dims"])):
        x = rec(f"fc{i}", np.maximum(x @ w[f"fc{i}/w"] + w[f"fc{i}/b"], 0))
    rec("head", x @ w["head/w"] + w["head/b"])
    return sites


def make_model(mcfg: Dict, seed: int, calib: np.ndarray) -> Dict:
    """Integer model from the seed, calibrated on ``calib`` payloads
    ([N, T, 2] int32 windows of the cell's traffic)."""
    rng, w = fp.draw_embed(mcfg, seed)
    c_prev = 2 * mcfg["embed_dim"]
    for i, ch in enumerate(mcfg["conv_filters"]):
        fp.draw_dense(rng, w, f"conv{i}", c_prev, ch, (mcfg["conv_kernel"],))
        c_prev = ch
    for i, fc in enumerate(mcfg["fc_dims"]):
        fp.draw_dense(rng, w, f"fc{i}", c_prev, fc)
        c_prev = fc
    fp.draw_dense(rng, w, "head", c_prev, mcfg["num_classes"])
    sa = {k: fp.shift_for(v) for k, v in _float_sites(w, mcfg, calib).items()}
    qp = fp.quantize_embed(w, sa["embed"])
    prev = sa["embed"]
    for i in range(len(mcfg["conv_filters"])):
        prev = fp.quantize_dense(w, qp, f"conv{i}", prev, sa[f"conv{i}"])
    qp["pool/mult"] = np.int32(round((1 << 15) / mcfg["seq_len"]))
    for i in range(len(mcfg["fc_dims"])):
        prev = fp.quantize_dense(w, qp, f"fc{i}", prev, sa[f"fc{i}"])
    fp.quantize_dense(w, qp, "head", prev, max(sa["head"], 0))
    return qp


def forward(qp: Dict, mcfg: Dict, payload: np.ndarray,
            bits: int = 8) -> np.ndarray:
    """[N, T, 2] payload windows -> [N, classes] int64 logits."""
    g = fp.on_grid(qp, bits)
    x = fp.embed_int(g, mcfg, payload)
    b, t, _ = x.shape
    k = mcfg["conv_kernel"]
    for i in range(len(mcfg["conv_filters"])):
        wq = g[f"conv{i}/w"]
        acc = fp.mm(_im2col(x, k), wq.reshape(-1, wq.shape[-1])) \
            + g[f"conv{i}/b"]
        x = fp.grid(np.maximum(fp.requant(acc, int(g[f"conv{i}/shift"])),
                               0), bits).reshape(b, t, -1)
    x = ((x.sum(axis=1) * int(g["pool/mult"])) >> 15).astype(
        np.int8).astype(np.int64)
    x = fp.grid(x, bits)
    for i in range(len(mcfg["fc_dims"])):
        acc = fp.mm(x, g[f"fc{i}/w"]) + g[f"fc{i}/b"]
        x = fp.grid(np.maximum(fp.requant(acc, int(g[f"fc{i}/shift"])), 0),
                    bits)
    return fp.mm(x, g["head/w"]) + g["head/b"]


def gemms(mcfg: Dict):
    """``fenix_int8_gemm`` calls per inference: (rows, K, N, out_bytes),
    rows being the GEMM rows one inference contributes."""
    t = mcfg["seq_len"]
    out = []
    c_prev = 2 * mcfg["embed_dim"]
    for ch in mcfg["conv_filters"]:
        out.append((t, mcfg["conv_kernel"] * c_prev, ch, 1))
        c_prev = ch
    for fc in mcfg["fc_dims"]:
        out.append((1, c_prev, fc, 1))
        c_prev = fc
    out.append((1, c_prev, mcfg["num_classes"], 4))
    return out


def macs_per_inference(mcfg: Dict) -> int:
    """Every MAC of the model is in its GEMMs."""
    return sum(r * k * n for r, k, n, _ in gemms(mcfg))
