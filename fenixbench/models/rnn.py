"""FENIX-RNN (paper §7.1): one tanh cell over the embedded window and a
linear head.  The cell's int32 pre-activation is brought to a 2^-4 grid
and indexed into a 512-entry int8 tanh table by its high bits."""

from __future__ import annotations

from typing import Dict

import numpy as np

from fenixbench.models import fixed_point as fp

# integer logits on both sides, and the reference indexes the same table
# the program reads: a sound run reads 0
VERDICT_GAP_LIMIT = 0


def _float_sites(w: Dict, mcfg: Dict, payload: np.ndarray) -> Dict:
    """Float64 forward of the unquantized weights: absmax per site."""
    x = fp.embed_float(w, mcfg, payload)
    sites = {"embed": fp.absmax(x)}
    b, t, _ = x.shape
    h = np.zeros((b, mcfg["rnn_units"]))
    pres = []
    for s in range(t):
        pre = x[:, s] @ w["cell/wx"] + h @ w["cell/wh"] + w["cell/b"]
        pres.append(pre)
        h = np.tanh(pre)
    sites["cell_pre"] = fp.absmax(np.stack(pres))
    sites["cell"] = fp.absmax(h)
    sites["head"] = fp.absmax(h @ w["head/w"] + w["head/b"])
    return sites


def make_model(mcfg: Dict, seed: int, calib: np.ndarray) -> Dict:
    """Integer model from the seed, calibrated on ``calib`` payloads
    ([N, T, 2] int32 windows of the cell's traffic)."""
    rng, w = fp.draw_embed(mcfg, seed)
    e2, u = 2 * mcfg["embed_dim"], mcfg["rnn_units"]
    w["cell/wx"] = rng.normal(0, e2 ** -0.5, (e2, u))
    w["cell/wh"] = rng.normal(0, u ** -0.5, (u, u))
    w["cell/b"] = rng.normal(0, 0.05, (u,))
    fp.draw_dense(rng, w, "head", u, mcfg["num_classes"])
    sa = {k: fp.shift_for(v) for k, v in _float_sites(w, mcfg, calib).items()}
    se, sh = sa["embed"], sa["cell"]
    qp = fp.quantize_embed(w, se)
    swx = fp.shift_for(fp.absmax(w["cell/wx"]))
    swh = fp.shift_for(fp.absmax(w["cell/wh"]))
    sa_pre = min(max(sa["cell_pre"], 4), se + swx, sh + swh)
    qp["cell/wx"] = fp.q(w["cell/wx"], swx)
    qp["cell/wh"] = fp.q(w["cell/wh"], swh)
    qp["cell/b"] = fp.q(w["cell/b"], se + swx, np.int32)
    qp["cell/shift_x"] = np.int32(se + swx - sa_pre)
    qp["cell/shift_h"] = np.int32(sh + swh - sa_pre)
    qp["tanh_lut"] = fp.q(np.tanh(np.arange(-256, 256) / 16.0), sh)
    qp["cell/lut_preshift"] = np.int32(sa_pre - 4)
    fp.quantize_dense(w, qp, "head", sh, max(sa["head"], 0))
    return qp


def forward(qp: Dict, mcfg: Dict, payload: np.ndarray,
            bits: int = 8) -> np.ndarray:
    """[N, T, 2] payload windows -> [N, classes] int64 logits."""
    g = fp.on_grid(qp, bits)
    x = fp.embed_int(g, mcfg, payload)
    b, t, _ = x.shape
    sx, sh = int(g["cell/shift_x"]), int(g["cell/shift_h"])
    pre_shift = int(g["cell/lut_preshift"])
    h = np.zeros((b, mcfg["rnn_units"]), np.int64)
    for s in range(t):
        accx = fp.mm(x[:, s], g["cell/wx"]) + g["cell/b"]
        acch = fp.mm(h, g["cell/wh"])
        pre = (accx >> sx) + (acch >> sh)
        lidx = np.clip(pre >> pre_shift, -256, 255)
        h = g["tanh_lut"][lidx + 256]
    return fp.mm(h, g["head/w"]) + g["head/b"]


def gemms(mcfg: Dict):
    """``fenix_int8_gemm`` calls per inference: (rows, K, N, out_bytes),
    the cell's two GEMMs listed once per timestep."""
    u = mcfg["rnn_units"]
    out = []
    for _ in range(mcfg["seq_len"]):
        out.append((1, 2 * mcfg["embed_dim"], u, 4))
        out.append((1, u, u, 4))
    out.append((1, u, mcfg["num_classes"], 4))
    return out


def macs_per_inference(mcfg: Dict) -> int:
    """Every MAC of the model is in its GEMMs."""
    return sum(r * k * n for r, k, n, _ in gemms(mcfg))
