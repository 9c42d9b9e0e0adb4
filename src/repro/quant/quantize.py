"""Post-training INT8 fixed-point quantization (paper §6 "Model Training
and Quantization", Vitis-AI analogue).

Power-of-two scales everywhere ("assigns different decimal point positions
to different layers"): an activation x is represented as x_q = round(x*2^sa)
int8; a weight as w_q = round(w*2^sw).  A layer's int32 accumulator then
carries scale 2^(sa_in+sw) and is requantized to the next activation grid by
a single right-shift — no multipliers, exactly what the FPGA (and the
Pallas int8 kernel) executes.

Nonlinearities: relu is a clip; tanh (RNN cell) is a 512-entry int8 LUT
indexed by the pre-activation's high bits — the standard FPGA mapping —
read on the device as a compare-and-select staircase (``lut_lookup``).
"""

from __future__ import annotations

from typing import Callable, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.fenix_models import TrafficModelConfig
from repro.models import traffic

I32 = jnp.int32
I8 = jnp.int8


def _shift_for(absmax: float) -> int:
    """Largest s with absmax * 2^s <= 127 (decimal point position)."""
    absmax = max(float(absmax), 1e-8)
    return int(np.floor(np.log2(127.0 / absmax)))


def _q(x: np.ndarray, shift: int, dtype=np.int8) -> np.ndarray:
    lim = 127 if dtype == np.int8 else 2**31 - 1
    return np.clip(np.round(np.asarray(x, np.float64) * (1 << shift)
                            if shift >= 0 else
                            np.asarray(x, np.float64) / (1 << -shift)),
                   -lim, lim).astype(dtype)


def quantize_array(x: np.ndarray, shift: int, dtype=np.int8) -> np.ndarray:
    """Fixed-point quantize: ``round(x * 2^shift)`` saturated to dtype.

    ``shift`` is the decimal-point position (2^-shift is the grid step);
    negative shifts divide instead.  The public form of the scheme every
    weight/activation in this module uses.
    """
    return _q(x, shift, dtype)


def dequantize_array(x_q: np.ndarray, shift: int) -> np.ndarray:
    """Inverse grid map: ``x_q * 2^-shift`` (float64).

    Round-trip contract (tests/test_quantize.py): for |x| <= dtype_max *
    2^-shift, ``|dequantize(quantize(x)) - x| <= 2^-(shift+1)`` — half a
    grid step; values beyond the representable range saturate.
    """
    return np.asarray(x_q, np.float64) * (2.0 ** -shift)


def tanh_table(sh: int) -> np.ndarray:
    """The RNN cell's 512-entry int8 tanh table on the 2^-sh output grid:
    entry ``i`` is ``tanh((i - 256) * 2^-4)``, rounded and saturated."""
    return _q(np.tanh(np.arange(-256, 256) / (1 << 4)), sh)


def lut_lookup(lut: jax.Array) -> Callable[[jax.Array], jax.Array]:
    """``idx -> lut[idx]`` for integer indices in ``[0, len(lut))``, bit
    for bit, without a gather.

    The table is read as a staircase: start from ``lut[0]`` and, in
    ascending order of each index ``k`` where the entry differs from the
    one before, take ``lut[k]`` wherever ``idx >= k``.  The table is
    constant between those indices, so the last ``k <= idx`` gives
    ``lut[idx]``, whatever the table's shape.  That is one compare and one
    select per change, element-wise on the vector unit (60 for
    ``tanh_table(6)``), where a gather on the TPU costs far more.  ``lut``
    must be concrete, as are the shifts ``int8_apply`` reads.
    """
    t = np.asarray(lut).astype(np.int64)
    steps = [int(k) for k in np.flatnonzero(np.diff(t)) + 1]

    def lookup(idx: jax.Array) -> jax.Array:
        out = jnp.full(idx.shape, int(t[0]), I32)
        for k in steps:
            out = jnp.where(idx >= k, int(t[k]), out)
        return out.astype(lut.dtype)
    return lookup


def _collect_activations(params: Dict, cfg: TrafficModelConfig,
                         payloads: jax.Array) -> Dict[str, float]:
    """Float forward, recording absmax at every quantization site."""
    sites: Dict[str, float] = {}

    def rec(name, x):
        sites[name] = max(sites.get(name, 0.0), float(jnp.max(jnp.abs(x))))
        return x

    ids = traffic.bucketize(payloads, cfg)
    x = rec("embed", traffic.embed_ids(params, ids))
    if cfg.kind == "cnn":
        for i in range(len(cfg.conv_filters)):
            x = rec(f"conv{i}", jax.nn.relu(traffic._conv1d(
                x, params[f"conv{i}/w"], params[f"conv{i}/b"])))
        x = rec("pool", jnp.mean(x, axis=1))
        for i in range(len(cfg.fc_dims)):
            x = rec(f"fc{i}", jax.nn.relu(
                x @ params[f"fc{i}/w"] + params[f"fc{i}/b"]))
        rec("head", x @ params["head/w"] + params["head/b"])
    else:
        def cell(h, xt):
            pre = xt @ params["cell/wx"] + h @ params["cell/wh"] \
                + params["cell/b"]
            h2 = jnp.tanh(pre)
            return h2, pre

        h0 = jnp.zeros((x.shape[0], cfg.rnn_units), x.dtype)
        h, pres = jax.lax.scan(cell, h0, x.swapaxes(0, 1))
        rec("cell_pre", pres)
        rec("cell", h)
        rec("head", h @ params["head/w"] + params["head/b"])
    return sites


def quantize_traffic(params: Dict, cfg: TrafficModelConfig,
                     calib_payloads: jax.Array) -> Dict:
    """Returns the integer model: int8 weights/tables + per-layer shifts."""
    sites = _collect_activations(params, cfg, calib_payloads)
    sa: Dict[str, int] = {k: min(_shift_for(v), 12)
                          for k, v in sites.items()}
    qp: Dict[str, np.ndarray] = {"cfg_shifts": sa}

    def qlayer(name, w, b, sa_in, sa_out):
        sw = min(_shift_for(np.max(np.abs(np.asarray(w)))), 12)
        qp[f"{name}/w"] = _q(np.asarray(w), sw)
        qp[f"{name}/b"] = _q(np.asarray(b), sa_in + sw, np.int32)
        shift = sa_in + sw - sa_out
        assert shift >= 0, (name, sa_in, sw, sa_out)
        qp[f"{name}/shift"] = shift

    se = sa["embed"]
    qp["embed_len/table"] = _q(np.asarray(params["embed_len/table"]), se)
    qp["embed_ipd/table"] = _q(np.asarray(params["embed_ipd/table"]), se)
    if cfg.kind == "cnn":
        prev = "embed"
        for i in range(len(cfg.conv_filters)):
            qlayer(f"conv{i}", params[f"conv{i}/w"], params[f"conv{i}/b"],
                   sa[prev], sa[f"conv{i}"])
            prev = f"conv{i}"
        # integer mean over T: (sum * mult) >> 15, then rescale to pool grid
        sa["pool"] = sa[prev]
        qp["pool/mult"] = np.int32(round((1 << 15) / cfg.seq_len))
        prev = "pool"
        for i in range(len(cfg.fc_dims)):
            qlayer(f"fc{i}", params[f"fc{i}/w"], params[f"fc{i}/b"],
                   sa[prev], sa[f"fc{i}"])
            prev = f"fc{i}"
        qlayer("head", params["head/w"], params["head/b"], sa[prev],
               max(sa["head"], 0))
    else:
        # RNN: both matmuls accumulate on the cell_pre grid
        sa_pre = sa["cell_pre"]
        sh = sa["cell"]
        swx = min(_shift_for(np.max(np.abs(np.asarray(
            params["cell/wx"])))), 12)
        swh = min(_shift_for(np.max(np.abs(np.asarray(
            params["cell/wh"])))), 12)
        qp["cell/wx"] = _q(np.asarray(params["cell/wx"]), swx)
        qp["cell/wh"] = _q(np.asarray(params["cell/wh"]), swh)
        qp["cell/b"] = _q(np.asarray(params["cell/b"]), sa["embed"] + swx,
                          np.int32)
        qp["cell/shift_x"] = sa["embed"] + swx - sa_pre
        qp["cell/shift_h"] = sh + swh - sa_pre
        assert qp["cell/shift_x"] >= 0 and qp["cell/shift_h"] >= 0
        # tanh LUT: index = clip(pre_q >> (sa_pre-4), -256, 255)
        qp["tanh_lut"] = tanh_table(sh)
        qp["cell/lut_preshift"] = sa_pre - 4
        qlayer("head", params["head/w"], params["head/b"], sh,
               max(sa["head"], 0))
    return jax.tree.map(jnp.asarray, qp)


# ---------------------------------------------------------------------------
# Integer-only inference (mirrors traffic.apply layer-for-layer)
# ---------------------------------------------------------------------------


def int8_apply(qp: Dict, cfg: TrafficModelConfig, payload: jax.Array,
               backend: Optional[str] = None) -> jax.Array:
    """payload [B,T,2] int32 -> logits int32 [B,classes]. Integer path.

    ``backend`` picks the int8 GEMM (``kernels/int8_matmul/ops.py``;
    None is the platform default)."""
    from repro.kernels.int8_matmul.ops import int8_conv1d, int8_matmul

    ids = traffic.bucketize(payload, cfg)
    el = jnp.take(qp["embed_len/table"], ids[..., 0], axis=0)
    ei = jnp.take(qp["embed_ipd/table"], ids[..., 1], axis=0)
    x = jnp.concatenate([el, ei], axis=-1)            # int8 [B,T,2E]
    b, t, _ = x.shape
    if cfg.kind == "cnn":
        for i in range(len(cfg.conv_filters)):
            x = int8_conv1d(x, qp[f"conv{i}/w"], qp[f"conv{i}/b"],
                            int(qp[f"conv{i}/shift"]), backend=backend)
            x = jnp.maximum(x, 0)                     # relu on the int8 grid
        xs = jnp.sum(x.astype(I32), axis=1)           # [B, C]
        x = ((xs * qp["pool/mult"]) >> 15).astype(I8)
        for i in range(len(cfg.fc_dims)):
            x = int8_matmul(x, qp[f"fc{i}/w"], qp[f"fc{i}/b"],
                            int(qp[f"fc{i}/shift"]), backend=backend)
            x = jnp.maximum(x, 0)
        return int8_matmul(x, qp["head/w"], qp["head/b"], None,
                           backend=backend)
    # rnn
    tanh = lut_lookup(qp["tanh_lut"])

    def cell(h, xt):
        accx = int8_matmul(xt, qp["cell/wx"], qp["cell/b"], None,
                           backend=backend)
        acch = int8_matmul(h, qp["cell/wh"], None, None, backend=backend)
        sx = int(qp["cell/shift_x"])
        sh_ = int(qp["cell/shift_h"])
        pre = (accx >> sx if sx > 0 else accx) \
            + (acch >> sh_ if sh_ > 0 else acch)      # on the cell_pre grid
        lidx = jnp.clip(pre >> int(qp["cell/lut_preshift"]), -256, 255)
        h2 = tanh(lidx + 256)
        return h2, None

    h0 = jnp.zeros((b, cfg.rnn_units), I8)
    h, _ = jax.lax.scan(cell, h0, x.swapaxes(0, 1))
    return int8_matmul(h, qp["head/w"], qp["head/b"], None, backend=backend)
