"""Plain numpy oracle for the integer RNN that ``int8_apply`` serves.

int64 products and sums, floor shifts, and the tanh table indexed — the
form every lookup of the table on the device must equal bit for bit.
"""

from __future__ import annotations

from typing import Dict, Tuple

import jax.numpy as jnp
import numpy as np

from repro.configs.fenix_models import TrafficModelConfig
from repro.models import traffic


def int8_rnn_ref(qp: Dict, cfg: TrafficModelConfig, payload: np.ndarray
                 ) -> Tuple[np.ndarray, np.ndarray]:
    """[N, T, 2] payload windows -> ([N, classes] logits, [N, units] last
    hidden state), both int64."""
    q = {k: np.asarray(v).astype(np.int64) for k, v in qp.items()
         if k != "cfg_shifts"}
    ids = np.asarray(traffic.bucketize(jnp.asarray(payload), cfg))
    x = np.concatenate([q["embed_len/table"][ids[..., 0]],
                        q["embed_ipd/table"][ids[..., 1]]], axis=-1)
    sx, sh = int(q["cell/shift_x"]), int(q["cell/shift_h"])
    pre_shift = int(q["cell/lut_preshift"])
    h = np.zeros((len(x), cfg.rnn_units), np.int64)
    for s in range(cfg.seq_len):
        pre = ((x[:, s] @ q["cell/wx"] + q["cell/b"]) >> sx) \
            + ((h @ q["cell/wh"]) >> sh)
        h = q["tanh_lut"][np.clip(pre >> pre_shift, -256, 255) + 256]
    return h @ q["head/w"] + q["head/b"], h
