"""The benchmark's model kinds, one module each: ``fenixbench/models/
<kind>.py``, found by the configuration's ``model.kind``.

A kind module is the yardstick for one model architecture, and imports
nothing of the program.  It exports:

* ``make_model(mcfg, seed, calib) -> qp``: the integer model in the
  layout the program's ``EngineModel`` serves, its weights drawn from the
  seed and its scales calibrated on ``calib`` ([N, T, 2] int32 payload
  windows of the cell's traffic);
* ``forward(qp, mcfg, payload, bits=8) -> logits``: the plain numpy
  reference; ``bits=4`` is the precision control;
* ``gemms(mcfg)``: the ``(rows, K, N, out_bytes)`` calls that
  ``fenix_int8_gemm`` runs per inference;
* ``macs_per_inference(mcfg)``: the whole model's useful MACs;
* ``VERDICT_GAP_LIMIT``: the limit of ``verdict_gap`` for this kind, with
  its reason (0 where both sides compute exact integer logits).

``fixed_point`` holds the arithmetic the INT8 kinds share.
"""

from __future__ import annotations

import importlib
from types import ModuleType
from typing import Dict

import numpy as np

CONTRACT = ("make_model", "forward", "gemms", "macs_per_inference",
            "VERDICT_GAP_LIMIT")


def get(kind: str) -> ModuleType:
    """The module of model kind ``kind``; an unknown kind is an error
    that names the file to add."""
    add = (f"add fenixbench/models/{kind}.py exporting "
           f"{', '.join(CONTRACT)}")
    if not kind.isidentifier():
        raise ValueError(f"model kind {kind!r} is not a module name")
    name = f"{__name__}.{kind}"
    try:
        mod = importlib.import_module(name)
    except ModuleNotFoundError as e:
        if e.name != name:
            raise
        raise ValueError(f"unknown model kind {kind!r}: {add}") from None
    missing = [a for a in CONTRACT if not hasattr(mod, a)]
    if missing:
        raise ValueError(f"model kind {kind!r} lacks {', '.join(missing)}: "
                         f"{add}")
    return mod


def classify(qp: Dict, mcfg: Dict, payload: np.ndarray,
             bits: int = 8, block: int = 4096) -> np.ndarray:
    """Logits of the kind's ``forward`` in blocks of rows (bounded host
    memory)."""
    fwd = get(mcfg["kind"]).forward
    out = [fwd(qp, mcfg, payload[i:i + block], bits)
           for i in range(0, len(payload), block)]
    return (np.concatenate(out) if out
            else np.zeros((0, mcfg["num_classes"]), np.int64))
