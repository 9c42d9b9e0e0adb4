"""DNN Inference Module (§5.2): INT8 CNN/RNN on the systolic GEMM.

Executes the quantized traffic model (quant/quantize.py) over feature
batches; every matmul/conv maps onto kernels/int8_matmul — the same
"one systolic array, many layer types" structure as the FPGA.  A simple
cycle model provides the latency/throughput numbers for the Figure 11
microbenchmark: MACs / (array_width^2 * f_clk) plus a fixed pipeline fill.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Optional

import jax
import jax.numpy as jnp

from repro.configs.fenix_models import TrafficModelConfig
from repro.quant.quantize import int8_apply


@dataclasses.dataclass(frozen=True)
class EngineModel:
    """A quantized traffic model serving on the INT8 systolic GEMM.

    ``qparams`` is the integer model from ``quant.quantize_traffic`` (or a
    ``serving.load_quantized`` checkpoint); ``backend`` selects the
    ``kernels/int8_matmul`` implementation for every GEMM this model runs
    — None (the platform's) or one of ``kernels.backend.BACKENDS``, threaded from
    ``FenixConfig(matmul_backend=...)`` by the serving factory.
    """

    cfg: TrafficModelConfig
    qparams: Dict
    backend: Optional[str] = None   # None: platform default; see ops

    @property
    def num_classes(self) -> int:
        return self.cfg.num_classes

    def infer(self, payload: jax.Array) -> jax.Array:
        """payload [B, T, 2] int32 -> class [B] int32."""
        logits = int8_apply(self.qparams, self.cfg, payload,
                            backend=self.backend)
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)

    def infer_engines(self, payload: jax.Array) -> jax.Array:
        """payload [E, B, T, 2] int32 -> class [E, B] int32.

        Batched farm inference: each engine's service batch runs through
        the same quantized model in one fused pass (classes are per-lane,
        so batching across engines cannot change any verdict).  Used when
        a single host drives several engines' lanes at once — the sharded
        farm step instead calls ``infer`` per engine shard.
        """
        e, b = payload.shape[:2]
        flat = payload.reshape((e * b,) + payload.shape[2:])
        return self.infer(flat).reshape(e, b)


class ByLenModel:
    """Deterministic stand-in Model Engine: class = F9 pkt_len mod 7.

    The one shared copy for benchmarks/examples/tests that measure the
    data plane and drivers rather than DNN quality — cross-driver
    identity assertions must compare against the *same* model, so do not
    redeclare this locally.
    """

    num_classes = 7

    def infer(self, payload: jax.Array) -> jax.Array:
        return (payload[:, -1, 0] % self.num_classes).astype(jnp.int32)

    def infer_engines(self, payload: jax.Array) -> jax.Array:
        return (payload[:, :, -1, 0] % self.num_classes).astype(jnp.int32)


def macs_per_inference(cfg: TrafficModelConfig) -> int:
    """Multiply-accumulates for one feature window (cycle model input)."""
    e = cfg.embed_dim
    d_in = 2 * e
    t = cfg.seq_len
    total = 0
    if cfg.kind == "cnn":
        c_prev = d_in
        for ch in cfg.conv_filters:
            total += t * cfg.conv_kernel * c_prev * ch
            c_prev = ch
        f_prev = c_prev
        for fc in cfg.fc_dims:
            total += f_prev * fc
            f_prev = fc
        total += f_prev * cfg.num_classes
    else:
        u = cfg.rnn_units
        total += t * (d_in * u + u * u)
        total += u * cfg.num_classes
    return total


@dataclasses.dataclass(frozen=True)
class CycleModel:
    """ZU19EG-style array: width x width INT8 MACs at f_clk."""
    array_width: int = 32
    f_clk_hz: float = 300e6
    pipeline_fill_cycles: int = 64

    def latency_us(self, cfg: TrafficModelConfig) -> float:
        macs = macs_per_inference(cfg)
        cycles = macs / (self.array_width ** 2) + self.pipeline_fill_cycles
        return cycles / self.f_clk_hz * 1e6

    def throughput_inf_per_s(self, cfg: TrafficModelConfig) -> float:
        macs = macs_per_inference(cfg)
        return self.f_clk_hz * self.array_width ** 2 / macs

    # -- engine-farm accounting (E independent arrays, ISSUE 3) -------------
    def farm_throughput_inf_per_s(self, cfg: TrafficModelConfig,
                                  num_engines: int) -> float:
        """Aggregate service rate of ``num_engines`` independent engines.

        Engines drain their ingress queues independently (no cross-engine
        pipeline), so farm throughput is additive.
        """
        return num_engines * self.throughput_inf_per_s(cfg)

    def farm_batch_latency_us(self, cfg: TrafficModelConfig, batch: int,
                              num_engines: int) -> float:
        """Service latency of a ``batch`` split across ``num_engines``.

        The router balances the batch (ceil split); each engine pipelines
        its share through its own systolic array: one fill + latency for
        the first inference, then one result per ``macs / width^2`` cycles.
        ``num_engines=1`` degenerates to the single-engine batch latency.
        """
        per_engine = -(-batch // max(num_engines, 1))
        if per_engine <= 0:
            return 0.0
        macs = macs_per_inference(cfg)
        issue_us = macs / (self.array_width ** 2) / self.f_clk_hz * 1e6
        return self.latency_us(cfg) + (per_engine - 1) * issue_us
