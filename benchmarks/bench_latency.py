"""Figure 11 analogue: latency breakdown, FENIX vs control-plane path.

Components (paper): internal transmission (PCB, sub-us), external
transmission (optical, 1-3us), inference (FENIX 1.2us FPGA vs FlowLens
>1000us CPU).  We report:
  - the FPGA cycle-model latency of our INT8 models (ZU19EG-like array)
  - measured CPU wall-time per inference (this container, for reference),
    for BOTH the served INT8 integer path (kernels/int8_matmul, "ref"
    backend) and the float parent model — the int8-vs-float serving
    comparison of the Fig. 11 analogue
  - engine-farm service latency of a 128-window batch at E in {1, 2, 4}
  - the control-plane path modeled with the paper's measured RTTs.
"""

from __future__ import annotations

import time
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks._io import write_json_atomic
from repro.configs.fenix_models import fenix_cnn, fenix_rnn
from repro.core.model_engine.inference import (CycleModel, EngineModel,
                                               macs_per_inference)
from repro.data.synthetic_traffic import make_flows, windows_from_flows
from repro.models import traffic
from repro.quant.quantize import quantize_traffic

# paper Figure 11 measurements (for the comparison rows)
PAPER = {
    "fenix": {"internal_us": 0.8, "external_us": 2.0, "inference_us": 1.2},
    "flowlens": {"transmission_us": 2100.0, "inference_us": 1500.0},
}


def main(out_path: str = None) -> Dict:
    flows = make_flows("iscx", 60, seed=0)
    x, _, _ = windows_from_flows(flows)
    out: Dict[str, Dict] = {"paper_fig11": PAPER}
    cm = CycleModel()
    for mk in (fenix_cnn, fenix_rnn):
        cfg = mk(7)
        params = traffic.init(cfg, 0)
        qp = quantize_traffic(params, cfg, jnp.asarray(x[:128]))
        model = EngineModel(cfg, qp)
        batch = jnp.asarray(x[:128])
        model.infer(batch)  # warm up / compile
        t0 = time.time()
        reps = 20
        for _ in range(reps):
            r = model.infer(batch)
        jax.block_until_ready(r)
        cpu_us = (time.time() - t0) / reps / batch.shape[0] * 1e6
        # float parent model on the same batch: what serving would cost
        # without quantization (per-inference wall time, this container)
        float_fn = jax.jit(lambda p, b: jnp.argmax(
            traffic.apply(p, cfg, b), -1))
        jax.block_until_ready(float_fn(params, batch))
        t0 = time.time()
        for _ in range(reps):
            r = float_fn(params, batch)
        jax.block_until_ready(r)
        float_us = (time.time() - t0) / reps / batch.shape[0] * 1e6
        # engine-farm service: the same 128-window batch split across E
        # engines (cycle model) and the fused multi-engine inference pass
        # (one infer_engines call serving every engine's lanes at once)
        farm_batch = jnp.asarray(x[:128]).reshape(4, 32, *x.shape[1:])
        fused = model.infer_engines(farm_batch)
        np.testing.assert_array_equal(np.asarray(fused).reshape(-1),
                                      np.asarray(model.infer(batch)))
        t0 = time.time()
        for _ in range(reps):
            r = model.infer_engines(farm_batch)
        jax.block_until_ready(r)
        fused_us = (time.time() - t0) / reps / batch.shape[0] * 1e6
        out[cfg.name] = {
            "macs_per_window": macs_per_inference(cfg),
            "fpga_cycle_model_us": cm.latency_us(cfg),
            "fpga_throughput_inf_s": cm.throughput_inf_per_s(cfg),
            "farm_batch128_us": {
                e: cm.farm_batch_latency_us(cfg, 128, e)
                for e in (1, 2, 4)},
            "farm4_fused_cpu_us_per_inf": fused_us,
            "cpu_measured_us_per_inf": cpu_us,
            "float_cpu_us_per_inf": float_us,
            "int8_vs_float_cpu_ratio": cpu_us / max(float_us, 1e-9),
            "speedup_vs_control_plane":
                (PAPER["flowlens"]["transmission_us"]
                 + PAPER["flowlens"]["inference_us"])
                / (PAPER["fenix"]["external_us"] + cm.latency_us(cfg)),
        }
    if out_path:
        write_json_atomic(out_path, out)
    return out


if __name__ == "__main__":
    import pprint
    pprint.pprint(main())
