"""Compile-only guards: the FENIX hot path compiled for a TPU v5e.

Every test here AOT-compiles (``jax.jit(...).lower(...).compile()``) for a
*described* ``v5e:2x2`` chip — the TPU compiler is installed even where no
chip is attached — so a kernel Mosaic refuses fails here, at no chip time:

* the fused admission gate at batch 4096, with supplied draws and with the
  on-core PRNG;
* the int8 GEMM at every layer shape the paper CNN (``fenix_cnn``) issues
  at the default 1024 service lanes;
* one jitted scan of the single-pipe device driver with both compiled
  backends, serving the paper CNN, whose ops carry every stage scope;
* the paper RNN (``fenix_rnn``) over 1024 lanes, whose cell loop holds
  its two GEMMs and no gather (the tanh table is read element-wise).

Nothing runs, so results are not checked here: the kernels' bit-for-bit
parity is the interpret-mode suites' job, and on the chip ``chip_smoke.py``
repeats it with the compiled kernels.  The topology is described inside a
fixture, never at import, and the persistent compilation cache is off
around the compiles (a described chip's executables cannot be read back).
"""

import os
import re
from typing import Dict, Set

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro import tracing
from repro.configs.fenix_models import fenix_cnn, fenix_rnn
from repro.core.data_engine.state import EngineConfig
from repro.core.fenix import PKT_KEYS, FenixConfig, FenixSystem
from repro.core.model_engine.inference import EngineModel
from repro.kernels import backend as kernel_backend
from repro.kernels.int8_matmul import ops as mm_ops
from repro.kernels.int8_matmul.kernel import int8_matmul_pallas
from repro.kernels.rate_gate.kernel import fused_gate_pallas
from repro.models import traffic
from repro.quant.quantize import int8_apply, quantize_traffic

pytestmark = pytest.mark.lowering

BATCH = 4096
LANES = 1024          # IOConfig().queue_len: the Model Engine's lanes
I32 = jnp.int32


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means no topology
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def no_persistent_cache():
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _spec(one_chip, shape, dtype=I32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


def _compiled_text(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("draws", ["supplied", "tpu_prng"])
def test_fused_gate_compiles(one_chip, draws):
    lut = _spec(one_chip, (64, 32))
    scal = _spec(one_chip, (1, 5))
    pkts = [_spec(one_chip, (BATCH,)) for _ in range(3)]
    if draws == "supplied":
        def gate(t, c, ts, lut, scal, r):
            return fused_gate_pallas(t, c, ts, lut, scal, rand16=r,
                                     tile_rows=32, interpret=False)
        text = _compiled_text(gate, *pkts, lut, scal,
                              _spec(one_chip, (BATCH,)))
    else:
        def gate(t, c, ts, lut, scal):
            return fused_gate_pallas(t, c, ts, lut, scal, tile_rows=32,
                                     interpret=False, use_tpu_prng=True)
        text = _compiled_text(gate, *pkts, lut, scal)
    assert "tpu_custom_call" in text


def _cnn_gemm_shapes(monkeypatch):
    """(m, k, n, has_bias, shift) of every int8_matmul that int8_apply
    issues for the paper CNN over LANES service lanes (random weights)."""
    mcfg = fenix_cnn()
    rng = np.random.default_rng(0)
    calib = rng.integers(1, 1500, (64, mcfg.seq_len, 2)).astype(np.int32)
    qp = quantize_traffic(traffic.init(mcfg, seed=0), mcfg,
                          jnp.asarray(calib))
    calls = []
    real = mm_ops.int8_matmul

    def record(a, b, bias=None, shift=None, backend=None):
        calls.append((a.shape[0], a.shape[1], b.shape[1], bias is not None,
                      shift))
        return real(a, b, bias=bias, shift=shift, backend="ref")

    monkeypatch.setattr(mm_ops, "int8_matmul", record)
    jax.eval_shape(lambda x: int8_apply(qp, mcfg, x),
                   jax.ShapeDtypeStruct((LANES, mcfg.seq_len, 2), I32))
    return calls


def test_int8_gemm_compiles_at_paper_cnn_shapes(one_chip, monkeypatch):
    shapes = _cnn_gemm_shapes(monkeypatch)
    assert len(shapes) == 6          # conv x3, fc x2, head
    for m, k, n, has_bias, shift in shapes:
        pad = lambda x: -(-x // 128) * 128               # noqa: E731
        a = _spec(one_chip, (pad(m), pad(k)), jnp.int8)
        b = _spec(one_chip, (pad(k), pad(n)), jnp.int8)
        bias = _spec(one_chip, (pad(n),)) if has_bias else None

        def gemm(a, b, bias):
            return int8_matmul_pallas(a, b, bias=bias, shift=shift,
                                      interpret=False)
        assert "tpu_custom_call" in _compiled_text(gemm, a, b, bias), \
            (m, k, n)


@pytest.fixture(scope="module")
def scan_text(one_chip):
    """One jitted device-driver scan (2 steps of BATCH packets) serving
    the paper CNN, with both kernels on their compiled backends, as the
    compiled module's text."""
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(kernel_backend, "on_tpu", lambda: True)
        mcfg = fenix_cnn()
        calib = np.full((8, mcfg.seq_len, 2), 100, np.int32)
        qp = quantize_traffic(traffic.init(mcfg, seed=0), mcfg,
                              jnp.asarray(calib))
        cfg = FenixConfig(driver="device", batch_size=BATCH,
                          engine=EngineConfig(gate_backend="pallas_tpu"),
                          matmul_backend="pallas_tpu")
        sys_ = FenixSystem(cfg, EngineModel(mcfg, qp))
        sys_._ensure_jits()

        def on_chip(x):
            return _spec(one_chip, x.shape, x.dtype)

        carry = jax.tree.map(on_chip, (sys_.state, sys_.queues, sys_._dl))
        dtypes = {"src_ip": jnp.uint32, "dst_ip": jnp.uint32,
                  "src_port": jnp.uint32, "dst_port": jnp.uint32,
                  "proto": jnp.uint32, "ts_us": I32, "pkt_len": I32}
        assert set(dtypes) == set(PKT_KEYS)
        chunks = {k: _spec(one_chip, (2, BATCH), dt)
                  for k, dt in dtypes.items()}
        chunks["_cp"] = _spec(one_chip, (2,), jnp.bool_)
        return sys_._scan_jit.lower(carry, chunks).compile().as_text()


def test_single_pipe_scan_compiles_with_compiled_kernels(scan_text):
    # the fused gate plus one GEMM per CNN layer
    assert scan_text.count("tpu_custom_call") >= 7


def test_single_pipe_scan_names_every_stage(scan_text):
    """The compiled scan carries all ten stage scopes, and the stage map
    puts each compiled kernel in its stage."""
    stage_of = tracing.stages_in_hlo(scan_text)
    assert set(tracing.STAGES) <= set(stage_of.values())
    kernels = {n: s for n, s in stage_of.items()
               if n.split(".")[0] in ("fenix_fused_gate", "fenix_int8_gemm")}
    assert {s for n, s in kernels.items()
            if n.startswith("fenix_fused_gate")} == {"gate"}
    gemms = [s for n, s in kernels.items() if n.startswith("fenix_int8_gemm")]
    assert len(gemms) >= 6 and set(gemms) == {"infer"}


def _computations(text: str) -> Dict[str, str]:
    """HLO module text -> {computation name: its body}."""
    comps, name, lines = {}, None, []
    for line in text.splitlines():
        head = re.match(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s.*\{\s*$", line)
        if name is None and head:
            name, lines = head.group(1), []
        elif name is not None and line.strip() == "}":
            comps[name] = "\n".join(lines)
            name = None
        elif name is not None:
            lines.append(line)
    return comps


def _reachable(comps: Dict[str, str], roots) -> Set[str]:
    """The computations ``roots`` call, directly or through others."""
    seen, todo = set(), list(roots)
    while todo:
        c = todo.pop()
        if c in seen:
            continue
        seen.add(c)
        todo += [r for r in re.findall(r"%([\w.\-]+)", comps[c])
                 if r in comps]
    return seen


def test_rnn_cell_loop_has_no_gather(one_chip, monkeypatch):
    """The paper RNN over LANES lanes with the compiled GEMM: the cell's
    while body (and every fusion it calls) holds the two cell GEMMs and
    no gather; the embedding takes before the loop may gather."""
    monkeypatch.setattr(kernel_backend, "on_tpu", lambda: True)
    mcfg = fenix_rnn()
    rng = np.random.default_rng(0)
    calib = rng.integers(1, 1500, (64, mcfg.seq_len, 2)).astype(np.int32)
    qp = quantize_traffic(traffic.init(mcfg, seed=0), mcfg,
                          jnp.asarray(calib))
    text = _compiled_text(
        lambda p: int8_apply(qp, mcfg, p, backend="pallas_tpu"),
        _spec(one_chip, (LANES, mcfg.seq_len, 2)))
    comps = _computations(text)
    bodies = re.findall(r"\bbody=%?([\w.\-]+)", text)
    assert len(bodies) == 1, bodies
    inside = [comps[c] for c in _reachable(comps, bodies)]
    assert sum(b.count("tpu_custom_call") for b in inside) == 2
    assert not any(re.search(r"\sgather\(", b) for b in inside)
