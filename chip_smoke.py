#!/usr/bin/env python3
"""Chip smoke: the FENIX replay path on a TPU, with its compiled kernels.

  python chip_smoke.py                one chip: kernel parity + main path
  python chip_smoke.py --four-chips   the 2-pipe x 2-engine farm on a 2x2
                                      mesh of four chips vs one chip's vmap

One chip, three phases:

1. device — ``jax.devices()[0]`` must be a TPU; anything else exits 1
   before any work, with no result line.
2. kernel parity — the compiled fused admission gate with supplied draws
   equals ``fused_admission_ref`` bit for bit at batch 4096 (and across
   a 3-tile grid); the on-core PRNG variant grants within 0.05 of the
   LUT's expected fraction; the compiled int8 GEMM equals
   ``int8_matmul_ref`` at every layer of the paper CNN over 1024 lanes;
   the paper RNN's logits over 1024 lanes equal a numpy forward that
   indexes its tanh table.
3. main path — ``FenixSystem(FenixConfig(model="int8_cnn",
   driver="device", batch_size=4096)).run_trace(TraceSpec(capture))``
   on a capture synthesized from a seed, with the platform's backends:
   both kernels in the lowered scan, zero host syncs, served inferences,
   verdict coverage; the same replay with the gate on supplied draws
   (``gate_backend="ref"``) equals an all-``ref`` replay verdict for
   verdict.  At the default token rate nearly every packet is granted,
   so the compiled gate is then checked where it binds: at a token rate
   of ``KNEE_HZ`` the ``ref`` replay grants 30-70% of the packets, and
   the on-core-PRNG replay's grant fraction and bucket-limited drops
   (grants a lifted bucket cap would add) are within 0.05 of it.
   Compile and replay seconds are printed as information.

``--four-chips`` runs only the farm comparison, at the same switch-wide
token rate: verdicts and stats of the 2x2 mesh equal those of nested vmap
on one chip (supplied draws), and the mesh on the platform's backends
grants within 0.05 of them.

Any failed check raises; the last line of stdout is one JSON object,
``{"ok": true, "device": {"platform", "kind", "count"}}``.  One process
drives the chip(s); it starts no other.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.join(HERE, "src")

SEED = 20250714
BATCH = 4096
N_FLOWS = 2000            # flows in the synthesized capture, all
FLOW_START_S = 4.0        # starting within its first seconds
N_PACKETS = 200_000       # packets replayed (~49 scan steps, 6 windows)
GRANT_TOL = 0.05          # tests/test_fused_gate.py's rate tolerance
# switch-wide token rate (1/us = fpga_hz / 1e6) at which this capture's
# ~81k packets/s meet a binding gate: both the LUT and the bucket turn
# packets away, and the ref gate grants ~59%
KNEE_HZ = 1e5
LIFTED_QUEUE = 1 << 14    # queue_len whose bucket cap never binds here


def log(msg: str) -> None:
    print(msg, flush=True)


def require_tpu(count: int):
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        sys.exit(f"chip_smoke: JAX runs on {devs[0].platform!r}, not a TPU")
    if len(devs) < count:
        sys.exit(f"chip_smoke: needs {count} TPU chips, found {len(devs)}")
    return devs


# -- phase 2: kernel parity ---------------------------------------------------

def _gate_case(seed: int, n: int, random_lut: bool, cost: int):
    import numpy as np
    import jax.numpy as jnp
    from repro.core.probability import LUTConfig, build_lut

    lcfg = LUTConfig()
    rng = np.random.default_rng(seed)
    if random_lut:
        lut = rng.integers(0, 1 << lcfg.prob_bits,
                           (lcfg.t_bins, lcfg.c_bins))
    else:
        lut = build_lut(n=float(rng.integers(10, 5000)),
                        q=float(rng.uniform(0.05, 4.0)),
                        v=float(rng.uniform(0.01, 0.2)), cfg=lcfg)
    t_last = int(rng.integers(0, 1 << 20))
    cols = {
        "t_i": rng.integers(0, 1 << 17, n),
        "c_i": rng.integers(0, 128, n),
        "ts": np.sort(rng.integers(t_last, t_last + 20 * n, n)),
        "lut": lut,
        "bucket": rng.integers(0, 64 * cost),
        "t_last": t_last,
    }
    args = {k: jnp.asarray(v, jnp.int32) for k, v in cols.items()}
    rand16 = jnp.asarray(rng.integers(0, 1 << lcfg.prob_bits, n), jnp.int32)
    kw = dict(cost_us=cost, bucket_cap_us=64 * cost, t_shift=lcfg.t_shift,
              c_shift=lcfg.c_shift, prob_bits=lcfg.prob_bits)
    return args, rand16, kw


def gate_parity() -> None:
    import numpy as np
    import jax.numpy as jnp
    from repro.kernels.rate_gate.ops import fused_admission

    for seed, n, random_lut, cost in ((1, BATCH, False, 1),
                                      (2, BATCH, True, 4),
                                      (3, BATCH, True, 32),
                                      (4, 3 * BATCH + 77, False, 2)):
        args, rand16, kw = _gate_case(seed, n, random_lut, cost)
        want = fused_admission(**args, rand16=rand16, backend="ref", **kw)
        got = fused_admission(**args, rand16=rand16, backend="pallas_tpu",
                              **kw)
        g_want, g_got = np.asarray(want[0]), np.asarray(got[0])
        assert (g_got == g_want).all(), \
            f"gate grants differ at n={n}: {(g_got != g_want).sum()} lanes"
        assert int(got[1]) == int(want[1]), (int(got[1]), int(want[1]))
        log(f"gate parity n={n} cost={cost}: {int(g_got.sum())} granted, "
            f"bucket {int(got[1])} — bit-identical to ref")
    # on-core PRNG: a cap that never binds, so grants are the selections
    args, _, kw = _gate_case(5, BATCH, True, 1)
    kw["bucket_cap_us"] = 1 << 30
    args["bucket"] = jnp.asarray(1 << 30, jnp.int32)
    lut = np.asarray(args["lut"])
    ti = np.clip(np.asarray(args["t_i"]) >> kw["t_shift"], 0,
                 lut.shape[0] - 1)
    ci = np.clip(np.asarray(args["c_i"]) >> kw["c_shift"], 0,
                 lut.shape[1] - 1)
    expect = lut[ti, ci].mean() / (1 << kw["prob_bits"])
    masks = []
    for seed in (11, 12):
        g, b = fused_admission(**args, seed=seed, backend="pallas_tpu", **kw)
        frac = float(np.asarray(g).mean())
        assert abs(frac - expect) < GRANT_TOL, (frac, expect)
        assert 0 <= int(b) <= kw["bucket_cap_us"]
        masks.append(np.asarray(g))
        log(f"gate on-core PRNG seed={seed}: grant fraction {frac:.4f} "
            f"vs LUT expectation {expect:.4f}")
    assert (masks[0] != masks[1]).any(), "PRNG seeds drew identical bits"


def gemm_parity() -> None:
    import numpy as np
    import jax.numpy as jnp
    from repro.configs.fenix_models import fenix_cnn, fenix_rnn
    from repro.kernels.int8_matmul import ops
    from repro.kernels.int8_matmul.ref import int8_matmul_ref
    from repro.models import traffic
    from repro.quant.quantize import int8_apply, quantize_traffic
    from repro.quant.ref import int8_rnn_ref

    mcfg = fenix_cnn()
    rng = np.random.default_rng(SEED)
    payload = np.stack([rng.integers(40, 1500, (1024, mcfg.seq_len)),
                        rng.integers(0, 100_000, (1024, mcfg.seq_len))],
                       axis=-1).astype(np.int32)
    qp = quantize_traffic(traffic.init(mcfg, seed=SEED), mcfg,
                          jnp.asarray(payload[:256]))
    calls = []
    real = ops.int8_matmul

    def record(a, b, bias=None, shift=None, backend=None):
        calls.append((a, b, bias, shift))
        return real(a, b, bias=bias, shift=shift, backend="ref")

    ops.int8_matmul = record
    try:
        logits_ref = int8_apply(qp, mcfg, jnp.asarray(payload),
                                backend="ref")
    finally:
        ops.int8_matmul = real
    assert len(calls) == 6, len(calls)
    for a, b, bias, shift in calls:
        rand_a = jnp.asarray(rng.integers(-127, 128, a.shape), jnp.int8)
        for x in (a, rand_a):
            got = ops.int8_matmul(x, b, bias=bias, shift=shift,
                                  backend="pallas_tpu")
            want = int8_matmul_ref(x, b, bias=bias, shift=shift)
            assert got.dtype == want.dtype
            assert bool(jnp.all(got == want)), (a.shape, b.shape)
        log(f"gemm parity [{a.shape[0]}x{a.shape[1]}]x[{b.shape[0]}x"
            f"{b.shape[1]}] shift={shift}: bit-identical to ref")
    logits = int8_apply(qp, mcfg, jnp.asarray(payload),
                        backend="pallas_tpu")
    assert bool(jnp.all(logits == logits_ref))
    log("int8 paper CNN logits over 1024 lanes: bit-identical to ref")
    rcfg = fenix_rnn()
    rqp = quantize_traffic(traffic.init(rcfg, seed=SEED), rcfg,
                           jnp.asarray(payload[:256]))
    got = np.asarray(int8_apply(rqp, rcfg, jnp.asarray(payload),
                                backend="pallas_tpu"))
    want, _ = int8_rnn_ref(rqp, rcfg, payload)
    assert (got == want).all(), int((got != want).sum())
    log("int8 paper RNN logits over 1024 lanes: bit-identical to the "
        "numpy forward that indexes the tanh table")


# -- phase 3: the main path ---------------------------------------------------

def synth_capture(workdir: str) -> str:
    from repro.data.synthetic_traffic import make_flows
    from repro.data.trace_ingest import synthesize_pcap

    flows = make_flows("iscx", N_FLOWS, seed=SEED, duration_s=FLOW_START_S,
                       min_per_class=15)
    path = os.path.join(workdir, "capture.pcap")
    src = synthesize_pcap(flows, path, limit=N_PACKETS)
    n_flows = len(set(src["flow_idx"].tolist()))
    log(f"capture: {len(src['ts_us'])} packets over {n_flows} flows")
    assert n_flows >= 1000, n_flows
    return path


def lowered_kernels(sys_) -> dict:
    """tpu_custom_call counts in the device driver's lowered scan."""
    import jax
    import jax.numpy as jnp
    from repro.core.fenix import PKT_KEYS
    from repro.kernels.int8_matmul.kernel import GEMM_NAME
    from repro.kernels.rate_gate.kernel import FUSED_GATE_NAME

    steps = sys_.cfg.control_plane_every * sys_._STAGE_WINDOWS
    dtypes = {k: jnp.uint32 for k in PKT_KEYS}
    dtypes.update(ts_us=jnp.int32, pkt_len=jnp.int32)
    block = {k: jax.ShapeDtypeStruct((steps, sys_.cfg.batch_size), dt)
             for k, dt in dtypes.items()}
    block["_cp"] = jax.ShapeDtypeStruct((steps,), jnp.bool_)
    sys_._ensure_jits()
    text = sys_._scan_jit.lower((sys_.state, sys_.queues, sys_._dl),
                                block).as_text()
    return {"tpu_custom_call": text.count("@tpu_custom_call"),
            "gate": text.count(FUSED_GATE_NAME),
            "gemm": text.count(GEMM_NAME)}


def replay(spec, clog, engine_kw=None, **cfg_kw):
    """One FenixSystem replay; returns (system, verdicts, seconds)."""
    from repro.core.data_engine.state import EngineConfig
    from repro.core.fenix import FenixConfig, FenixSystem

    sys_ = FenixSystem(FenixConfig(model="int8_cnn", driver="device",
                                   batch_size=BATCH,
                                   engine=EngineConfig(**(engine_kw or {})),
                                   **cfg_kw))
    c0, t0 = clog.seconds, time.perf_counter()
    verdict = sys_.run_trace(spec)["verdict"]
    wall = time.perf_counter() - t0
    label = " ".join(str(kw) for kw in (engine_kw, cfg_kw) if kw)
    log(f"replay {label or 'platform backends'}: "
        f"{wall:.2f} s wall, {clog.seconds - c0:.2f} s of it backend "
        f"compile; granted {sys_.stats['granted']}, inferences "
        f"{sys_.stats['inferences']}")
    return sys_, verdict, wall


def gate_at_knee(spec, span_us: int, clog) -> None:
    """The compiled gate inside the scan where it binds: on-core PRNG vs
    supplied draws at ``KNEE_HZ``, grant fraction and bucket-limited
    drops (the grants that lifting the bucket cap adds)."""
    packets, granted = 0, {}
    for draws, cfg_kw in (("prng", {}), ("ref", {"gate_backend": "ref"})):
        for cap, engine_kw in (("cap", {}),
                               ("lifted", {"queue_len": LIFTED_QUEUE})):
            sys_, _, _ = replay(spec, clog, {"fpga_hz": KNEE_HZ, **engine_kw},
                                **cfg_kw)
            packets = sys_.stats["packets"]
            granted[draws, cap] = sys_.stats["granted"]
            ecfg = sys_.gcfg
            # token conservation: spend never outruns refill plus one cap
            assert granted[draws, cap] * ecfg.cost_us <= \
                span_us + ecfg.bucket_cap_us + ecfg.cost_us, (draws, cap)
    frac = {d: granted[d, "cap"] / packets for d in ("prng", "ref")}
    drops = {d: (granted[d, "lifted"] - granted[d, "cap"]) / packets
             for d in ("prng", "ref")}
    log(f"gate at {KNEE_HZ:.0f} tokens/s: grant fraction on-core PRNG "
        f"{frac['prng']:.4f} vs ref {frac['ref']:.4f}; bucket-limited "
        f"drops {drops['prng']:.4f} vs {drops['ref']:.4f} of packets "
        f"(tolerance {GRANT_TOL})")
    assert 0.3 <= frac["ref"] <= 0.7, frac
    assert drops["ref"] >= GRANT_TOL, drops      # the bucket does bind
    assert abs(frac["prng"] - frac["ref"]) < GRANT_TOL, frac
    assert abs(drops["prng"] - drops["ref"]) < GRANT_TOL, drops


def main_path(clog) -> None:
    from repro.data.trace_ingest import TraceSpec

    with tempfile.TemporaryDirectory() as workdir:
        spec = TraceSpec(synth_capture(workdir))
        ts = spec.load()["ts_us"]
        span_us = int(ts[-1]) - int(ts[0])
        t0 = time.perf_counter()
        sys_, verdict, first = replay(spec, clog)
        log(f"first replay incl. int8_cnn training: "
            f"{time.perf_counter() - t0:.2f} s")
        sys_.reset()
        t0 = time.perf_counter()
        verdict2 = sys_.run_trace(spec)["verdict"]
        again = time.perf_counter() - t0
        stats = dict(sys_.stats)
        # same seeds after reset: the compiled replay is deterministic
        assert (verdict == verdict2).all(), int((verdict != verdict2).sum())
        log(f"replay seconds (compiled, information only): {again:.3f}; "
            f"first call incl. compile: {first:.3f}")
        kernels = lowered_kernels(sys_)
        log(f"lowered scan kernels: {kernels}")
        assert kernels["gate"] >= 1 and kernels["gemm"] >= 1, kernels
        assert sys_.host_syncs == 0, sys_.host_syncs
        assert stats["inferences"] > 0, stats
        coverage = float((verdict2 >= 0).mean())
        assert coverage > 0, coverage
        log(f"main path: packets={stats['packets']} "
            f"granted={stats['granted']} inferences={stats['inferences']} "
            f"coverage={coverage:.4f} host_syncs={sys_.host_syncs}")
        gemm_only, v_gemm, _ = replay(spec, clog, gate_backend="ref")
        ref, v_ref, _ = replay(spec, clog, gate_backend="ref",
                               matmul_backend="ref")
        assert gemm_only.stats == ref.stats, (gemm_only.stats, ref.stats)
        assert (v_gemm == v_ref).all(), int((v_gemm != v_ref).sum())
        log(f"compiled GEMM replay == ref replay: {len(v_ref)} verdicts, "
            f"stats identical")
        log(f"default rate: grant fraction "
            f"{stats['granted'] / stats['packets']:.4f} (platform) vs "
            f"{ref.stats['granted'] / ref.stats['packets']:.4f} (ref)")
        gate_at_knee(spec, span_us, clog)


# -- --four-chips: the farm on a 2x2 mesh ------------------------------------

def four_chips(clog) -> None:
    import jax
    from repro.core.data_engine.state import EngineConfig
    from repro.core.fenix import FenixConfig, FenixSystem
    from repro.data.trace_ingest import TraceSpec

    with tempfile.TemporaryDirectory() as workdir:
        stream = TraceSpec(synth_capture(workdir)).load()
    # the same switch-wide token rate as the one-chip knee: admission is
    # pooled over the 2 engines, so each engine runs at half of it
    kw = dict(num_pipes=2, num_engines=2, driver="farm", batch_size=BATCH,
              model="int8_cnn", engine=EngineConfig(fpga_hz=KNEE_HZ / 2))
    systems = {}
    for name, extra, devices in (
            ("mesh", {"gate_backend": "ref"}, None),
            ("vmap", {"gate_backend": "ref"}, jax.devices()[:1]),
            ("mesh_platform", {}, None)):
        sys_ = FenixSystem(FenixConfig(**kw, **extra), devices=devices)
        c0, t0 = clog.seconds, time.perf_counter()
        verdict = sys_.run_trace(stream)["verdict"]
        log(f"farm {name} {sys_.placement}: "
            f"{time.perf_counter() - t0:.2f} s wall, "
            f"{clog.seconds - c0:.2f} s backend compile; {sys_.stats}")
        systems[name] = (sys_, verdict)
    mesh, v_mesh = systems["mesh"]
    vmap, v_vmap = systems["vmap"]
    devices = {d.id for d in mesh._mesh.devices.flat}
    assert mesh.placement == {"mode": "mesh", "devices": 4}, mesh.placement
    assert len(devices) == 4 and mesh._mesh.devices.shape == (2, 2)
    assert vmap.placement == {"mode": "vmap", "devices": 1}
    assert mesh.stats == vmap.stats, (mesh.stats, vmap.stats)
    assert (v_mesh == v_vmap).all(), int((v_mesh != v_vmap).sum())
    assert mesh.stats["inferences"] > 0
    log(f"2x2 mesh over devices {sorted(devices)} == one-chip vmap: "
        f"{len(v_mesh)} verdicts, stats identical")
    plat = systems["mesh_platform"][0].stats
    frac = plat["granted"] / plat["packets"]
    frac_ref = mesh.stats["granted"] / mesh.stats["packets"]
    log(f"mesh on the platform's backends: grant fraction {frac:.4f} vs "
        f"supplied draws {frac_ref:.4f}")
    assert 0.3 <= frac_ref <= 0.7, frac_ref
    assert abs(frac - frac_ref) < GRANT_TOL, (frac, frac_ref)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 2x2 farm mesh vs one-chip vmap phase")
    args = ap.parse_args()
    if not os.path.isdir(os.path.join(SRC, "repro")):
        sys.exit(f"chip_smoke: no src/repro next to {__file__}")
    sys.path.insert(0, SRC)
    devs = require_tpu(4 if args.four_chips else 1)
    from repro import compile_cache, tracing

    cache_dir = compile_cache.enable()
    clog = tracing.compile_log()
    log(f"device: {devs[0].platform} {devs[0].device_kind} x{len(devs)}; "
        f"compile cache {cache_dir}")
    if args.four_chips:
        four_chips(clog)
    else:
        gate_parity()
        gemm_parity()
        main_path(clog)
    log(f"backend compile total {clog.seconds:.2f} s in {clog.compiles} "
        f"compiles; persistent cache {clog.hits} hits, {clog.misses} "
        f"misses")
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))


if __name__ == "__main__":
    main()
