"""Benchmark harness: one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows per the repo convention; full
JSON artifacts land in benchmarks/results/.

  throughput   — data-plane pps at batch 4096 (segment vs seed dense path)
  pipes        — multi-pipeline pps sweep (num_pipes x batch, ISSUE 2)
  engines      — Model-Engine farm sweep at E in {1,2,4} (ISSUE 3; bar:
                 E=2 >= 1.7x served inferences/s over E=1 at saturation)
  oversub      — Figure 10 analogue at batch 8192 (F1 + pps vs offered
                 load past the Model-Engine service capacity)
  traces       — real-trace replay (ISSUE 4): pcap fixture -> streaming
                 ingest (bit-identity oracle) -> all four drivers
                 (host/device/pipes/farm) via run_trace(trace=...)
  soak         — sustained streaming replay (ISSUE 9): double-buffered
                 ingest vs sync staging vs the per-window host-sync
                 loop; steady-state pps, zero-host-sync assertion, RSS
  accuracy     — Table 2 (macro-F1, 9 schemes x 2 tasks)
  resource     — Tables 3+4 (SRAM/VMEM/MAC proxies)
  scalability  — Figure 10 (F1 vs concurrency/throughput)
  latency      — Figure 11 (FPGA cycle model, TPU roofline, CPU measured)
  fairness     — Appendix A (E[interval] == N/V)
  roofline     — §Roofline table from the dry-run artifacts (if present)

``python -m benchmarks.run [--fast] [--only section[,section...]]``
"""

from __future__ import annotations

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from benchmarks._io import write_json_atomic
from repro import compile_cache

RESULTS = os.path.join(os.path.dirname(__file__), "results")

SECTIONS = ("throughput", "gate", "pipes", "engines", "oversub", "traces",
            "soak", "accuracy", "resource", "scalability", "latency",
            "fairness", "roofline")


def _row(name: str, us: float, derived: str) -> None:
    print(f"{name},{us:.2f},{derived}")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true",
                    help="smaller accuracy/scalability settings")
    ap.add_argument("--only", default=None,
                    help="comma-separated subset of: " + ",".join(SECTIONS))
    args, _ = ap.parse_known_args()
    compile_cache.enable()
    os.makedirs(RESULTS, exist_ok=True)
    only = args.only.split(",") if args.only else None
    if only:
        unknown = sorted(set(only) - set(SECTIONS))
        if unknown:
            ap.error(f"unknown --only section(s): {', '.join(unknown)}; "
                     f"valid sections: {', '.join(SECTIONS)}")

    def want(name):
        return only is None or name in only

    print("name,us_per_call,derived")

    if want("throughput"):
        from benchmarks import bench_scalability
        n_b = 4 if args.fast else 12
        res = bench_scalability.throughput(n_batches=n_b)
        write_json_atomic(os.path.join(RESULTS, "throughput.json"), res)
        _row("fastpath_throughput", res["segment"]["us_per_batch"],
             f"pps={res['segment']['pps']:.0f};"
             f"speedup_vs_dense={res['speedup_vs_dense']:.1f}x")

    if want("gate"):
        from benchmarks import bench_gate
        iters, interp = (20, 1) if args.fast else (50, 3)
        res = bench_gate.sweep(iters=iters, interp_iters=interp)
        write_json_atomic(os.path.join(RESULTS, "gate.json"), res)
        for r in res["rows"]:
            _row(f"gate_b{r['batch_size']}_p{r['num_pipes']}",
                 r["fused_us"],
                 f"unfused_us={r['unfused_us']};"
                 f"speedup_fused={r['speedup_fused']:.2f}x;"
                 f"granted={r['granted']}")

    if want("pipes"):
        from benchmarks import bench_scalability
        sizes = (4096,) if args.fast else (4096, 8192)
        steps = 4 if args.fast else 8
        rows = bench_scalability.pipes_sweep(batch_sizes=sizes,
                                             n_steps=steps)
        write_json_atomic(os.path.join(RESULTS, "pipes.json"),
                          {"rows": rows})
        for r in rows:
            _row(f"pipes_p{r['num_pipes']}_b{r['batch_size']}",
                 r["wall_s"] * 1e6 / max(r["packets"] // r["batch_size"], 1),
                 f"pps={r['pps']:.0f};"
                 f"speedup_vs_1pipe={r['speedup_vs_1pipe']:.2f}x;"
                 f"sharded={r['sharded']}")

    if want("engines"):
        from benchmarks import bench_scalability
        steps = 192 if args.fast else 512
        rows = bench_scalability.engines_sweep(engines=(1, 2, 4),
                                               n_steps=steps)
        write_json_atomic(os.path.join(RESULTS, "engines.json"),
                          {"rows": rows})
        for r in rows:
            _row(f"engines_e{r['num_engines']}", r["wall_s"] * 1e6,
                 f"served_per_s={r['served_inf_per_s']:.0f};"
                 f"speedup_vs_1eng={r['speedup_vs_1eng']:.2f}x;"
                 f"sharded={r['sharded']}")

    if want("oversub"):
        from benchmarks import bench_scalability
        t0 = time.time()
        if args.fast:
            res = bench_scalability.oversub_sweep(
                oversubs=(0.5, 16.0), n_flows=250, pkts=20_000,
                train_steps=150, train_flows=250)
        else:
            res = bench_scalability.oversub_sweep()
        write_json_atomic(os.path.join(RESULTS, "oversub.json"), res)
        rows = res["rows"]
        _row("oversub", (time.time() - t0) * 1e6,
             f"f1_lo={rows[0]['macro_f1']:.3f};"
             f"f1_hi={rows[-1]['macro_f1']:.3f};"
             f"rel_drop={res['rel_f1_drop']:.3f};"
             f"pps={rows[-1]['pps_wall']:.0f}")

    if want("traces"):
        from benchmarks import bench_traces
        t0 = time.time()
        res = bench_traces.main(
            out_path=os.path.join(RESULTS, "traces.json"),
            fast=args.fast)
        for r in res["rows"]:
            _row(f"traces_{r['driver']}", r["wall_s"] * 1e6,
                 f"pps={r['pps_wall']:.0f};"
                 f"served_per_s={r['served_inf_per_s']:.0f};"
                 f"classified_frac={r['classified_frac']:.3f}")
        _row("traces_total", (time.time() - t0) * 1e6,
             f"packets={res['rows'][0]['packets']};"
             f"source={res['source']}")

    if want("soak"):
        from benchmarks import bench_soak
        t0 = time.time()
        res = bench_soak.main(
            out_path=os.path.join(RESULTS, "soak.json"), fast=args.fast)
        _row("soak", (time.time() - t0) * 1e6,
             f"steady_pps={res['overlap']['steady_pps']:.0f};"
             f"overlap_speedup={res['overlap_speedup']:.2f}x;"
             f"zerosync_speedup={res['zerosync_speedup']:.2f}x;"
             f"host_syncs={res['overlap']['host_syncs']};"
             f"rss_growth_mb={res['overlap']['rss_growth_mb']}")

    if want("accuracy"):
        from benchmarks import bench_accuracy
        t0 = time.time()
        n, s = (250, 150) if args.fast else (700, 350)
        res = bench_accuracy.main(n_flows=n, steps=s,
                                  out_path=os.path.join(RESULTS,
                                                        "accuracy.json"))
        for task in ("iscx", "ustc"):
            best = res[task]["fenix-rnn-flow"]["macro_f1"]
            pkt = res[task]["fenix-cnn-pkt"]["macro_f1"]
            _row(f"accuracy_{task}", (time.time() - t0) * 1e6,
                 f"fenix_flow_f1={best:.3f};fenix_pkt_f1={pkt:.3f}")

    if want("resource"):
        from benchmarks import bench_resource
        t0 = time.time()
        res = bench_resource.main(os.path.join(RESULTS, "resource.json"))
        _row("resource", (time.time() - t0) * 1e6,
             f"sram_frac={res['data_engine']['sram_fraction_tofino2']:.4f}")

    if want("scalability"):
        from benchmarks import bench_scalability
        t0 = time.time()
        scales = ((1000, 0.5), (1000, 16.0)) if args.fast else \
            ((1000, 0.5), (1000, 4.0), (1000, 16.0), (1000, 64.0),
             (4000, 16.0), (8000, 16.0))
        rows = bench_scalability.main(
            os.path.join(RESULTS, "scalability.json"), scales=scales,
            include_throughput=False)
        drop = (rows[0]["macro_f1"] - rows[-1]["macro_f1"]) \
            / max(rows[0]["macro_f1"], 1e-9)
        _row("scalability", (time.time() - t0) * 1e6,
             f"f1_small={rows[0]['macro_f1']:.3f};"
             f"f1_large={rows[-1]['macro_f1']:.3f};rel_drop={drop:.3f}")

    if want("latency"):
        from benchmarks import bench_latency
        t0 = time.time()
        res = bench_latency.main(os.path.join(RESULTS, "latency.json"))
        us = res["fenix-cnn"]["fpga_cycle_model_us"]
        _row("latency_fenix_cnn", us,
             f"speedup_vs_ctrl={res['fenix-cnn']['speedup_vs_control_plane']:.0f}x")
        _row("latency_fenix_rnn", res["fenix-rnn"]["fpga_cycle_model_us"],
             f"speedup_vs_ctrl="
             f"{res['fenix-rnn']['speedup_vs_control_plane']:.0f}x")

    if want("fairness"):
        from benchmarks import bench_fairness
        t0 = time.time()
        rows = bench_fairness.main(os.path.join(RESULTS, "fairness.json"))
        _row("fairness", (time.time() - t0) * 1e6,
             f"max_rel_err={max(r['rel_err'] for r in rows):.3f}")

    if want("roofline"):
        from repro.launch import roofline
        t0 = time.time()
        try:
            cells = roofline.load_cells("baseline")
            ok = [c for c in cells if c.get("status") == "ok"]
            if ok:
                worst = min(ok, key=lambda c: c.get("useful_ratio", 1.0))
                _row("roofline", (time.time() - t0) * 1e6,
                     f"cells={len(ok)};worst_ratio="
                     f"{worst['useful_ratio']:.2f}@"
                     f"{worst['arch']}x{worst['shape']}")
                write_json_atomic(os.path.join(RESULTS, "roofline.json"),
                                  cells, default=str)
        except Exception as e:  # dry-run artifacts absent
            _row("roofline", 0.0, f"skipped({e})")


if __name__ == "__main__":
    main()
