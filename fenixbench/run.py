"""Run one benchmark cell on the accelerator and print its result line.

    python3 -m fenixbench.run --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

Everything a cell is made of is found by name from ``BENCHMARK.json``:
the configuration ``fenixbench/configs/<config>.json``, its model kind
``fenixbench/models/<kind>.py`` (weights, reference, work counts and the
``verdict_gap`` limit), the traffic mix ``fenixbench/traffic/
<traffic>.json`` (whose ``entry`` names how packets enter the system;
``columns`` is the one entry so far) and one reader per metric,
``fenixbench/metrics/<metric>.py``.

A run builds the traffic pool and the INT8 model from the seed, builds
``FenixSystem`` on the device driver with that model, warms up every
shape the window uses, then calls ``run_trace`` on consecutive segments
of one endless stream, never reset, until ``--seconds`` have passed.

With ``--trace 1`` the window is a short traced stretch, reduced to the
per-layer metrics.  Once the window has closed, the system is freed and
the run is compared with the plain references: the verdicts of a sample
of table slots (``reference.judge``), the grant share
(``admission.replay``), and the conservation of granted lanes and
answers through the Vector I/O FIFO and the delay line.  The last stdout
line is the result; the compared numbers and their limits end stderr and
the result line.
"""

from __future__ import annotations

import time

_T0 = time.monotonic()

import argparse  # noqa: E402
import copy  # noqa: E402
import gc  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from typing import Dict, Optional  # noqa: E402

import numpy as np  # noqa: E402

from fenixbench import (admission, models, reference,  # noqa: E402
                        trace_reduce, traffic_gen, work)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
# table slots whose verdicts the reference judges, drawn from the seed
CHECK_SLOTS = 512
# calibration windows for the INT8 scales
CALIB_WINDOWS = 512
WARMUP_CALLS = 2
# length of the traced stretch of a --trace 1 run
TRACE_SECONDS = 2.0
# every compared number and its limit (see PERF.md for the readings
# each limit was set from); a run is correct when each is <= its limit.
# ``verdict_gap``'s limit is the model kind's ``VERDICT_GAP_LIMIT``.
LIMITS = {"due_missed_pct": 2.0, "admission_gap_pct": 2.0,
          "lanes_lost": 0, "answers_lost": 0}


class CompileLog:
    """Backend compiles (count and seconds) and persistent-cache
    hits/misses, read from JAX's monitoring events."""

    def __init__(self):
        import jax

        self.compiles = 0
        self.seconds = 0.0
        self.hits = 0
        self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _dur(self, name, secs, **_):
        if name == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
            self.seconds += secs

    def _event(self, name, **_):
        if name == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif name == "/jax/compilation_cache/cache_misses":
            self.misses += 1


def _merge(base: Dict, over: Optional[Dict]) -> Dict:
    out = copy.deepcopy(base)
    for k, v in (over or {}).items():
        if isinstance(v, dict) and isinstance(out.get(k), dict):
            out[k] = _merge(out[k], v)
        else:
            out[k] = v
    return out


def load_cell(name: str, root: str = ROOT):
    """(benchmark, cell, config, mix) for workload ``name``."""
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    cell = cells[name]
    with open(os.path.join(BENCH_DIR, "configs",
                           cell["config"] + ".json")) as f:
        cfg = json.load(f)
    mix = traffic_gen.load_mix(cell["traffic"])
    return bench, cell, cfg, mix


def cell_metrics(bench: Dict, cell: str, kind: str):
    """Names of the ``end_to_end`` or ``per_layer`` metrics a cell
    reports."""
    return [m["name"] for m in bench[kind]
            if cell in m.get("workloads", [cell])]


def _calibration(pool: Dict, seq_len: int, n: int,
                 rng: np.random.Generator) -> np.ndarray:
    """Payload windows (a flow's last ``seq_len`` packets, front-padded)
    for ``n`` packets of the pool."""
    order = np.argsort(pool["flow"], kind="stable")
    flow = pool["flow"][order]
    ts = pool["ts_us"][order].astype(np.int64)
    first = np.r_[True, flow[1:] != flow[:-1]]
    ipd = np.where(first, 0, np.diff(ts, prepend=ts[0]))
    feats = np.stack([pool["pkt_len"][order], ipd], axis=-1)
    at = rng.choice(len(order), size=n, replace=False)
    back = at[:, None] - np.arange(seq_len - 1, -1, -1)[None, :]
    ok = back >= 0
    back = np.maximum(back, 0)
    ok &= flow[back] == flow[at][:, None]
    return np.where(ok[..., None], feats[back], 0).astype(np.int32)


def engine_model(mcfg: Dict, qp: Dict):
    """The program's ``EngineModel`` serving ``qp``: every key of the
    configuration's ``model`` is a field of the program's
    ``TrafficModelConfig`` (lists as tuples), named for its kind."""
    import dataclasses

    import jax
    import jax.numpy as jnp
    from repro.configs.fenix_models import TrafficModelConfig
    from repro.core.model_engine.inference import EngineModel

    fields = {f.name for f in dataclasses.fields(TrafficModelConfig)}
    unknown = sorted(set(mcfg) - fields)
    if unknown:
        raise ValueError(f"the program's TrafficModelConfig has no "
                         f"field {', '.join(unknown)}")
    cfg = TrafficModelConfig(name=mcfg["kind"], **{
        k: tuple(v) if isinstance(v, list) else v for k, v in mcfg.items()})
    return EngineModel(cfg=cfg, qparams=jax.tree.map(jnp.asarray, qp))


def _build_system(cfg: Dict, qp: Dict):
    from repro.core.data_engine.state import EngineConfig
    from repro.core.fenix import FenixConfig, FenixSystem
    from repro.core.model_engine.vector_io import IOConfig

    sw = cfg["switch"]
    model = engine_model(cfg["model"], qp)
    fcfg = FenixConfig(
        engine=EngineConfig(n_slots_log2=sw["n_slots_log2"],
                            ring_depth=sw["ring_depth"],
                            fpga_hz=sw["fpga_hz"],
                            link_bw_bytes=sw["link_bw_bytes"],
                            feat_bytes=sw["feat_bytes"],
                            queue_len=sw["bucket_len"]),
        io=IOConfig(queue_len=sw["queue_len"],
                    feat_len=sw["ring_depth"] + 1),
        batch_size=sw["batch_size"], loop_latency_us=sw["loop_latency_us"],
        control_plane_every=sw["control_plane_every"], driver="device")
    return FenixSystem(fcfg, model=model)


def _batch_bounds(ts: np.ndarray, b: int):
    """First and last timestamp of every batch of ``b`` packets (the last
    batch may be partial)."""
    n = len(ts)
    starts = np.arange(0, n, b)
    ends = np.minimum(starts + b, n) - 1
    return ts[starts].astype(np.int64), ts[ends].astype(np.int64)


class Replay:
    """The cell's entry: set-up, one timed call (one segment of the
    endless stream), and what the reference needs afterwards."""

    def __init__(self, cfg: Dict, mix: Dict, pool: Dict, seed: int,
                 system):
        import jax

        if mix["entry"] != "columns":
            raise ValueError(f"unknown entry {mix['entry']!r}")
        self.jax = jax
        self.cfg, self.pool, self.system = cfg, pool, system
        sw = cfg["switch"]
        self.B = sw["batch_size"]
        self.S = mix["segment_pkts"]
        if self.S % self.B:
            raise ValueError("segment_pkts must be a whole number of "
                             "batches")
        rng = np.random.default_rng([int(seed), 3])
        n_slots = 1 << sw["n_slots_log2"]
        self.hash = reference.hash_five_tuple(pool)
        self.slot = (self.hash & np.uint32(n_slots - 1)).astype(np.int64)
        sampled = rng.choice(n_slots, size=min(CHECK_SLOTS, n_slots),
                             replace=False)
        self.mask = np.isin(self.slot, sampled)
        self.per = traffic_gen.n_segments(pool, self.S)
        self.sel = [np.flatnonzero(self.mask[j * self.S:(j + 1) * self.S])
                    for j in range(self.per)]
        self.rec: Dict[int, np.ndarray] = {}
        self.calls = 0

    def call(self) -> None:
        TA = self.jax.profiler.TraceAnnotation
        k = self.calls
        with TA("bench.stage"):
            seg = traffic_gen.cycle_segment(self.pool, k, self.S)
        with TA("bench.run_trace"):
            out = self.system.run_trace(seg)
        with TA("bench.fetch"):
            self.rec[k] = out["verdict"][self.sel[k % self.per]]
        self.calls += 1

    def counters(self) -> Dict[str, int]:
        """The program's counters, and the pointers of its Vector I/O FIFO
        and delay line, so far."""
        s = self.system
        st = s.stats
        return {"packets": st["packets"], "granted": st["granted"],
                "served": st["inferences"], "dropped_q": st["dropped_q"],
                "dropped_inflight": st["dropped_inflight"],
                "steps": int(sum(st["engine_q_depth_hist"][0])),
                "fifo_head": int(s.queues["head"]),
                "fifo_tail": int(s.queues["tail"]),
                "answers_pushed": int(s._dl["tail"])}

    def batches(self, calls):
        """The stream's batches over ``calls``, for ``admission.replay``."""
        B, cpe = self.B, self.cfg["switch"]["control_plane_every"]
        for k in calls:
            j = k % self.per
            seg = traffic_gen.cycle_segment(self.pool, k, self.S)
            sl = slice(j * self.S, (j + 1) * self.S)
            h, slot, watch = self.hash[sl], self.slot[sl], self.mask[sl]
            for i in range(self.S // B):
                b = slice(i * B, (i + 1) * B)
                yield {"ts_us": seg["ts_us"][b], "hash": h[b],
                       "slot": slot[b], "watch": watch[b],
                       "cp": (i + 1) % cpe == 0}

    def slot_runs(self, answered: np.ndarray):
        """The sampled slots' packets in stream order, for
        ``reference.judge``, with ``answered``: the admission reference's
        answered flag of each; and the drain flag of every batch."""
        pool, B, K = self.pool, self.B, self.calls
        pos = traffic_gen.stream_positions(pool, self.S, K, self.mask)
        n_pool = self.per * self.S
        pi = pos % n_pool
        ts = pool["ts_us"][pi].astype(np.int64) \
            + (pos // n_pool) * int(pool["period_us"])
        verdict = np.concatenate([self.rec[k] for k in range(K)])
        f, last = _batch_bounds(pool["ts_us"][:n_pool], B)
        passes = -(-K * self.S // n_pool)
        off = np.repeat(np.arange(passes) * int(pool["period_us"]),
                        n_pool // B)
        first = np.tile(f, passes) + off
        lastb = np.tile(last, passes) + off
        nxt = np.r_[lastb[1:], np.iinfo(np.int64).max // 2]
        drains = reference.batch_drains(first, lastb, nxt,
                                        self.cfg["switch"])
        sl = self.slot[pi]
        order = np.argsort(sl, kind="stable")
        cuts = np.flatnonzero(np.diff(sl[order])) + 1
        runs = [{"batch": pos[g] // B, "ts": ts[g],
                 "pkt_len": pool["pkt_len"][pi[g]], "hash": self.hash[pi[g]],
                 "verdict": verdict[g], "answered": answered[g]}
                for g in np.split(order, cuts) if len(g)]
        return runs, drains


def _stalls(lat) -> Dict[str, float]:
    """The window's call times: median, mean and longest, and the seconds
    that calls spent beyond twice the median (host stalls)."""
    lat = np.asarray(lat)
    med = float(np.median(lat))
    return {"call_ms_median": med * 1e3, "call_ms_mean": lat.mean() * 1e3,
            "call_ms_max": lat.max() * 1e3,
            "stall_s": float(np.maximum(lat - 2 * med, 0).sum())}


def _trace_options():
    import jax

    po = jax.profiler.ProfileOptions()
    po.python_tracer_level = 0
    return po


class NoDevice(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


def measure(workload: str, seed: int, seconds: float, trace: bool, *,
            root: str = ROOT, require_tpu: bool = True,
            control: bool = False,
            overrides: Optional[Dict] = None) -> Dict:
    """Run one cell: set-up, the window, the reference comparison.

    Returns ``{"result": <result line object>, "check": {name: value},
    "info": {...}}``.  ``control`` also reads the 4-bit control's gap
    over the judged windows (``info["control_gap"]``), which the
    benchmark's own runs do not.  ``overrides`` (tests only) merges into
    the configuration (``"config"``) and the mix (``"mix"``), and may
    give ``"peaks"`` and the trace's ``"device_lines"`` selector for a
    platform without them."""
    overrides = overrides or {}
    import jax

    devices = jax.devices()
    bench, cell, cfg, mix = load_cell(workload, root)
    cfg = _merge(cfg, overrides.get("config"))
    mix = _merge(mix, overrides.get("mix"))
    mcfg, sw = cfg["model"], cfg["switch"]
    kind_mod = models.get(mcfg["kind"])
    limits = {"verdict_gap": kind_mod.VERDICT_GAP_LIMIT, **LIMITS}
    chips = int(cell["chips"])
    if require_tpu and devices[0].platform != "tpu":
        raise NoDevice(f"needs a TPU, JAX found {devices[0].platform!r}")
    if len(devices) < chips:
        raise NoDevice(f"{workload} needs {chips} chips, JAX found "
                       f"{len(devices)}")
    kind = devices[0].device_kind
    pk = overrides.get("peaks") or work.peaks(kind)
    if require_tpu:
        from repro import compile_cache

        compile_cache.enable()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    clog = CompileLog()
    parts = {"init": time.monotonic() - _T0}
    t = time.monotonic()
    pool = traffic_gen.make_pool(mix, seed)
    parts["traffic"] = time.monotonic() - t
    t = time.monotonic()
    calib = _calibration(pool, mcfg["seq_len"], CALIB_WINDOWS,
                         np.random.default_rng([int(seed), 4]))
    qp = kind_mod.make_model(mcfg, seed, calib)
    system = _build_system(cfg, qp)
    replay = Replay(cfg, mix, pool, seed, system)
    parts["weights_and_build"] = time.monotonic() - t
    t = time.monotonic()
    for _ in range(WARMUP_CALLS):
        replay.call()
    parts["compile_and_warmup"] = time.monotonic() - t
    parts["backend_compile"] = clog.seconds
    parts["cache_hits"], parts["cache_misses"] = clog.hits, clog.misses
    setup_s = time.monotonic() - _T0
    window = min(seconds, TRACE_SECONDS) if trace else seconds
    tdir = os.path.join(root, "build", "fenixbench", "trace",
                        f"{workload}_{int(seed)}")
    if trace:
        shutil.rmtree(tdir, ignore_errors=True)
        jax.profiler.start_trace(tdir, profiler_options=_trace_options())
    first_call = replay.calls
    compiles_before = clog.compiles
    before = replay.counters()
    t_start = prev = time.monotonic()
    lat = []
    while True:
        replay.call()
        now = time.monotonic()
        lat.append(now - prev)
        prev = now
        if now - t_start >= window:
            break
    elapsed = prev - t_start
    reduced = None
    if trace:
        jax.profiler.stop_trace()
        devs, spans = trace_reduce.load(
            trace_reduce.find_xplane(tdir),
            **({"device_lines": overrides["device_lines"]}
               if "device_lines" in overrides else {}))
        reduced = trace_reduce.reduce(devs, spans)
        shutil.rmtree(tdir, ignore_errors=True)
    after = replay.counters()
    delta = {k: after[k] - before[k] for k in after}
    used = devices[:chips]
    stats = [d.memory_stats() or {} for d in used]
    mem_peak = max(int(s.get("peak_bytes_in_use", 0)) for s in stats)
    window_calls = range(first_call, replay.calls)
    sent = len(window_calls) * replay.S
    # free the program's device state before the reference runs
    replay.system = system = None
    gc.collect()
    per_batch, answered = admission.replay(
        replay.batches(range(replay.calls)), sw,
        np.random.default_rng([int(seed), 6]))
    ref_granted = int(per_batch[first_call * replay.S // replay.B:].sum())
    runs, drains = replay.slot_runs(answered)
    verdicts = reference.judge(runs, drains, qp, mcfg,
                               np.random.default_rng([int(seed), 5]),
                               sw["ring_depth"], control)
    check = {
        "verdict_gap": verdicts["verdict_gap"],
        "due_missed_pct": round(verdicts["due_missed_pct"], 3),
        "admission_gap_pct": round(
            100.0 * abs(delta["granted"] / max(ref_granted, 1) - 1.0), 3),
        "lanes_lost": (delta["granted"] - delta["served"]
                       - delta["dropped_q"] - delta["fifo_tail"]
                       + delta["fifo_head"]),
        "answers_lost": delta["served"] - delta["answers_pushed"]}
    correct = (verdicts["judged"] > 0 and verdicts["due_checked"] > 0
               and all(check[k] <= limits[k] for k in limits))
    ctx = {"mcfg": mcfg, "switch": sw, "chips": chips,
           "peaks": pk, "setup_s": setup_s,
           "window": {"seconds": elapsed, "packets": sent,
                      "latencies_s": lat},
           "counters": delta, "trace": reduced}
    names = cell_metrics(bench, workload,
                         "per_layer" if trace else "end_to_end")
    metrics = {}
    units = {m["name"]: m["unit"] for m in bench["end_to_end"]
             + bench["per_layer"]}
    for name in names:
        mod = importlib.import_module(f"fenixbench.metrics.{name}")
        v = mod.read(ctx)
        if v is not None:
            metrics[name] = {"value": float(v), "unit": units[name]}
    device = {"platform": devices[0].platform, "kind": kind,
              "count": chips, "memory_peak_bytes": mem_peak}
    result = {"correct": bool(correct), "attempted": len(window_calls),
              "failed": 0, "metrics": metrics, "device": device}
    if reduced is not None:
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        result["breakdown"] = {"device_ops": reduced["device_ops"],
                               "idle_gaps": reduced["idle_gaps"]}
    info = {"setup_parts": parts, "judged": verdicts["judged"],
            "events": verdicts["events"], "skipped": verdicts["skipped"],
            "unexplained": verdicts["unexplained"],
            "due": verdicts["due"], "due_checked": verdicts["due_checked"],
            "due_skipped": verdicts["due_skipped"],
            "windows": verdicts["windows"],
            "control_gap": verdicts.get("control_gap"),
            "window_counters": delta, "ref_granted": ref_granted,
            "served_per_step": delta["served"] / max(delta["steps"], 1),
            "window_compiles": clog.compiles - compiles_before,
            **_stalls(lat)}
    result["check"] = {k: {"value": check[k], "limit": limits[k]}
                       for k in limits}
    result["check"]["judged_events"] = {"value": verdicts["judged"],
                                        "limit": ">0"}
    result["check"]["due_checked"] = {"value": verdicts["due_checked"],
                                      "limit": ">0"}
    return {"result": result, "check": check, "info": info}


def run_cell(workload: str, seed: int, seconds: float, trace: bool,
             out=sys.stdout, err=sys.stderr, **kw) -> int:
    """Run one cell and print its result line; the exit code."""
    try:
        m = measure(workload, seed, seconds, trace, **kw)
    except NoDevice as e:
        print(f"fenixbench: {e}", file=err)
        return 3
    print("fenixbench: " + json.dumps(m["info"]), file=err)
    for k, v in m["result"]["check"].items():
        print(f"check {k} {v['value']} limit {v['limit']}", file=err)
    err.flush()
    print(json.dumps(m["result"]), file=out)
    out.flush()
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    a = ap.parse_args(argv)
    return run_cell(a.workload, a.seed, a.seconds, bool(a.trace))


if __name__ == "__main__":
    sys.exit(main())
