"""Model-Engine Farm (§7 scale-out): E FPGA engines behind one switch.

FENIX's discussion points at the natural scale-out beyond one ZU19EG:
several FPGA Model Engines served by one switch.  This module makes that a
first-class subsystem rather than a loop around ``EngineModel.infer``:

* **Topology.**  An ``"engine"`` mesh axis *orthogonal* to the existing
  ``"pipe"`` axis: ``farm_mesh`` builds a 2-D ``(num_pipes, num_engines)``
  device mesh when enough devices are up, and the same per-(pipe, engine)
  cell function runs under nested ``vmap`` (with both axis names) on hosts
  below ``P * E`` devices.

* **Dataflow.**  Each pipe's Data Engine and Vector-I/O ring stay exactly
  as in the multi-pipeline driver.  The pipes' dequeued lanes are routed
  to per-engine *ingress* FIFOs by an occupancy-based router
  (``vio.engine_intake`` — the ``pipe_shares`` waterfall with engines as
  the consumers: the least-loaded engine takes the most lanes, and no lane
  is ever assigned beyond an engine's free capacity).  Every engine then
  drains its own ingress queue against its own per-engine service budget
  (the single-engine ``vio.step_budget``), runs its inference batch, and
  the verdicts scatter back through the *owning pipe's* delay line, tagged
  with the serving engine.

* **Collectives.**  Four per step, all static-shaped: one scalar
  ``[occupancy, t0, t1]`` all-gather over ``"pipe"`` (as in the pipes
  driver), one scalar free-space all-gather over ``"engine"``, one lane
  all-gather over ``"pipe"`` (features must reach their engine — the one
  place lane data crosses the mesh), and one result all-gather over
  ``"engine"`` (ids + classes only, no features).

``num_engines=1`` forced through the farm path is bit-identical to the
multi-pipeline driver (asserted in tests/test_engine_farm.py): the single
engine's ingress queue is pass-through (everything routed is served within
the step), its budget is the pipes driver's single budget, and the engine
tag is 0 everywhere.
"""

from __future__ import annotations

import warnings
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec

from repro.core.data_engine import rate_limiter as rl
from repro.core.model_engine import delay_line as dl
from repro.core.model_engine import vector_io as vio
from repro.tracing import scope

I32 = jnp.int32

# engine ingress queue-depth histogram: log2 buckets 0, 1, 2-3, 4-7, ...
DEPTH_BUCKETS = 16
_DEPTH_EDGES = np.asarray([1 << b for b in range(DEPTH_BUCKETS - 1)],
                          np.int64)


def farm_mesh(num_pipes: int, num_engines: int,
              devices: Optional[Sequence[jax.Device]] = None
              ) -> Optional[Mesh]:
    """2-D ``(pipe, engine)`` device mesh, or None for the vmap fallback.

    One device per (pipeline, engine) cell, taken from ``devices``
    (default ``jax.devices()`` — on CPU CI these are the
    ``--xla_force_host_platform_device_count`` virtual devices).  Fewer
    than ``num_pipes * num_engines`` devices run the same cell function
    under nested ``vmap`` on one device instead, and say so with a
    ``RuntimeWarning``.
    """
    devs = jax.devices() if devices is None else list(devices)
    need = num_pipes * num_engines
    if len(devs) >= need:
        return Mesh(np.asarray(devs[:need]).reshape(num_pipes, num_engines),
                    ("pipe", "engine"))
    warnings.warn(f"a {num_pipes}x{num_engines} farm needs {need} devices "
                  f"but {len(devs)} are present: it runs under nested vmap "
                  "on one device", RuntimeWarning, stacklevel=2)
    return None


def depth_histogram(depths: np.ndarray,
                    num_engines: int) -> List[List[int]]:
    """Per-engine log2 histogram of ingress queue-depth samples.

    ``depths`` is [n_samples, num_engines]; bucket b counts samples in
    [2^(b-1), 2^b) (bucket 0 is depth 0), saturating at the last bucket.
    """
    depths = np.asarray(depths, np.int64).reshape(-1, num_engines)
    hist = np.zeros((num_engines, DEPTH_BUCKETS), np.int64)
    for e in range(num_engines):
        b = np.searchsorted(_DEPTH_EDGES, depths[:, e], side="right")
        hist[e] = np.bincount(b, minlength=DEPTH_BUCKETS)
    return hist.tolist()


def route_ranks(shares: jax.Array, lanes: int,
                start: jax.Array, take: jax.Array
                ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Resolve one engine's intake ranks to (pipe, lane, valid) coordinates.

    The step's routed lanes form one pipe-major sequence: pipe p
    contributes its ``shares[p]`` dequeued lanes (FIFO order) at global
    ranks ``[offset_p, offset_p + shares[p])``.  Engine e takes the rank
    window ``[start, start + take)``; this maps each of its ``lanes``
    intake positions back to the owning (pipe, lane-within-pipe) pair.
    """
    csum = jnp.cumsum(shares)
    offs = csum - shares
    k = jnp.arange(lanes, dtype=I32)
    rank = start.astype(I32) + k
    pipe = jnp.searchsorted(csum, rank, side="right").astype(I32)
    pipe_c = jnp.minimum(pipe, shares.shape[0] - 1)
    lane = rank - offs[pipe_c]
    return pipe_c, lane, k < take


def gather_results(res_pipe: jax.Array, res_n: jax.Array,
                   my_pipe: jax.Array,
                   values: Tuple[jax.Array, ...]
                   ) -> Tuple[Tuple[jax.Array, ...], jax.Array]:
    """Select one pipe's results from the all-gathered [E, S] farm output.

    Flattens engine-major (engine order, then each engine's FIFO service
    order — deterministic), packs the lanes owned by ``my_pipe`` to the
    front, and returns the packed value arrays plus the count.  Each value
    array keeps its [E, S] shape flattened to [E * S].
    """
    e, s = res_pipe.shape
    lane_ok = jnp.arange(s, dtype=I32)[None, :] < res_n[:, None]
    mine = (lane_ok & (res_pipe == my_pipe)).reshape(-1)
    rank = jnp.cumsum(mine.astype(I32))
    dest = jnp.where(mine, rank - 1, e * s)
    packed = tuple(
        jnp.zeros((e * s,), v.dtype).at[dest].set(v.reshape(-1),
                                                  mode="drop")
        for v in values)
    return packed, jnp.sum(mine.astype(I32))


def make_farm_step(num_pipes: int, num_engines: int, iocfg: vio.IOConfig,
                   base_rate_per_us: float, loop_latency_us: int,
                   de_local, model, mesh: Optional[Mesh], masked: bool,
                   local_cfg=None):
    """One scan step of the farm driver: sharded pipes feeding E engines.

    ``de_local`` is the pipe-local Data-Engine body (built by
    ``fenix._make_pipe_local`` from the per-pipe local config);
    ``base_rate_per_us`` is the SINGLE-engine global service rate — each
    engine's budget uses it directly, so the farm's aggregate service is
    ``num_engines`` times the pipes driver's single budget and
    ``num_engines=1`` reproduces that budget bit-for-bit.

    ``local_cfg`` is the per-pipe ``EngineConfig`` the in-scan control
    plane rebuilds each pipe's admission LUT with when the chunk's
    ``"_cp"`` flag marks a T_w window boundary (``lax.cond`` at the end of
    the cell, after the freeze-select, so frozen pipes roll their windows
    too — exactly when the old host-side rebuild ran).  The update is a
    pure function of the pipe's own switch state, so it is engine-invariant
    by construction (required: ``pstate`` is replicated over the
    ``"engine"`` axis).

    The cell function below is written per (pipe, engine) coordinate and
    runs either under ``shard_map`` on the 2-D mesh or under nested
    ``vmap`` with the same axis names.  Values that only vary along one
    axis stay unbatched along the other (vmap) / replicated (shard_map),
    so the Data Engine is computed once per pipe and the service once per
    engine in both modes.

    ``masked=True`` compiles the traffic-skew variant: a pipe whose stream
    is exhausted replays a dummy batch with its switch state frozen and
    zero merge weight.  The engines keep draining backlog during such
    steps; results owned by a frozen pipe are still pushed to its delay
    line (they are real results of earlier real batches), timestamped with
    the farm-wide clock ``max_p(now_p)`` instead of the frozen pipe's
    dummy clock.
    """
    imax = jnp.iinfo(jnp.int32)
    serve_lanes = vio.engine_serve_lanes(iocfg, num_pipes)

    def cell_step(pstate, pqueues, pdline, eq, chunk):
        cp = chunk["_cp"]
        # -- pipe-local switch stage (varies over "pipe" only) --------------
        if masked:
            active = chunk["_active"]
            chunk = {k: v for k, v in chunk.items() if k != "_active"}
        new_s, new_q, new_d, aux = de_local(pstate, pqueues, pdline, chunk)
        if masked:
            pstate, pqueues, pdline = jax.tree.map(
                lambda nu, old: jnp.where(active, nu, old),
                (new_s, new_q, new_d), (pstate, pqueues, pdline))
            occ_self = (pqueues["tail"] - pqueues["head"]) \
                * active.astype(I32)
            lo_self = jnp.where(active, aux["ts_first"], imax.max)
            hi_self = jnp.where(active, aux["now"], imax.min)
        else:
            pstate, pqueues, pdline = new_s, new_q, new_d
            occ_self = pqueues["tail"] - pqueues["head"]
            lo_self, hi_self = aux["ts_first"], aux["now"]
        gath = jax.lax.all_gather(
            jnp.stack([occ_self, lo_self, hi_self]), "pipe")    # [P, 3]
        hi = jnp.max(gath[:, 2])
        # -- per-engine service budget (the farm's one step_budget site) ----
        ebudget = vio.step_budget(jnp.min(gath[:, 1]), hi,
                                  base_rate_per_us,
                                  num_pipes * iocfg.queue_len)
        free_self = vio.engine_free(eq, iocfg, num_pipes)
        freeg = jax.lax.all_gather(free_self, "engine")         # [E]
        # pipes dequeue against the farm's pooled budget, capped by the
        # total ingress space so the router can always place every lane
        take_total = jnp.minimum(num_engines * ebudget, jnp.sum(freeg))
        shares = vio.pipe_shares(gath[:, 0], take_total)        # [P]
        # actual per-pipe dequeues: dequeue_device additionally caps each
        # share at serve_lanes (same as the pipes driver); the router must
        # see the capped counts or it would route phantom lanes.  Every
        # cell derives them from the gathered scalars — no extra collective
        counts = jnp.minimum(shares, iocfg.serve_lanes)         # [P]
        my_share = shares[jax.lax.axis_index("pipe")]
        with scope("vio_dequeue"):
            pqueues, s_de, h_de, f_de, _ = vio.dequeue_device(
                pqueues, iocfg, my_share)
        # -- route lanes to engines (the one lane-data collective; slot,
        # hash, and features pack into a single [L, 2+K] int32 gather —
        # int32<->uint32 casts round-trip bitwise) -------------------------
        with scope("vio_enqueue"):
            lane_pack = jnp.concatenate(
                [s_de[:, None], h_de.astype(I32)[:, None],
                 f_de.reshape(f_de.shape[0], -1)], axis=1)
            lanes = jax.lax.all_gather(lane_pack, "pipe")   # [P, L, 2+K]
            intake = vio.engine_intake(freeg, jnp.sum(counts))  # [E]
            e_idx = jax.lax.axis_index("engine")
            estart = (jnp.cumsum(intake) - intake)[e_idx]
            pipe_of, lane_of, valid_in = route_ranks(
                counts, serve_lanes, estart, intake[e_idx])
            flat = pipe_of * iocfg.serve_lanes + lane_of
            sel = lanes.reshape(num_pipes * iocfg.serve_lanes, -1)[flat]
            eq = vio.enqueue_engine(
                eq, iocfg, num_pipes, valid_in,
                sel[:, 0], sel[:, 1].astype(jnp.uint32),
                sel[:, 2:].reshape(serve_lanes, iocfg.feat_len,
                                   iocfg.feat_dim),
                pipe_of)
        # -- per-engine service (varies over "engine" only) -----------------
        with scope("vio_dequeue"):
            eq, es, eh, ef, ep, srv = vio.dequeue_engine(eq, iocfg,
                                                         num_pipes, ebudget)
        with scope("infer"):
            ecls = model.infer(ef)
        depth_self = eq["tail"] - eq["head"]
        # -- results return through the owning pipe's delay line (the one
        # id+class collective: [slot, hash, class, pipe, count] rows) ------
        with scope("delay_push"):
            res_pack = jnp.stack([es, eh.astype(I32), ecls, ep,
                                  jnp.full_like(es, srv)])      # [5, S]
            res = jax.lax.all_gather(res_pack, "engine")    # [E, 5, S]
            res_s, res_c, res_p = res[:, 0], res[:, 2], res[:, 3]
            res_h = res[:, 1].astype(jnp.uint32)
            res_n = res[:, 4, 0]
            eng_id = jnp.broadcast_to(
                jnp.arange(num_engines, dtype=I32)[:, None], res_s.shape)
            (sel_s, sel_h, sel_c, sel_e), my_cnt = gather_results(
                res_p, res_n, jax.lax.axis_index("pipe"),
                (res_s, res_h, res_c, eng_id))
            if masked:
                # frozen pipes still receive backlog verdicts; stamp them
                # with the farm-wide clock, not the dummy replay's
                # timestamps
                push_ts = jnp.where(active, aux["now"], hi) \
                    + loop_latency_us
            else:
                push_ts = aux["now"] + loop_latency_us
            pdline = dl.push(pdline, push_ts, sel_s, sel_h, sel_c, my_cnt,
                             engines=sel_e)
        # in-scan control plane: rebuild this pipe's LUT + roll its window
        # when the chunk closes a T_w window — no host round trip
        with scope("control_plane"):
            pstate = jax.lax.cond(
                cp, lambda s: rl.control_plane_update(s, local_cfg),
                lambda s: s, pstate)
        pstats = jnp.stack([aux["granted"], aux["classified"],
                            aux["n_tree"]])
        if masked:
            pstats = pstats * active.astype(I32)
        return (pstate, pqueues, pdline, eq, aux["verdict"], pstats,
                srv, depth_self)

    if mesh is not None:
        def shard_body(pstate, pqueues, pdline, eq, chunk):
            args = jax.tree.map(lambda x: x[0],
                                (pstate, pqueues, pdline, eq, chunk))
            out = cell_step(*args)
            return jax.tree.map(lambda x: jnp.asarray(x)[None], out)

        pipe_sp, eng_sp = PartitionSpec("pipe"), PartitionSpec("engine")
        stage = jax.shard_map(
            shard_body, mesh=mesh,
            in_specs=(pipe_sp, pipe_sp, pipe_sp, eng_sp, pipe_sp),
            out_specs=(pipe_sp, pipe_sp, pipe_sp, eng_sp, pipe_sp,
                       pipe_sp, eng_sp, eng_sp),
            # outputs are replicated along their unmentioned axis by
            # construction (deterministic compute from replicated inputs /
            # all-gathered operands); skip the static varying-axes checker
            check_vma=False)
    else:
        inner = jax.vmap(cell_step, axis_name="engine",
                         in_axes=(None, None, None, 0, None),
                         out_axes=(None, None, None, 0, None, None, 0, 0))
        stage = jax.vmap(inner, axis_name="pipe",
                         in_axes=(0, 0, 0, None, 0),
                         out_axes=(0, 0, 0, None, 0, 0, None, None))

    def step_fn(carry, chunk):
        pstates, pqueues, pdls, eqs = carry
        (pstates, pqueues, pdls, eqs, verdict, pstats, served,
         depth) = stage(pstates, pqueues, pdls, eqs, chunk)
        return (pstates, pqueues, pdls, eqs), (verdict,
                                               pstats.sum(axis=0),
                                               served, depth)

    return step_fn


def make_farm_tail(num_pipes: int, num_engines: int, iocfg: vio.IOConfig,
                   base_rate_per_us: float, loop_latency_us: int,
                   de_local, model):
    """Per-pipe tail step of the farm driver.

    A pipe whose stream outlasts the uniform scan finishes its trailing
    (< batch) packets here, draining only its own ring against its
    1/num_pipes share of every engine's budget.  Tail lanes are served
    directly (no ingress round-trip — the scan is over, there is no later
    step to drain a queue) but still capacity-split across the engines by
    the same waterfall, so per-engine service accounting stays exact and
    every lane carries its serving-engine tag.  ``num_engines=1`` is the
    pipes driver's tail step bit-for-bit.
    """
    tail_rate = base_rate_per_us / num_pipes

    def tail_fn(carry, chunk):
        state, queues, dline = carry
        state, queues, dline, aux = de_local(state, queues, dline, chunk)
        ebudget = vio.step_budget(aux["ts_first"], aux["now"], tail_rate,
                                  iocfg.queue_len)
        with scope("vio_dequeue"):
            queues, s2, h2, f2, cnt = vio.dequeue_device(
                queues, iocfg, num_engines * ebudget)
            assign = vio.engine_intake(
                jnp.full((num_engines,), ebudget, I32), cnt)
            tags = jnp.searchsorted(jnp.cumsum(assign),
                                    jnp.arange(s2.shape[0], dtype=I32),
                                    side="right").astype(I32)
            tags = jnp.minimum(tags, num_engines - 1)
        with scope("infer"):
            cls = model.infer(f2)
        with scope("delay_push"):
            dline = dl.push(dline, aux["now"] + loop_latency_us, s2, h2,
                            cls, cnt, engines=tags)
        stats = jnp.stack([aux["granted"], cnt, aux["classified"],
                           aux["n_tree"]])
        return (state, queues, dline), (aux["verdict"], stats, assign)

    return tail_fn
