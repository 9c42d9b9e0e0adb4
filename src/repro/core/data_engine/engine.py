"""The fused Data Engine (§4): per-packet switch pipeline as a lax.scan.

``process_batch`` preserves the exact per-packet sequential semantics of the
switch (shared token bucket, ring ordering) by scanning over packets; the
stateless stages (hashing, LUT lookup, feature assembly) vectorize inside
each scan step.  ``process_batch_fast`` is the vectorized throughput mode
used by the Tbps-scale simulator: identical flow/ring/probability semantics,
token-bucket admission approximated by a prefix-sum credit check (documented
deviation; validated against the scan mode in tests).

Both are *per-shard pure functions*: every table, bucket, and PRNG they
touch lives in the state dict they are handed.  The multi-pipeline data
plane exploits this directly — ``shard_map`` (or ``process_pipes_fast``'s
vmap) runs ``process_batch_fast`` once per pipe against that pipe's slice
of the stacked state, with the *local* ``EngineConfig``
(``local_engine_config``: 1/P of the slot space, 1/P of the token rate) and
zero cross-pipe communication.
"""

from __future__ import annotations

import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.data_engine import buffer_manager as bm
from repro.core.data_engine import flow_tracker as ft
from repro.core.data_engine import rate_limiter as rl
from repro.core.data_engine.state import EngineConfig
from repro.tracing import scope

I32 = jnp.int32


def _packet_step(state: Dict, pkt: Dict, cfg: EngineConfig,
                 tree: Optional[Dict] = None, tree_depth: int = 4):
    """One packet through Flow Tracker -> Rate Limiter -> Buffer Manager."""
    ts = pkt["ts_us"].astype(I32)
    slot, h, is_new, collision = ft.lookup(state, cfg, pkt)
    state = ft.on_packet(state, cfg, slot, h, is_new, collision, ts)
    feat = bm.extract_feature(state, cfg, slot, pkt, is_new)
    # rate limiter decides whether this flow ships features now
    state, granted = rl.step(state, cfg, slot, ts)
    # mirror packet payload (F1..F8 + current F9), valid when granted
    payload = bm.assemble(state, cfg, slot, feat)
    state = bm.push(state, cfg, slot, feat, ts)
    # preliminary per-packet verdict (§4.1): stored class else switch tree
    stored_cls = state["cls"][slot]
    if tree is not None:
        from repro.core.data_engine.decision_tree import predict
        pre = predict(tree, feat, tree_depth)
    else:
        pre = jnp.asarray(-1, I32)
    verdict = jnp.where(stored_cls >= 0, stored_cls, pre)
    out = {"granted": granted, "slot": slot, "hash": h,
           "payload": payload, "verdict": verdict, "is_new": is_new}
    return state, out


@functools.partial(jax.jit, static_argnames=("cfg", "tree_depth"))
def process_batch(state: Dict, packets: Dict, cfg: EngineConfig,
                  tree: Optional[Dict] = None, tree_depth: int = 4
                  ) -> Tuple[Dict, Dict]:
    """Scan a packet batch through the pipeline (exact semantics).

    packets: dict of [n] arrays. Returns (state', outputs of shape [n, ...]).
    """

    def step(st, pkt):
        return _packet_step(st, pkt, cfg, tree=tree, tree_depth=tree_depth)

    return jax.lax.scan(step, state, packets)


@functools.partial(jax.jit, static_argnames=("cfg",))
def process_batch_fast(state: Dict, packets: Dict, cfg: EngineConfig
                       ) -> Tuple[Dict, Dict]:
    """Vectorized admission (simulator fast path).

    Probability gating is exact; the shared token bucket is approximated by
    granting selected packets while their cumulative cost fits the credit
    available at batch start + refill up to each arrival.
    """
    from repro.core.data_engine.state import hash_five_tuple

    n = packets["ts_us"].shape[0]
    ts = packets["ts_us"].astype(I32)
    with scope("flow_table"):
        h = hash_five_tuple(packets["src_ip"], packets["dst_ip"],
                            packets["src_port"], packets["dst_port"],
                            packets["proto"])
        slot = (h & jnp.uint32(cfg.n_slots - 1)).astype(I32)
        stored = state["hash"][slot]
        # first occurrence of each slot in this batch determines
        # new/collision
        first_in_batch = _first_occurrence(slot, cfg.n_slots)
        is_new = first_in_batch & ((stored == 0) | (stored != h))
    # probability lookup against batch-start backlog (approximation)
    with scope("sort_segment"):
        run = (_running_count_dense(slot, n) if cfg.dense_backlog
               else _running_count(slot, n))
    with scope("flow_table"):
        t_i = jnp.maximum(ts - state["bklog_t"][slot], 0)
        c_i = jnp.maximum(state["bklog_n"][slot], 0) + run
    # fused admission: LUT lookup + threshold + token bucket in ONE call
    # (rl.admit_batch -> fused_admission).  Bucket semantics: spend_i <=
    # burst credit (capped at batch start) + refill_i.  The cap limits
    # *idle accumulation*, not throughput: refill earned during the batch
    # is spendable immediately (matches the scan semantics whenever packet
    # timestamps are spread out; see test_data_engine).
    with scope("gate"):
        key, sub = jax.random.split(state["rng_key"])
        granted, bucket_new = rl.admit_batch(state, cfg, t_i, c_i, ts, sub)
        state = dict(state)
        state["rng_key"] = key
        state["bucket"] = bucket_new
        state["t_last"] = ts[-1]
        state["granted"] = state["granted"] + granted.sum().astype(I32)
    # features + mirror payloads from the PRE-update ring (F1..F8 then F9);
    # ipd is 0 for flows new to the table (no previous timestamp)
    with scope("feature_ring"):
        known = (stored != 0) & (stored == h)
        feat = jnp.stack(
            [packets["pkt_len"].astype(I32),
             jnp.where(known, jnp.maximum(ts - state["last_ts"][slot], 0),
                       0)],
            axis=-1)
        idx = state["buff_idx"][slot]
        order = jnp.mod(idx[:, None] + jnp.arange(cfg.ring_depth)[None],
                        cfg.ring_depth)
        seq = jnp.take_along_axis(state["ring"][slot], order[..., None],
                                  axis=1)
        payload = jnp.concatenate([seq, feat[:, None]], axis=1)
        # ring bulk update (last write per slot wins)
        state["ring"] = state["ring"].at[slot, idx].set(feat)
        nxt = jnp.where(idx + 1 == cfg.ring_depth, 0, idx + 1)
        state["buff_idx"] = state["buff_idx"].at[slot].set(nxt)
    # flow table bulk update (last write per slot wins)
    with scope("flow_table"):
        state["hash"] = state["hash"].at[slot].set(h)
        state["last_ts"] = state["last_ts"].at[slot].set(ts)
        state["bklog_n"] = state["bklog_n"].at[slot].add(1)
        state["bklog_n"] = state["bklog_n"].at[slot].set(
            jnp.where(granted, 0, state["bklog_n"][slot]))
        state["bklog_t"] = state["bklog_t"].at[slot].set(
            jnp.where(granted, ts, state["bklog_t"][slot]))
        state["flow_cnt"] = state["flow_cnt"] + is_new.sum().astype(I32)
        state["win_pkt_cnt"] = state["win_pkt_cnt"] + n
        verdict = jnp.where(state["cls"][slot] >= 0, state["cls"][slot], -1)
    out = {"granted": granted, "slot": slot, "hash": h, "payload": payload,
           "verdict": verdict, "is_new": is_new}
    return state, out


@functools.partial(jax.jit, static_argnames=("local_cfg",))
def process_pipes_fast(states: Dict, packets: Dict,
                       local_cfg: EngineConfig) -> Tuple[Dict, Dict]:
    """Vectorized admission across pipes: states/packets carry a leading
    [num_pipes] dim, each pipe running ``process_batch_fast`` on its own
    table, bucket, and PRNG stream.  The mesh-sharded driver in ``fenix.py``
    wraps the same per-pipe function in ``shard_map``; this vmap form is the
    1-device fallback and the unit-testable reference for it.
    """
    return jax.vmap(lambda st, pk: process_batch_fast(st, pk, local_cfg)
                    )(states, packets)


def _first_occurrence(slot: jax.Array, n_slots: int) -> jax.Array:
    """Mask of packets that are the first in batch to touch their slot."""
    n = slot.shape[0]
    first_idx = jnp.full((n_slots,), n, jnp.int32).at[slot].min(
        jnp.arange(n, dtype=jnp.int32))
    return first_idx[slot] == jnp.arange(n)


def _running_count(slot: jax.Array, n: int) -> jax.Array:
    """#earlier packets in this batch with the same slot (backlog adjust).

    O(n log n) sort/segment formulation: stable-sort packets by slot (ties
    keep batch order), then each packet's rank within its equal-slot run —
    position minus the running maximum of run-start positions — IS the
    count of earlier same-slot packets.  No n x n intermediate, so batch
    sizes of 4096-8192 stay cache-resident.
    """
    order = jnp.argsort(slot, stable=True)
    s = slot[order]
    idx = jnp.arange(n, dtype=I32)
    is_start = jnp.concatenate(
        [jnp.ones((1,), bool), s[1:] != s[:-1]])
    seg_first = jax.lax.cummax(jnp.where(is_start, idx, 0))
    run_sorted = idx - seg_first
    return jnp.zeros((n,), I32).at[order].set(run_sorted)


def _running_count_dense(slot: jax.Array, n: int) -> jax.Array:
    """O(n^2) reference for ``_running_count`` (tests + throughput bench)."""
    eq = slot[None, :] == slot[:, None]
    tri = jnp.tril(jnp.ones((n, n), bool), k=-1)
    return jnp.sum(eq & tri, axis=1).astype(I32)
