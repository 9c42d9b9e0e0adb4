"""Plain reference of the switch's admission: which packets it grants.

The Data Engine grants a packet when a 16-bit draw falls under the
control plane's probability for the packet's slot (the paper's Eq. 2 over
the time since the slot's last grant ``T_i`` and the packets it has
backlogged since ``C_i``, binned into a LUT), and while the token bucket
has credit.  The draws are made on the chip, but the LUT is close to a
step in ``T_i``: a flow is granted soon after its period passes, whoever
draws, so the grants are a property of the stream and the configuration
(two replays with different draws agree on all but about 1 in 10^4).
``replay`` runs the same registers over the same stream with draws of
its own:

* per packet, ``T_i`` and ``C_i`` from the slot's backlog registers at
  batch start, plus the packets of the slot earlier in the batch;
* the batch's bucket credit: the burst at batch start plus the refill up
  to each arrival, spent in packet order by the selected packets;
* at each control-plane boundary a new LUT from the window's new flows
  ``N`` and packets ``Q`` (Eq. 2 in float32, as the switch's control
  plane computes it in the scan), and the window counters restart.

Nothing here imports the program.
"""

from __future__ import annotations

from typing import Dict, Iterable, Tuple

import numpy as np

# LUT layout of the configuration (the switch's power-of-two binning)
T_SHIFT, C_SHIFT, T_BINS, C_BINS, PROB_BITS = 10, 0, 64, 32, 16


def probability(t, c, n, q, v, dtype=np.float64):
    """Eq. 2, clipped to [0, 1]: the linear ramp between the periods
    ``N/V`` and ``Q/(Q_i V)`` (``Q_i = C/T``), a step at ``N/V`` where the
    two meet."""
    f = dtype
    t = np.asarray(t, f)
    c = np.maximum(np.asarray(c, f), f(1e-12))
    n, q, v = f(n), f(q), f(v)
    denom = q * t - n * c
    with np.errstate(divide="ignore", invalid="ignore"):
        slow = c * (v * t - n) / np.where(np.abs(denom) < 1e-9, np.inf,
                                          denom)
        fast = t * (v * c - q) / np.where(np.abs(denom) < 1e-9, np.inf,
                                          -denom)
    p = np.where(denom > 1e-9, slow,
                 np.where(denom < -1e-9, fast, (t >= n / v).astype(f)))
    return np.clip(p, 0.0, 1.0)


def build_lut(n, q_per_us, v_per_us, dtype=np.float64) -> np.ndarray:
    """[T_BINS, C_BINS] integer probabilities at the bins' centres."""
    f = dtype
    ti = (np.arange(T_BINS, dtype=f) + f(0.5)) * f(1 << T_SHIFT)
    cj = (np.arange(C_BINS, dtype=f) + f(0.5)) * f(1 << C_SHIFT)
    tt, cc = np.meshgrid(ti, cj, indexing="ij")
    p = probability(tt, cc, n, q_per_us, v_per_us, dtype)
    return np.round(p * f((1 << PROB_BITS) - 1)).astype(np.int32)


def rate_per_us(sw: Dict) -> float:
    """Token rate V: min(FPGA rate, link bytes / feature bytes), per us."""
    return min(sw["fpga_hz"], sw["link_bw_bytes"]
               / max(sw["feat_bytes"], 1)) / 1e6


def _running_count(slot: np.ndarray) -> np.ndarray:
    """Packets of the same slot earlier in the batch (0 for the slot's
    first)."""
    # a stable sort of 16-bit keys is a radix sort, ten times faster
    key = slot.astype(np.uint16) if slot.max() < 1 << 16 else slot
    order = np.argsort(key, kind="stable")
    s = slot[order]
    idx = np.arange(len(s))
    start = np.r_[True, s[1:] != s[:-1]]
    run = idx - np.maximum.accumulate(np.where(start, idx, 0))
    out = np.empty(len(s), np.int64)
    out[order] = run
    return out


def replay(batches: Iterable[Dict[str, np.ndarray]], sw: Dict,
           rng: np.random.Generator, n_est: float = 1000.0,
           q_est_pps: float = 1e6) -> Tuple[np.ndarray, np.ndarray]:
    """Admission over ``batches`` (each ``ts_us``, ``hash``, ``slot``,
    ``cp``, true where the batch closes a control-plane window, and
    ``watch``, a mask of packets to report), from a freshly reset switch
    whose first LUT is built for ``n_est`` flows at ``q_est_pps``.

    Returns the grants of every batch, and for the watched packets in
    stream order whether each was granted and found room in an empty
    Vector I/O FIFO (the first ``queue_len`` grants of a batch)."""
    n_slots = 1 << sw["n_slots_log2"]
    v = rate_per_us(sw)
    cost = max(1, int(round(1.0 / v)))
    cap = sw["bucket_len"] * cost
    window_us = float(sw.get("window_us", 1_000_000))
    table = np.zeros(n_slots, np.uint32)
    bk_n = np.zeros(n_slots, np.int64)
    bk_t = np.zeros(n_slots, np.int64)
    lut = build_lut(n_est, q_est_pps / 1e6, v)
    bucket, t_last = cap, 0
    flow_cnt = win_pkts = 0
    out, answered = [], []
    for b in batches:
        ts = b["ts_us"].astype(np.int64)
        h, slot = b["hash"], b["slot"]
        n = len(ts)
        stored = table[slot]
        run = _running_count(slot)
        is_new = (run == 0) & ((stored == 0) | (stored != h))
        t_i = np.maximum(ts - bk_t[slot], 0)
        c_i = np.maximum(bk_n[slot], 0) + run
        p = lut[np.clip(t_i >> T_SHIFT, 0, T_BINS - 1),
                np.clip(c_i >> C_SHIFT, 0, C_BINS - 1)]
        selected = rng.integers(0, 1 << PROB_BITS, n) < p
        t_ref = ts[0] if t_last == 0 else t_last
        credit = min(bucket, cap) + np.maximum(ts - t_ref, 0)
        granted = selected & (np.cumsum(selected * cost) <= credit)
        bucket = int(np.clip(credit[-1] - granted.sum() * cost, 0, cap))
        t_last = int(ts[-1])
        table[slot] = h
        np.add.at(bk_n, slot, 1)
        bk_n[slot] = np.where(granted, 0, bk_n[slot])
        bk_t[slot] = np.where(granted, ts, bk_t[slot])
        flow_cnt += int(is_new.sum())
        win_pkts += n
        out.append(int(granted.sum()))
        room = np.cumsum(granted) <= sw["queue_len"]
        answered.append((granted & room)[b["watch"]])
        if b["cp"]:
            f32 = np.float32
            q = max(f32(win_pkts), f32(1.0)) / f32(max(window_us, 1.0))
            lut = build_lut(max(f32(flow_cnt), f32(1.0)), q, f32(v), f32)
            flow_cnt = win_pkts = 0
    return np.asarray(out, np.int64), np.concatenate(answered)
