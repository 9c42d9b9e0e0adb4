"""The one traffic generator: a mix file's parameters -> packet columns.

A mix (``fenixbench/traffic/<name>.json``) describes application traffic
as per-class flow profiles (packet-length and inter-packet-delay laws,
burstiness, flow sizes, the class ratio) plus how many flows are
concurrently alive.  ``make_pool`` turns it into one stationary stretch
of trace time:

* flow shapes are drawn per class exactly as the ISCX profiles of the
  program's synthetic corpus draw them (gamma flow sizes, jittered
  length/IPD laws, burst mixture), vectorized over all flows;
* flows arrive as a Poisson process whose rate holds ``concurrent_flows``
  alive on average.  The pool keeps only the packets inside
  ``[1, pool_trace_us]``: flows already in progress at the start are
  included (a flow of duration D overlaps the stretch with weight
  ``D + T``), so the stretch has no ramp-up.

``cycle_segment`` replays the pool as an endless stream whose
timestamps never decrease: pass ``p`` adds ``p * period_us``.
``write_pcap`` writes columns as a classic pcap (Ethernet/IPv4/TCP|UDP),
vectorized, in the record layout the program's capture parser reads.
"""

from __future__ import annotations

import json
import os
from typing import Dict

import numpy as np

PKT_COLS = ("ts_us", "pkt_len", "src_ip", "dst_ip", "src_port",
            "dst_port", "proto")
COL_DTYPES = {"ts_us": np.int32, "pkt_len": np.int32,
              "src_ip": np.uint32, "dst_ip": np.uint32,
              "src_port": np.uint32, "dst_port": np.uint32,
              "proto": np.uint32}

TRAFFIC_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "traffic")


def load_mix(name: str, directory: str = TRAFFIC_DIR) -> Dict:
    """Read a mix file; a ``"base"`` key names a mix whose keys it
    inherits and overrides."""
    with open(os.path.join(directory, name + ".json")) as f:
        mix = json.load(f)
    base = mix.pop("base", None)
    if base is not None:
        merged = load_mix(base, directory)
        merged.update(mix)
        mix = merged
    mix["name"] = name
    return mix


def _rng(seed: int, stream: int) -> np.random.Generator:
    # any non-negative seed, 2**31 and above included
    return np.random.default_rng([int(seed), stream])


def _flow_shapes(mix: Dict, n_flows: int, rng: np.random.Generator):
    """Per-flow class, packet lengths and IPDs, flattened.

    Returns (label [F], n_pkts [F], lens [P], ipd [P]) with the packets of
    flow f at ``offsets[f]:offsets[f+1]`` and ``ipd`` of each flow's first
    packet 0."""
    classes = mix["classes"]
    ratio = np.asarray([c["ratio"] for c in classes], np.float64)
    label = rng.choice(len(classes), size=n_flows, p=ratio / ratio.sum())
    col = {k: np.asarray([c[k] for c in classes], np.float64)[label]
           for k in ("len_mean", "len_std", "len_bimodal", "ipd_log_mu",
                     "ipd_log_sigma", "burstiness", "flow_len_mean")}
    shape = mix["flow_len_gamma_shape"]
    n = np.maximum(mix["flow_len_min"], rng.gamma(
        shape, col["flow_len_mean"] / shape).astype(np.int64))
    n = np.minimum(n, mix["flow_len_max"])
    lo, hi = mix["len_jitter"]
    lm = col["len_mean"] * rng.uniform(lo, hi, n_flows)
    im = col["ipd_log_mu"] + rng.normal(0.0, mix["ipd_mu_jitter"], n_flows)
    fid = np.repeat(np.arange(n_flows), n)
    total = int(n.sum())
    mtu = rng.random(total) < col["len_bimodal"][fid]
    m_lo, m_hi = mix["mtu_len"]
    lens = np.where(
        mtu, m_hi - rng.integers(0, m_hi - m_lo + 1, total),
        np.clip(rng.normal(lm[fid], col["len_std"][fid]),
                mix["min_len"], m_hi))
    burst = rng.random(total) < col["burstiness"][fid]
    b_lo, b_hi = mix["burst_ipd_us"]
    ipd = np.where(burst, rng.integers(b_lo, b_hi, total),
                   10.0 ** rng.normal(im[fid], col["ipd_log_sigma"][fid])
                   ).astype(np.int64)
    ipd = np.clip(ipd, *mix["ipd_clip_us"])
    offsets = np.concatenate([[0], np.cumsum(n)])
    ipd[offsets[:-1]] = 0
    return label, n, offsets, lens.astype(np.int32), ipd


def make_pool(mix: Dict, seed: int) -> Dict[str, np.ndarray]:
    """One stationary stretch of the mix, a whole number of segments long.

    Returns the packet columns (``PKT_COLS``) time-ordered, plus
    ``flow`` (flow index per packet), ``period_us`` (the stretch length;
    the pass offset of ``cycle_segment``) and ``flow_start``/``flow_end``
    (every flow's full extent, for the concurrency check)."""
    rng = _rng(seed, 1)
    T = int(mix["pool_trace_us"])
    conc = float(mix["concurrent_flows"])
    # flow durations: estimate E[D] from a base draw, then draw the flows
    # that overlap [0, T) length-biased (weight D + T) from a larger one
    n_base = int(mix["base_flows"])
    _, n_pk, off, lens, ipd = _flow_shapes(mix, n_base, rng)
    dur = np.add.reduceat(ipd, off[:-1]).astype(np.float64)
    n_over = rng.poisson(conc * (1.0 + T / dur.mean()))
    pick = rng.choice(n_base, size=n_over, p=(dur + T) / (dur + T).sum())
    start = (rng.random(n_over) * (dur[pick] + T) - dur[pick]).astype(
        np.int64)
    # a picked flow's packets inside [0, T) are one contiguous run of its
    # packets; ``cum`` is non-decreasing over all base packets, so one
    # searchsorted per flow bounds the run and only those packets expand
    cum = np.cumsum(ipd)
    c0 = cum[off[pick]]
    a = np.clip(np.searchsorted(cum, c0 - start, "left"),
                off[pick], off[pick + 1])
    b = np.clip(np.searchsorted(cum, c0 - start + T, "left"),
                off[pick], off[pick + 1])
    cnt = b - a
    flow = np.repeat(np.arange(n_over), cnt)
    src = np.repeat(a, cnt) + (np.arange(int(cnt.sum())) - np.repeat(
        np.cumsum(cnt) - cnt, cnt))
    ts = start[flow] + (cum[src] - c0[flow]) + 1
    plen = lens[src]
    order = np.argsort(ts, kind="stable")
    seg = int(mix["segment_pkts"])
    n_keep = (len(order) // seg) * seg
    if n_keep == 0:
        raise ValueError(f"mix {mix['name']!r} yields {len(order)} packets "
                         f"in {T} us, less than one segment of {seg}")
    order = order[:n_keep]
    flow, ts, plen = flow[order], ts[order], plen[order]
    tcp = rng.random(n_over) < mix["tcp_share"]
    five = {
        "src_ip": rng.integers(1, 2**31, n_over),
        "dst_ip": rng.integers(1, 2**31, n_over),
        "src_port": rng.integers(1024, 65535, n_over),
        "dst_port": rng.integers(1, 1024, n_over),
        "proto": np.where(tcp, 6, 17),
    }
    pool = {k: v[flow].astype(COL_DTYPES[k]) for k, v in five.items()}
    pool["ts_us"] = ts.astype(np.int32)
    pool["pkt_len"] = plen.astype(np.int32)
    pool["flow"] = flow.astype(np.int32)
    pool["period_us"] = T
    pool["flow_start"] = start
    pool["flow_end"] = start + dur[pick].astype(np.int64)
    return pool


def concurrency(pool: Dict, times_us: np.ndarray) -> np.ndarray:
    """Flows alive (first packet <= t <= last packet) at each time."""
    s = np.sort(pool["flow_start"] + 1)
    e = np.sort(pool["flow_end"] + 1)
    return (np.searchsorted(s, times_us, side="right")
            - np.searchsorted(e, times_us, side="left"))


def n_segments(pool: Dict, seg: int) -> int:
    return len(pool["ts_us"]) // seg


def cycle_segment(pool: Dict, k: int, seg: int) -> Dict[str, np.ndarray]:
    """Segment ``k`` of the endless replay of ``pool``: packets
    ``[k*seg, (k+1)*seg)`` of the cycled stream, pass ``p`` shifted by
    ``p * period_us`` so trace time never runs backwards."""
    per = n_segments(pool, seg)
    p, j = divmod(k, per)
    sl = slice(j * seg, (j + 1) * seg)
    out = {c: pool[c][sl] for c in PKT_COLS}
    out["ts_us"] = (out["ts_us"].astype(np.int64)
                    + p * int(pool["period_us"])).astype(np.int32)
    return out


def stream_positions(pool: Dict, seg: int, n_calls: int,
                     mask: np.ndarray) -> np.ndarray:
    """Global stream positions, over ``n_calls`` cycled segments, of the
    pool packets selected by ``mask``."""
    per = n_segments(pool, seg)
    idx = np.flatnonzero(mask[:per * seg])
    out = []
    for k in range(n_calls):
        p, j = divmod(k, per)
        lo, hi = np.searchsorted(idx, [j * seg, (j + 1) * seg])
        out.append(idx[lo:hi] + p * per * seg)
    return np.concatenate(out) if out else np.zeros(0, np.int64)


# ---------------------------------------------------------------------------
# pcap writing (Ethernet / IPv4 / TCP or UDP headers, no payload bytes)
# ---------------------------------------------------------------------------

_ETH = np.frombuffer(b"\x02\x00\x00\x00\x00\x01\x02\x00\x00\x00\x00\x02"
                     b"\x08\x00", np.uint8)


def _be16(x: np.ndarray) -> np.ndarray:
    x = np.asarray(x, np.uint32)
    return np.stack([(x >> 8) & 0xFF, x & 0xFF], axis=-1).astype(np.uint8)


def _be32(x: np.ndarray) -> np.ndarray:
    x = np.asarray(x, np.uint64)
    return np.stack([(x >> s) & 0xFF for s in (24, 16, 8, 0)],
                    axis=-1).astype(np.uint8)


def _le32(x: np.ndarray) -> np.ndarray:
    return _be32(x)[:, ::-1]


def write_pcap(cols: Dict[str, np.ndarray], path: str) -> int:
    """Write packet columns as a little-endian microsecond pcap.

    The IP total-length field carries ``pkt_len`` and ``orig_len`` the
    full frame, headers only are captured (snaplen 96), protocol 6 gets a
    20-byte TCP header and anything else an 8-byte UDP header.  Returns
    the number of records."""
    n = len(cols["ts_us"])
    proto = np.asarray(cols["proto"], np.uint32)
    tcp = proto == 6
    plen = np.asarray(cols["pkt_len"], np.uint32) & 0xFFFF
    ip = np.zeros((n, 20), np.uint8)
    ip[:, 0] = 0x45
    ip[:, 2:4] = _be16(plen)
    ip[:, 4:6] = _be16(np.arange(n) & 0xFFFF)
    ip[:, 8] = 64
    ip[:, 9] = proto.astype(np.uint8)
    ip[:, 12:16] = _be32(cols["src_ip"])
    ip[:, 16:20] = _be32(cols["dst_ip"])
    words = ip.reshape(n, 10, 2).astype(np.uint32)
    s = (words[:, :, 0] << 8 | words[:, :, 1]).sum(axis=1)
    while (s >> 16).any():
        s = (s & 0xFFFF) + (s >> 16)
    ip[:, 10:12] = _be16(~s & 0xFFFF)
    l4 = np.zeros((n, 20), np.uint8)
    l4[:, 0:2] = _be16(cols["src_port"])
    l4[:, 2:4] = _be16(cols["dst_port"])
    l4[:, 12] = 5 << 4
    l4[:, 13] = 0x10
    l4[:, 14:16] = _be16(np.full(n, 8192))
    udp_len = np.maximum(plen.astype(np.int64) - 20, 8) & 0xFFFF
    l4[~tcp, 4:6] = _be16(udp_len[~tcp])
    l4[~tcp, 6:8] = 0
    l4_len = np.where(tcp, 20, 8)
    frame_len = 14 + 20 + l4_len
    ts = np.asarray(cols["ts_us"], np.int64)
    rec = np.zeros((n, 16), np.uint8)
    rec[:, 0:4] = _le32(ts // 1_000_000)
    rec[:, 4:8] = _le32(ts % 1_000_000)
    rec[:, 8:12] = _le32(frame_len)
    rec[:, 12:16] = _le32(14 + np.maximum(plen, frame_len - 14))
    full = np.concatenate([rec, np.broadcast_to(_ETH, (n, 14)), ip, l4],
                          axis=1)                          # [n, 70]
    size = 16 + frame_len
    keep = np.arange(70)[None, :] < size[:, None]
    hdr = np.frombuffer(
        np.array([0xA1B2C3D4], "<u4").tobytes()
        + np.array([2, 4], "<u2").tobytes()
        + np.array([0, 0, 96, 1], "<u4").tobytes(), np.uint8)
    tmp = path + ".part"
    with open(tmp, "wb") as f:
        f.write(hdr.tobytes())
        f.write(full[keep].tobytes())
    os.replace(tmp, path)
    return n
