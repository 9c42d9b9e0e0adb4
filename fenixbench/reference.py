"""Plain reference of the switch's observable semantics, and the verdict
comparison that decides ``correct``.

What a replay returns is one verdict per packet: the class the flow
table holds for the packet's slot when its batch is processed, or -1.  A
class enters the table only as the answer of the INT8 model for a feature
window that the switch mirrored for a granted packet of that slot.  Which
packets are granted is decided by draws on the chip, so the reference
does not replay admission.  It checks, for a sample of table slots drawn
from the seed, every change of a slot's verdict:

* the first verdict a slot shows after a reset is -1, and a verdict never
  returns to -1;
* a change to class ``c`` at batch ``B_j`` is explained by a packet of the
  slot that could have been granted and answered in between: a packet
  of the slot's previous batch ``B_{j-1}`` (granted there, served in that
  step, delivered at the next batch start), or of an earlier batch whose
  answer could still have been in flight (no batch in between drained the
  Vector I/O FIFO and outlasted the loop latency);
* the gap of that change is the smallest, over those candidates and over
  every feature window the candidate can have held, of how far the
  reference's logit for ``c`` lies below the reference's best logit.  The
  program's INT8 answers are exact integers, so a sound run reads 0.

A candidate's feature window is recomputed from the stream: the slot's
8-entry ring of (pkt_len, ipd) written once per batch that touches the
slot, rotated oldest first, plus the packet's own feature, whose IPD is 0
unless the slot held the packet's own flow hash at batch start.  Where two
packets of one batch write the same slot, which write lands is not
specified, so every write that could have landed is carried as an
alternative.  A window with more than ``MAX_ALTERNATIVES`` alternatives is
not judged and is counted.  Nothing here imports the program.
"""

from __future__ import annotations

import itertools
from typing import Dict, List, Optional

import numpy as np

from fenixbench import models

MAX_ALTERNATIVES = 256
# verdict changes judged per run at most, drawn from the seed
MAX_EVENTS = 1000
# due answers checked per run at most, drawn from the seed
MAX_DUE = 2000
# gap reported for a verdict that no answer can explain (a class where -1
# is due, or -1 after a class): beyond any int32 logit gap
UNEXPLAINED = 2 ** 31


def hash_five_tuple(cols: Dict[str, np.ndarray]) -> np.ndarray:
    """The switch's 32-bit 5-tuple hash (0 is reserved for empty)."""
    u = np.uint32
    with np.errstate(over="ignore"):
        h = cols["src_ip"].astype(u) * u(0x9E3779B1)
        h ^= cols["dst_ip"].astype(u) * u(0x85EBCA77)
        h ^= cols["src_port"].astype(u) * u(0xC2B2AE3D)
        h ^= cols["dst_port"].astype(u) * u(0x27D4EB2F)
        h ^= cols["proto"].astype(u) * u(0x165667B1)
        h ^= h >> u(15)
        h *= u(0x2545F491)
        h ^= h >> u(13)
    return np.maximum(h, u(1))


def batch_drains(first_ts: np.ndarray, last_ts: np.ndarray,
                 next_last_ts: np.ndarray, sw: Dict) -> np.ndarray:
    """Per batch: the step's service budget covers a full FIFO and the
    next batch ends at least one loop latency later, so every answer
    granted up to this batch is on the table by the next batch."""
    span = np.maximum(last_ts.astype(np.int64) - first_ts, 1)
    rate = np.float32(min(sw["fpga_hz"], sw["link_bw_bytes"]
                          / max(sw["feat_bytes"], 1)) / 1e6)
    budget = np.clip(np.floor(span.astype(np.float32) * rate), 1,
                     sw["queue_len"])
    return (budget >= sw["queue_len"]) & (
        next_last_ts.astype(np.int64) - last_ts >= sw["loop_latency_us"])


def _windows(batch, ts, plen, h, ring_depth: int) -> List[Optional[tuple]]:
    """Feature-window alternatives of every packet of one slot, in stream
    order, from a freshly reset table: per packet the ring's entries
    oldest first and the packet's own feature, each a set of what it can
    hold (None where the windows number over ``MAX_ALTERNATIVES``)."""
    ring = [{(0, 0)} for _ in range(ring_depth)]
    idx = 0
    last_ts = {0}
    hashes = {0}
    out: List[Optional[tuple]] = []
    n = len(batch)
    i = 0
    while i < n:
        j = i
        while j < n and batch[j] == batch[i]:
            j += 1
        written = set()
        seq = tuple(frozenset(ring[(idx + r) % ring_depth])
                    for r in range(ring_depth))
        n_seq = int(np.prod([len(s) for s in seq]))
        for k in range(i, j):
            feats = set()
            for stored in hashes:
                if stored != 0 and stored == int(h[k]):
                    feats |= {(int(plen[k]), max(int(ts[k]) - lt, 0))
                              for lt in last_ts}
                else:
                    feats.add((int(plen[k]), 0))
            written |= feats
            out.append(None if n_seq * len(feats) > MAX_ALTERNATIVES
                       else (seq, frozenset(feats)))
        ring[idx] = written
        idx = (idx + 1) % ring_depth
        last_ts = {int(t) for t in ts[i:j]}
        hashes = {int(x) for x in h[i:j]}
        i = j
    return out


def _expand(alt: tuple) -> list:
    """Every window a packet's ring and feature sets can form."""
    seq, feats = alt
    return [list(s) + [f] for s in itertools.product(*seq) for f in feats]


def judge(slots: List[Dict[str, np.ndarray]], drains: np.ndarray,
          qp: Dict, mcfg: Dict, rng: np.random.Generator,
          ring_depth: int = 8, control: bool = False) -> Dict:
    """Compare every verdict change of the sampled slots.

    ``slots``: one dict per (slot, reset epoch) with the slot's packets in
    stream order: ``batch`` (global batch index), ``ts``, ``pkt_len``,
    ``hash``, ``verdict`` and ``answered`` (``admission.replay``'s flag).
    ``drains``: ``batch_drains`` over every global batch index.

    At most ``MAX_EVENTS`` changes, drawn with ``rng``, are judged.
    Returns the program's widest gap (``verdict_gap``) and counts; with
    ``control``, also the 4-bit control's widest gap over the same
    candidate windows (``control_gap``): the gap of the class that the
    4-bit model puts first.

    Also the answers that are due: where the admission reference answers
    a packet of a batch that drains after one that drained (so its FIFO
    started empty), and every packet of the slot in that batch is of one
    flow, the slot's next verdict is the class the reference gives the
    last answered packet's window (one of them, where the window has
    alternatives).  Of at most ``MAX_DUE`` such answers, drawn with
    ``rng``, ``due_missed_pct`` is the share whose verdict is another."""
    n_b = len(drains)
    # nxt[g]: the first batch at or after g that drains
    nxt = np.minimum.accumulate(
        np.where(drains, np.arange(n_b), n_b)[::-1])[::-1]
    events = []          # (class, [window alternatives per candidate])
    due = []             # (window alternatives, the next verdict)
    n_events = n_skipped = n_unexplained = n_due_skipped = 0
    for sl in slots:
        b = sl["batch"]
        alts = _windows(b, sl["ts"], sl["pkt_len"], sl["hash"], ring_depth)
        starts = np.flatnonzero(np.r_[True, b[1:] != b[:-1]])
        ends = np.r_[starts[1:], len(b)]
        for gi in range(len(starts) - 1):
            lo, hi, bj = starts[gi], ends[gi], b[starts[gi]]
            ans = np.flatnonzero(sl["answered"][lo:hi])
            if (not len(ans) or not drains[bj]
                    or (bj and not drains[bj - 1])
                    or (sl["hash"][lo:hi] != sl["hash"][lo]).any()):
                continue
            if alts[lo + ans[-1]] is None:
                n_due_skipped += 1
                continue
            due.append((alts[lo + ans[-1]],
                        int(sl["verdict"][starts[gi + 1]])))
        prev_v = None
        for gi, (lo, hi) in enumerate(zip(starts, ends)):
            v = sl["verdict"][lo:hi]
            if (v != v[0]).any():
                n_unexplained += 1
                prev_v = None
                continue
            v = int(v[0])
            if gi == 0:
                if v != -1:
                    n_unexplained += 1
                prev_v = v
                continue
            if prev_v is None or v == prev_v:
                prev_v = v
                continue
            prev_v = v
            if v < 0:
                n_unexplained += 1
                continue
            n_events += 1
            b_prev = b[starts[gi - 1]]
            cand = list(range(starts[gi - 1], ends[gi - 1]))
            for g2 in range(gi - 2, -1, -1):
                if nxt[b[starts[g2]]] < b_prev:
                    break
                cand.extend(range(starts[g2], ends[g2]))
            if any(alts[k] is None for k in cand):
                n_skipped += 1
                continue
            events.append((v, [alts[k] for k in cand]))
    if len(events) > MAX_EVENTS:
        keep = rng.choice(len(events), MAX_EVENTS, replace=False)
        events = [events[i] for i in np.sort(keep)]
    n_due = len(due)
    if len(due) > MAX_DUE:
        keep = rng.choice(len(due), MAX_DUE, replace=False)
        due = [due[i] for i in np.sort(keep)]
    windows: List[list] = []
    judged = []          # (class, ids of its windows)
    for c, cands in events:
        flat = [w for a in cands for w in _expand(a)]
        judged.append((c, range(len(windows), len(windows) + len(flat))))
        windows.extend(flat)
    checked = []         # (the next verdict, ids of the answer's windows)
    for alt, v in due:
        flat = _expand(alt)
        checked.append((v, range(len(windows), len(windows) + len(flat))))
        windows.extend(flat)
    out = {"events": n_events, "judged": len(judged),
           "skipped": n_skipped, "unexplained": n_unexplained,
           "due": n_due, "due_checked": len(checked),
           "due_skipped": n_due_skipped, "windows": len(windows)}
    gap = UNEXPLAINED if n_unexplained else 0
    if windows:
        pay = np.asarray(windows, np.int64)
        logits = models.classify(qp, mcfg, pay)
        best = logits.max(axis=1)
        cls = logits.argmax(axis=1)
        for c, ids in judged:
            ids = np.asarray(ids)
            gap = max(gap, (best[ids] - logits[ids, c]).min().item())
        missed = sum(v not in set(cls[np.asarray(ids)].tolist())
                     for v, ids in checked)
        out["due_missed_pct"] = 100.0 * missed / max(len(checked), 1)
        if control and judged:
            ids = np.concatenate([np.asarray(i) for _, i in judged])
            low = models.classify(qp, mcfg, pay[ids], bits=4)
            out["control_gap"] = (best[ids] - logits[ids, low.argmax(
                axis=1)]).max().item()
    out["verdict_gap"] = gap
    out.setdefault("due_missed_pct", 0.0)
    return out
