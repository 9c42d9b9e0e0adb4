"""Readings that the limits of ``correct`` are set from.

    python3 -m fenixbench.control --workload cnn_iscx --seconds 3 \
        --seeds 11,12,13 --control-seeds 11,12,13

For each of ``--seeds`` it runs the cell as the benchmark does (a
window of ``--seconds``) and prints the program's reading of every
compared number, plus the 4-bit control's gap over the same judged
windows (the gap of the class the 4-bit model puts first).  For each of
``--control-seeds`` it runs the cell with the 4-bit reference put in the
program's place (``ControlModel``) and prints what the check reads.
One JSON line per run.  The benchmark's own runs never run this.

For each of ``--fault-seeds`` it runs the cell once with each planted
fault of ``FAULTS``.  ``AlteredModel`` (``--altered-seeds``) is the fault
"an answer altered where it is produced": the program's own class plus
one.  ``half_lanes`` is "half of the batch left out": every dequeued lane
leaves the Vector I/O FIFO and half are served; ``half_answers`` loses
half of the served answers before the delay line; ``half_delivery``
loses the answers for odd table slots where the delay line delivers
them; ``gate_half`` halves every admission probability.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import sys

import jax.numpy as jnp
import numpy as np

from fenixbench import models
from fenixbench import run


class ControlModel:
    """The reference model computed on the 4-bit grid, served in the
    program's place through a host callback."""

    def __init__(self, qp, mcfg):
        self.qp, self.mcfg = qp, mcfg
        self.num_classes = mcfg["num_classes"]

    def _host(self, payload):
        logits = models.classify(self.qp, self.mcfg, np.asarray(payload),
                                 bits=4)
        return logits.argmax(axis=1).astype(np.int32)

    def infer(self, payload):
        import jax
        import jax.numpy as jnp

        return jax.pure_callback(
            self._host, jax.ShapeDtypeStruct(payload.shape[:1], jnp.int32),
            payload)


class AlteredModel:
    """The program's model with every answer moved to the next class."""

    def __init__(self, inner):
        self.inner = inner
        self.num_classes = inner.num_classes

    def infer(self, payload):
        return (self.inner.infer(payload) + 1) % self.num_classes


def _half_lanes(dequeue):
    """Vector I/O fault: every lane leaves the FIFO, half are served."""
    def patched(q, cfg, budget):
        q, slots, hashes, feats, count = dequeue(q, cfg, budget)
        return q, slots, hashes, feats, count // 2
    return patched


def _half_answers(push):
    """Model Engine fault: half of the served answers reach the delay
    line."""
    def patched(dline, deliver_ts, slots, hashes, cls, count, *a, **k):
        return push(dline, deliver_ts, slots, hashes, cls, count // 2,
                    *a, **k)
    return patched


def _half_delivery(deliver):
    """Delivery fault: answers for odd table slots never land."""
    def patched(state, dline, now, n_slots):
        new, dline = deliver(state, dline, now, n_slots)
        odd = (jnp.arange(n_slots) % 2) == 1
        return dict(new, cls=jnp.where(odd, state["cls"], new["cls"])), dline
    return patched


def _gate_half(admit):
    """Admission fault: the gate reads every probability at half."""
    def patched(state, *a, **k):
        return admit(dict(state, lut=state["lut"] // 2), *a, **k)
    return patched


# program functions the planted faults wrap: (module, name, wrapper)
FAULTS = {
    "half_lanes": ("repro.core.model_engine.vector_io", "dequeue_device",
                   _half_lanes),
    "half_answers": ("repro.core.model_engine.delay_line", "push",
                     _half_answers),
    "half_delivery": ("repro.core.model_engine.delay_line", "deliver",
                      _half_delivery),
    "gate_half": ("repro.core.data_engine.rate_limiter", "admit_batch",
                  _gate_half),
}
KINDS = ("control", "altered") + tuple(FAULTS)


@contextlib.contextmanager
def serving(kind: str):
    """Within the block, systems the harness builds run with ``kind``
    planted: ``"control"`` (the 4-bit reference in the model's place),
    ``"altered"`` (every answer moved by one class), or one of
    ``FAULTS``."""
    import importlib

    build = run._build_system

    def patched(cfg, qp):
        system = build(cfg, qp)
        if kind == "control":
            system.model = ControlModel(qp, cfg["model"])
        elif kind == "altered":
            system.model = AlteredModel(system.model)
        return system

    import jax

    # the program's module-level jits keep their traces across systems
    jax.clear_caches()
    mod = attr = orig = None
    if kind in FAULTS:
        mod_name, attr, wrap = FAULTS[kind]
        mod = importlib.import_module(mod_name)
        orig = getattr(mod, attr)
        setattr(mod, attr, wrap(orig))
    elif kind not in KINDS:
        raise ValueError(f"unknown kind {kind!r}")
    run._build_system = patched
    try:
        yield
    finally:
        run._build_system = build
        if mod is not None:
            setattr(mod, attr, orig)
        jax.clear_caches()


def reading(m, seed: int, kind: str) -> dict:
    return {"seed": seed, "kind": kind,
            "correct": m["result"]["correct"], **m["check"],
            "control_gap": m["info"]["control_gap"],
            "ref_granted": m["info"]["ref_granted"],
            "served_per_step": m["info"]["served_per_step"],
            "judged": m["info"]["judged"], "events": m["info"]["events"],
            "due_checked": m["info"]["due_checked"],
            "skipped": m["info"]["skipped"]}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--altered-seeds", default="")
    ap.add_argument("--fault-seeds", default="",
                    help="seeds for each of " + ", ".join(FAULTS))
    a = ap.parse_args(argv)
    seeds = [int(s) for s in a.seeds.split(",") if s]
    for s in seeds:
        m = run.measure(a.workload, s, a.seconds, False, control=True)
        print(json.dumps(reading(m, s, "program")), flush=True)
    for kind, arg in ((("control", a.control_seeds),
                       ("altered", a.altered_seeds))
                      + tuple((f, a.fault_seeds) for f in FAULTS)):
        for s in [int(x) for x in arg.split(",") if x]:
            with serving(kind):
                m = run.measure(a.workload, s, a.seconds, False,
                                control=True)
            print(json.dumps(reading(m, s, kind)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
