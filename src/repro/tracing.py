"""Names for the FENIX replay's device stages and host phases.

Three things, each cheap enough to stay on:

* **Stage scopes.**  :func:`scope` wraps one stage of a scan step in
  ``jax.named_scope("fenix_<stage>")``, so every HLO op the stage lowers to
  (the Pallas custom calls included) carries the stage in its ``op_name``
  metadata.  :func:`stage_of_ops` reads that metadata back from the
  compiled single-pipe scan and step programs, lazily, as
  ``{HLO instruction name: stage}``; a profiler trace names its device ops
  by those instruction names.
* **Host spans.**  :func:`span` opens a ``jax.profiler.TraceAnnotation``
  (so the span lands on the device trace's clock whenever a profiler runs)
  and appends ``(name, call_id, parent, t0_ns, t1_ns, attrs)`` to a bounded
  in-memory ring, read by :func:`spans`.  A span opened outside any other
  starts a call: the ``fenix.run_trace`` span of each ``run_trace`` call,
  whose attributes carry the call's counters.  Times are ``time.time_ns``,
  the clock the profiler stamps its events with.
* **Compile counter.**  :func:`compile_log` registers one ``jax.monitoring``
  listener per process and counts backend compiles, their seconds, and
  persistent-cache hits and misses.
"""

from __future__ import annotations

import collections
import contextlib
import dataclasses
import itertools
import re
import threading
import time
from typing import Dict, Iterator, List, NamedTuple, Optional, Tuple

import jax

STAGES = ("deliver", "flow_table", "sort_segment", "gate", "feature_ring",
          "vio_enqueue", "vio_dequeue", "infer", "delay_push",
          "control_plane")
SCOPE_PREFIX = "fenix_"
OTHER = "other"
# spans kept: a few per call, so thousands cover any traced window
RING_SPANS = 8192


def scope(stage: str):
    """``jax.named_scope`` of one scan stage (one of :data:`STAGES`)."""
    if stage not in STAGES:
        raise ValueError(f"unknown stage {stage!r}; one of {STAGES}")
    return jax.named_scope(SCOPE_PREFIX + stage)


# -- host spans ---------------------------------------------------------------

class Span(NamedTuple):
    name: str
    call_id: int
    parent: Optional[str]
    t0_ns: int
    t1_ns: int
    attrs: Dict


class Parent(NamedTuple):
    """An open span, as a parent for spans opened on another thread."""
    name: str
    call_id: int


_ring: collections.deque = collections.deque(maxlen=RING_SPANS)
_calls = itertools.count(1)
_local = threading.local()


def _stack() -> List[Parent]:
    st = getattr(_local, "stack", None)
    if st is None:
        st = _local.stack = []
    return st


def current() -> Optional[Parent]:
    """The innermost span open on this thread, or None."""
    st = _stack()
    return st[-1] if st else None


@contextlib.contextmanager
def span(name: str, parent: Optional[Parent] = None,
         **attrs) -> Iterator[Dict]:
    """Time the enclosed block as span ``name``; yields its attribute dict,
    which the block may fill in.  ``parent`` (from :func:`current`) places
    a span opened on another thread; by default the parent is the
    innermost span open on this thread, and a span with none starts a new
    call."""
    st = _stack()
    if parent is None and st:
        parent = st[-1]
    call_id = parent.call_id if parent is not None else next(_calls)
    st.append(Parent(name, call_id))
    t0 = time.time_ns()
    try:
        with jax.profiler.TraceAnnotation(name):
            yield attrs
    finally:
        t1 = time.time_ns()
        st.pop()
        _ring.append(Span(name, call_id,
                          parent.name if parent is not None else None,
                          t0, t1, attrs))


def spans() -> List[Span]:
    """The ring's spans, oldest first (a span enters when it closes)."""
    return list(_ring)


# -- compile counter ----------------------------------------------------------

@dataclasses.dataclass
class CompileLog:
    """Backend compiles (count and seconds) and persistent-cache hits and
    misses since the listener was registered."""
    compiles: int = 0
    seconds: float = 0.0
    hits: int = 0
    misses: int = 0

    def _duration(self, name, secs, **_):
        if name == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
            self.seconds += secs

    def _event(self, name, **_):
        if name == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif name == "/jax/compilation_cache/cache_misses":
            self.misses += 1


_compile_log: Optional[CompileLog] = None
_compile_lock = threading.Lock()


def compile_log() -> CompileLog:
    """The process's compile counter, registered on first use."""
    global _compile_log
    with _compile_lock:
        if _compile_log is None:
            log = CompileLog()
            jax.monitoring.register_event_duration_secs_listener(
                log._duration)
            jax.monitoring.register_event_listener(log._event)
            _compile_log = log
        return _compile_log


# -- stage map ----------------------------------------------------------------

# kind -> [jitted function, shape key, abstract arguments, stage map or
# None] of the newest program of that kind a system ran
_programs: Dict[str, list] = {}
# "scan" last, so its names win where the two programs share one
_PROGRAM_KINDS = ("step", "scan")


def note_program(kind: str, fn, key, *args) -> None:
    """Remember the arguments' shapes of a call of jitted ``fn`` (a
    ``"scan"`` or ``"step"`` program), once per ``key``, for
    :func:`stage_of_ops`."""
    old = _programs.get(kind)
    if old is not None and old[0] is fn and old[1] == key:
        return
    _programs[kind] = [fn, key, jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                       weak_type=x.weak_type), args), None]


def stage_of_ops() -> Dict[str, str]:
    """``{HLO instruction name: stage}`` of the single-pipe scan and step
    programs the newest system ran (``"other"`` for ops outside every
    stage scope).  Built when first asked for: lowering the noted shapes
    again finds the executable the call compiled."""
    out: Dict[str, str] = {}
    for kind in _PROGRAM_KINDS:
        prog = _programs.get(kind)
        if prog is None:
            continue
        if prog[3] is None:
            fn, _, args, _ = prog
            prog[3] = stages_in_hlo(fn.lower(*args).compile().as_text())
        out.update(prog[3])
    return out


_COMP = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s.*\{\s*$")
_INSTR = re.compile(r"^\s*(ROOT\s+)?%?([\w.\-]+)\s*=\s")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"calls=%?([\w.\-]+)")
_REF = re.compile(r"%([\w.\-]+)")
_STAGE = re.compile(SCOPE_PREFIX + "(" + "|".join(STAGES) + r")(?![a-z_])")


def _scope_of(line: str) -> Tuple[Optional[str], bool]:
    """(innermost stage scope of the instruction's ``op_name``, whether
    the compiler made the instruction: no ``op_name``, or one that lost
    its name stack)."""
    md = _OP_NAME.search(line)
    if md is None:
        return None, True
    found = _STAGE.findall(md.group(1))
    return (found[-1] if found else None), "/" not in md.group(1)


def stages_in_hlo(text: str) -> Dict[str, str]:
    """``{instruction name: stage}`` for every instruction of a compiled
    HLO module's text.

    An instruction takes the innermost ``fenix_`` scope of its
    ``op_name``; a fusion takes its root's (for a tuple root, the first
    element that has one), else its own.  An instruction the compiler
    made (a layout copy or relayout, an async copy, a decomposed scan)
    takes the stage most of its users have, else most of its operands,
    looking through other such instructions; whatever is left is
    ``"other"``."""
    scope: Dict[str, Optional[str]] = {}
    made: Dict[str, bool] = {}
    operands: Dict[str, List[str]] = {}
    root: Dict[str, str] = {}               # computation -> root name
    calls: Dict[str, str] = {}              # fusion -> fused computation
    tuples = set()
    comp = None
    for line in text.splitlines():
        m = _INSTR.match(line)
        if m is None:
            c = _COMP.match(line)
            if c is not None:
                comp = c.group(1)
            continue
        name = m.group(2)
        scope[name], made[name] = _scope_of(line)
        body = line[m.end():].split(", metadata=")[0]
        operands[name] = _REF.findall(body.split("backend_config=")[0])
        if m.group(1) and comp is not None:
            root[comp] = name
        if " tuple(" in body:
            tuples.add(name)
        cm = _CALLS.search(body) if " fusion(" in body else None
        if cm is not None:
            calls[name] = cm.group(1)
    for name, comp_name in calls.items():
        r = root.get(comp_name)
        inner = [] if r is None else [r] + (operands[r] if r in tuples
                                            else [])
        st = next((scope[i] for i in inner if scope.get(i)), None)
        if st is not None:
            scope[name], made[name] = st, False
    users: Dict[str, List[str]] = {}
    for name, ops in operands.items():
        for o in ops:
            if o in scope:
                users.setdefault(o, []).append(name)

    def inherit(name: str, edges: Dict[str, List[str]]) -> Optional[str]:
        seen, todo, votes = {name}, list(edges.get(name, ())), {}
        while todo:
            n = todo.pop()
            if n in seen:
                continue
            seen.add(n)
            if scope.get(n):
                votes[scope[n]] = votes.get(scope[n], 0) + 1
            elif made.get(n):
                todo.extend(edges.get(n, ()))
        return max(votes, key=votes.get) if votes else None

    out = {}
    for name, st in scope.items():
        if st is None and made[name]:
            st = inherit(name, users) or inherit(name, operands)
        out[name] = st or OTHER
    return out
