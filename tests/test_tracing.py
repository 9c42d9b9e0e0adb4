"""``repro.tracing``: the host spans of ``run_trace``, the counters its
``fenix.run_trace`` span carries, the compile counter, and the stage map
read back from the compiled scan (CPU)."""

import re
import threading
import warnings

import jax
import jax.numpy as jnp
import pytest

from repro import tracing
from repro.core.fenix import PKT_KEYS, FenixConfig, FenixSystem, TraceSpec
from repro.core.model_engine import vector_io as vio
from repro.core.model_engine.inference import ByLenModel
from repro.data.synthetic_traffic import make_flows, packet_stream

# an odd batch size no other test compiles, so the first call compiles
_B, _CPE, _N = 96, 4, 1000


@pytest.fixture(scope="module")
def stream():
    return packet_stream(make_flows("iscx", 12, seed=5), limit=_N)


@pytest.fixture(autouse=True)
def no_persistent_cache():
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _system(driver="device", **kw):
    return FenixSystem(FenixConfig(batch_size=_B, control_plane_every=_CPE,
                                   driver=driver, **kw), ByLenModel())


def _calls(before: int):
    """{call_id: [spans]} of the spans that closed after ``before``."""
    out = {}
    for s in tracing.spans()[before:]:
        out.setdefault(s.call_id, []).append(s)
    return out


def _ring_len() -> int:
    return len(tracing.spans())


def test_span_tree_of_device_driver(stream):
    sys_ = _system()
    n0 = _ring_len()
    for _ in range(2):
        sys_.run_trace(dict(stream))
    calls = _calls(n0)
    assert len(calls) == 2
    for spans in calls.values():
        names = [s.name for s in spans]
        # _N is not a whole number of batches: the tail step dispatches
        # and waits once more after the scan
        assert names == ["fenix.stage_in", "fenix.dispatch", "fenix.wait",
                         "fenix.dispatch", "fenix.wait", "fenix.fetch_out",
                         "fenix.run_trace"]
        top = spans[-1]
        assert top.parent is None
        assert all(s.parent == "fenix.run_trace" for s in spans[:-1])
        assert all(top.t0_ns <= s.t0_ns <= s.t1_ns <= top.t1_ns
                   for s in spans[:-1])


@pytest.mark.parametrize("overlap", [True, False])
def test_span_tree_of_double_buffered_trace_spec(stream, overlap):
    sys_ = _system()
    n0 = _ring_len()
    sys_.run_trace(TraceSpec(dict(stream), chunk_pkts=300, overlap=overlap))
    calls = _calls(n0)
    assert len(calls) == 1
    (spans,) = calls.values()
    names = [s.name for s in spans]
    assert names[-1] == "fenix.run_trace" and names[0] == "fenix.stage_in"
    # four 300-packet chunks parse, plus the parse that finds the end
    assert names.count("fenix.parse") == 5
    # under a whole staging block (_CPE * 4 batches): one block of every
    # full batch, then the tail
    assert names.count("fenix.device_put") == 2
    assert names.count("fenix.dispatch") == names.count("fenix.wait") == 2
    # each item and the end-of-stream sentinel
    assert names.count("fenix.ingest_wait") == (3 if overlap else 0)
    assert names.count("fenix.fetch_out") == 1
    # the producer thread's spans belong to the call as well
    assert all(s.parent == "fenix.run_trace" for s in spans[:-1])


@pytest.mark.parametrize("driver", ["host", "device", "pipes", "farm"])
def test_run_trace_counters_equal_stats_deltas(stream, driver):
    kw = {"num_pipes": 2} if driver in ("pipes", "farm") else {}
    if driver == "farm":
        kw["num_engines"] = 2
    with warnings.catch_warnings():
        # fewer devices than pipes: the sharded drivers fall back to vmap
        warnings.simplefilter("ignore", RuntimeWarning)
        sys_ = _system(driver, **kw)
    keys = {"packets": "packets", "granted": "granted",
            "served": "inferences", "fifo_drops": "dropped_q",
            "inflight_drops": "dropped_inflight"}
    half = len(stream["ts_us"]) // 2
    for sl in (slice(0, half), slice(half, None)):
        before = dict(sys_.stats,
                      steps=sum(sys_.stats["engine_q_depth_hist"][0]))
        n0 = _ring_len()
        sys_.run_trace({k: v[sl] for k, v in stream.items()})
        top = tracing.spans()[n0:][-1]
        assert top.name == "fenix.run_trace"
        for attr, stat in keys.items():
            assert top.attrs[attr] == sys_.stats[stat] - before[stat], attr
        steps = sum(sys_.stats["engine_q_depth_hist"][0]) - before["steps"]
        assert top.attrs["steps"] == steps > 0
        lanes = (top.attrs["served"] if driver == "host"
                 else steps * sys_._lanes_per_step)
        assert top.attrs["lanes"] == lanes
        assert top.attrs["packets"] == sl.indices(_N)[1] - sl.indices(_N)[0]


def test_fifo_depth_max_reaches_queue_len_when_grants_drop(stream):
    qlen = 8
    sys_ = _system(io=vio.IOConfig(queue_len=qlen))
    n0 = _ring_len()
    sys_.run_trace(dict(stream))
    top = tracing.spans()[-1]
    assert top.name == "fenix.run_trace" and _ring_len() > n0
    assert top.attrs["fifo_drops"] > 0
    assert top.attrs["fifo_depth_max"] == qlen
    roomy = _system()
    roomy.run_trace(dict(stream))
    top = tracing.spans()[-1]
    assert top.attrs["fifo_drops"] == 0
    assert 0 < top.attrs["fifo_depth_max"] <= roomy.cfg.io.queue_len


def test_compiles_counted_on_first_call_only(stream):
    sys_ = _system()
    sys_.run_trace(dict(stream))
    first = tracing.spans()[-1]
    sys_.run_trace(dict(stream))
    again = tracing.spans()[-1]
    assert first.attrs["compiles"] > 0
    assert again.attrs["compiles"] == 0
    assert tracing.compile_log().seconds > 0


def test_spans_nest_and_start_calls():
    n0 = _ring_len()
    with tracing.span("outer", k=1) as attrs:
        attrs["late"] = 2
        with tracing.span("inner"):
            pass
        parent = tracing.current()

        def remote():
            assert tracing.current() is None
            with tracing.span("remote", parent):
                pass
        t = threading.Thread(target=remote)
        t.start()
        t.join(timeout=10)
        assert not t.is_alive()
    with tracing.span("next"):
        pass
    inner, remote, outer, nxt = tracing.spans()[n0:]
    assert (inner.name, inner.parent) == ("inner", "outer")
    assert (remote.name, remote.parent) == ("remote", "outer")
    assert remote.call_id == outer.call_id
    assert outer.parent is None and outer.attrs == {"k": 1, "late": 2}
    assert inner.call_id == outer.call_id != nxt.call_id
    assert tracing.current() is None


def test_scope_rejects_unknown_stage():
    with pytest.raises(ValueError):
        tracing.scope("parse")


def _scan_text(sys_, stream, steps):
    spec = lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype)  # noqa: E731
    carry = jax.tree.map(spec, (sys_.state, sys_.queues, sys_._dl))
    chunks = {k: jax.ShapeDtypeStruct((steps, _B), stream[k].dtype)
              for k in PKT_KEYS}
    chunks["_cp"] = jax.ShapeDtypeStruct((steps,), jnp.bool_)
    return sys_._scan_jit.lower(carry, chunks).compile().as_text()


def test_stage_of_ops_names_every_op_of_the_compiled_scan(stream):
    sys_ = _system()
    sys_.run_trace(dict(stream))
    c0 = tracing.compile_log().compiles
    stage_of = tracing.stage_of_ops()
    # read back from the executable the call compiled: no new compile
    assert tracing.compile_log().compiles == c0
    text = _scan_text(sys_, stream, _N // _B)
    for stage in tracing.STAGES:
        assert f"/{tracing.SCOPE_PREFIX}{stage}/" in text, stage
    ops = set(re.findall(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s", text,
                         re.MULTILINE))
    assert ops and ops <= set(stage_of)
    assert set(stage_of.values()) == set(tracing.STAGES) | {tracing.OTHER}


def test_stages_in_hlo_rules():
    text = "\n".join([
        "%fused_computation.1 (p: s32[4]) -> s32[4] {",
        '  ROOT %add.1 = s32[4] add(%p, %p), metadata={op_name="jit(f)/'
        'while/body/fenix_gate/add"}',
        "}",
        "%body.2 (q: s32[4]) -> s32[4] {",
        "  %copy.3 = s32[4]{0} copy(%q)",
        "  %fusion.4 = s32[4] fusion(%copy.3), kind=kLoop, "
        'calls=%fused_computation.1, metadata={op_name="jit(f)/while/'
        'body/fenix_infer/mul"}',
        '  %gather.5 = s32[4] gather(%q), metadata={op_name="gather"}',
        '  %x.6 = s32[4] add(%gather.5, %gather.5), metadata={op_name='
        '"jit(f)/fenix_flow_table/fenix_sort_segment/add"}',
        '  ROOT %y.7 = s32[4] add(%q, %q), metadata={op_name="jit(f)/y"}',
        "}",
    ])
    got = tracing.stages_in_hlo(text)
    # a fusion takes its root's scope over its own
    assert got["fusion.4"] == "gate" and got["add.1"] == "gate"
    # compiler-made ops take their users' stage
    assert got["copy.3"] == "gate"
    assert got["gather.5"] == "sort_segment"
    # the innermost scope wins; an op outside every scope is "other"
    assert got["x.6"] == "sort_segment" and got["y.7"] == "other"
