"""Each cell's whole run (set-up, window, reference comparison, result
line) on the CPU at tiny sizes, with the platform's ``ref`` kernels and
the ``*_tiny`` model widths."""

import io
import json

import pytest

from fenixbench import run

from .conftest import TINY, bench_root

CELLS = sorted(w["name"] for w in run.load_cell("cnn_iscx")[0]
               ["workloads"])
KEYS = {"correct", "attempted", "failed", "metrics", "device", "check"}
# the kernels' names exist only in the compiled TPU kernels, so their
# rooflines have nothing to read on the CPU; XLA:CPU runs most of the
# model's ops on intra-op threads that the trace selector leaves out, so
# the Model Engine's stage time reads nothing there in most runs
TPU_ONLY = {"gate_roofline", "gemm_roofline", "inference_us"}


def cpu_lines(plane, lines):
    if plane != "/host:CPU":
        return []
    return [n for n in lines if n.startswith("tf_XLAPjRtCpuClient")]


def _run(cell, trace, root, seed=2**31 + 5):
    out, err = io.StringIO(), io.StringIO()
    rc = run.run_cell(cell, seed, 0.6, trace, out=out, err=err,
                      require_tpu=False, root=root,
                      overrides=dict(TINY, device_lines=cpu_lines))
    assert rc == 0, err.getvalue()
    res = json.loads(out.getvalue().strip().splitlines()[-1])
    return res, err.getvalue()


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("trace", [0, 1])
def test_cell_rehearsal(cell, trace, tmp_path):
    root = bench_root(tmp_path)
    bench = run.load_cell(cell, root)[0]
    res, err = _run(cell, trace, root)
    assert set(res) == KEYS | ({"breakdown"} if trace else set())
    assert list(res)[-1] == "check"
    assert res["correct"] is True, err
    assert res["failed"] == 0 and res["attempted"] >= 1
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(
        res["device"])
    kind = "per_layer" if trace else "end_to_end"
    want = set(run.cell_metrics(bench, cell, kind))
    if trace:
        want -= TPU_ONLY
        assert res["device"]["busy_s"] > 0
        assert res["device"]["window_s"] > 0
        assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}
    assert want <= set(res["metrics"])
    for m in res["metrics"].values():
        assert set(m) == {"value", "unit"} and m["value"] > 0
    assert res["check"]["judged_events"]["value"] > 0
    assert res["check"]["due_checked"]["value"] > 0
    info = json.loads([ln for ln in err.splitlines()
                       if ln.startswith("fenixbench: ")][-1][12:])
    # warm-up covered every shape: nothing compiles in the window
    assert info["window_compiles"] == 0
    # the compared numbers end stderr, each with its limit
    tail = err.strip().splitlines()[-len(res["check"]):]
    assert all(line.startswith("check ") and " limit " in line
               for line in tail)


def test_stalls():
    lat = [0.04] * 9 + [0.2]
    got = run._stalls(lat)
    assert got["call_ms_median"] == pytest.approx(40.0)
    assert got["call_ms_mean"] == pytest.approx(56.0)
    assert got["call_ms_max"] == pytest.approx(200.0)
    # only the call beyond twice the median counts, by its excess
    assert got["stall_s"] == pytest.approx(0.12)
