"""Model kinds as modules: the weights, reference logits and work counts
of the paper's two kinds, a kind that does not exist, and a kind added
with new files only, run through ``run.measure`` on the CPU."""

import copy
import hashlib
import json
import os
import sys

import numpy as np
import pytest

from fenixbench import models, run, work

from .conftest import ROOT, TINY, bench_root

CONFIGS = os.path.join(ROOT, "fenixbench", "configs")
SEEDS = (3, 2**33 + 17)
# sha256 of the integer model, and of its logits at 8 and 4 bits, over
# ``_payload`` windows; recorded from the models as they were when both
# kinds lived in one module, so moving code cannot change a bit
DIGESTS = {
    ("cnn", 3): (
        "fb235a961cfad8c15d55e22a8f7b8da060e2f6a69ca63f96de5a95924ccb0c41",
        "8b8d957ba30b7d47df00bc48003c9cc9b5fae724beb6954b3367bc0a82910f7f",
        "dbe54ac081b15ae9a6b53177cf43b67f9ed128c2f72ed3365efa0812f8d01ae1"),
    ("cnn", 2**33 + 17): (
        "46807d1b0559bed9ecdc988425f802a8615ce7693145db79503839e276bc6149",
        "d2b3efe084233e8ae152260f3dc44293652861974500e92dc413664d07cddd39",
        "18668af25ef78727ec2e3b9358b082b5d9f9507d8164a5ee18410e79d43b09e8"),
    ("rnn", 3): (
        "be9dc11b8af35ce5d513bc888678a29be90841532523352258c579e4f9068f3d",
        "1dd860d4a005bb9b3422c6e68caf620958baff39fac0aa1cabff89c26a30edd8",
        "f4a64477474a620213dd236d88c0291d74a7af5ad59af42a40661706b76d3da4"),
    ("rnn", 2**33 + 17): (
        "cccbed84e28fc9bcd80ce96102ce4c8e5b86c4aa796896211904c157c7e5393e",
        "a309559155070ddbb4296d82a07a281c0408eb1b8f1d615425a52b709d8b2a5f",
        "ce5bb9b8391c4b85dddd8b2a9db9f9ba017d450e99752ea4526fa8f917e31640"),
}
# (MACs per inference, GEMM weight bytes, GEMM activation bytes per
# inference, the GEMM calls as (rows, K, N, out_bytes) with repeats)
WORK = {
    "cnn": (1425152, 392960, 11900,
            [(9, 96, 64, 1), (9, 192, 128, 1), (9, 384, 256, 1),
             (1, 256, 512, 1), (1, 512, 256, 1), (1, 256, 7, 4)]),
    "rnn": (185216, 185216, 10812,
            [(1, 32, 128, 4), (1, 128, 128, 4)] * 9 + [(1, 128, 7, 4)]),
}


def _mcfg(kind):
    with open(os.path.join(CONFIGS, f"fenix_{kind}.json")) as f:
        return json.load(f)["model"]


def _payload(seed, n):
    """[n, 9, 2] windows: packet lengths and IPDs over every bucket."""
    rng = np.random.default_rng([seed, 7])
    ln = rng.integers(0, 1600, (n, 9))
    ipd = rng.integers(0, 1 << rng.integers(1, 21, (n, 9)))
    return np.stack([ln, ipd], -1).astype(np.int32)


def _digest(arrays):
    h = hashlib.sha256()
    for k in sorted(arrays):
        a = np.ascontiguousarray(np.asarray(arrays[k]))
        h.update(f"{k}|{a.dtype.str}|{a.shape}|".encode())
        h.update(a.tobytes())
    return h.hexdigest()


@pytest.mark.parametrize("kind,seed", sorted(DIGESTS))
def test_weights_and_logits_are_bit_identical(kind, seed):
    mcfg = _mcfg(kind)
    qp = models.get(kind).make_model(mcfg, seed, _payload(seed, 64))
    pay = _payload(seed + 1, 96)
    got = (_digest(qp),) + tuple(
        _digest({"l": models.classify(qp, mcfg, pay, bits=b, block=40)})
        for b in (8, 4))
    assert got == DIGESTS[kind, seed]


@pytest.mark.parametrize("kind", sorted(WORK))
def test_work_totals_are_unchanged(kind):
    mcfg = _mcfg(kind)
    macs, wbytes, abytes, calls = WORK[kind]
    assert work.gemms(mcfg) == calls
    assert work.macs_per_inference(mcfg) == macs
    assert work.gemm_macs_per_inference(mcfg) == macs
    assert work.gemm_weight_bytes(mcfg) == wbytes
    assert work.gemm_act_bytes_per_inference(mcfg) == abytes
    assert models.get(kind).VERDICT_GAP_LIMIT == 0


def test_unknown_kind_names_the_file_to_add(tmp_path):
    with pytest.raises(ValueError, match="fenixbench/models/nosuch.py"):
        models.get("nosuch")
    # a module of the package that is not a kind lacks the contract
    with pytest.raises(ValueError, match="lacks make_model"):
        models.get("fixed_point")
    with pytest.raises(ValueError, match="not a module name"):
        models.get("../cnn")
    # a run fails at load, before it looks for a device
    with pytest.raises(ValueError, match="fenixbench/models/nosuch.py"):
        run.measure("cnn_iscx", 1, 0.6, False, require_tpu=True,
                    root=bench_root(tmp_path),
                    overrides={"config": {"model": {"kind": "nosuch"}}})


TOY = '''"""A throwaway kind: the RNN's arithmetic under a name of its own."""
from fenixbench.models.rnn import (forward, gemms,  # noqa: F401
                                   macs_per_inference, make_model)

# distinct from the paper kinds' 0, so the run shows whose limit it used
VERDICT_GAP_LIMIT = 1
'''


@pytest.fixture
def toy_kind(tmp_path, monkeypatch):
    """A kind ``toy`` and its cell ``toy_iscx``, as new files only: the
    kind module, its configuration and the entries in a root's
    ``BENCHMARK.json``."""
    kinds = tmp_path / "models"
    kinds.mkdir()
    (kinds / "toy.py").write_text(TOY)
    monkeypatch.setattr(models, "__path__",
                        list(models.__path__) + [str(kinds)])
    bench_dir = tmp_path / "bench"
    (bench_dir / "configs").mkdir(parents=True)
    with open(os.path.join(CONFIGS, "fenix_rnn.json")) as f:
        cfg = json.load(f)
    cfg["name"] = "toy"
    cfg["model"]["kind"] = "toy"
    (bench_dir / "configs" / "toy.json").write_text(json.dumps(cfg))
    monkeypatch.setattr(run, "BENCH_DIR", str(bench_dir))
    root = tmp_path / "root"
    root.mkdir()
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"].append({"name": "toy", "source": "tests",
                             "file": "fenixbench/configs/toy.json",
                             "reduced": [], "why": "plumbing"})
    bench["workloads"].append({"name": "toy_iscx", "config": "toy",
                               "traffic": "iscx_steady", "chips": 1,
                               "why": "plumbing"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    # stands in for the branch that a new kind adds to the program's model
    # factory: the program serves the toy on its RNN path
    serve = run.engine_model
    monkeypatch.setattr(run, "engine_model",
                        lambda mcfg, qp: serve(dict(mcfg, kind="rnn"), qp))
    yield str(root)
    sys.modules.pop(f"{models.__name__}.toy", None)


def test_new_kind_runs_with_new_files_only(toy_kind):
    m = run.measure("toy_iscx", 2**31 + 9, 0.6, False, require_tpu=False,
                    root=toy_kind, overrides=copy.deepcopy(TINY))
    res = m["result"]
    assert res["correct"] is True, res["check"]
    assert res["check"]["verdict_gap"] == {"value": 0, "limit": 1}
    assert m["info"]["judged"] > 0 and m["info"]["due_checked"] > 0
    assert set(res["metrics"]) == {"replay_pps", "setup_s"}
