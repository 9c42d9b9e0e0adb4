"""The yardstick's work functions and peaks, and the refusal to run
off the accelerator."""

import io
import json
import os

import pytest

from fenixbench import run, work

CONFIGS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "configs")


@pytest.mark.parametrize("name,factory", [("fenix_cnn", "fenix_cnn"),
                                          ("fenix_rnn", "fenix_rnn")])
def test_macs_match_the_program_today(name, factory):
    from repro.configs import fenix_models
    from repro.core.model_engine.inference import macs_per_inference

    with open(os.path.join(CONFIGS, name + ".json")) as f:
        mcfg = json.load(f)["model"]
    program = macs_per_inference(getattr(fenix_models, factory)())
    assert work.macs_per_inference(mcfg) == program
    assert work.gemm_weight_bytes(mcfg) > 0
    assert work.gemm_act_bytes_per_inference(mcfg) > 0


def test_published_cnn_work():
    with open(os.path.join(CONFIGS, "fenix_cnn.json")) as f:
        mcfg = json.load(f)["model"]
    assert work.macs_per_inference(mcfg) == 1425152
    assert len(work.gemms(mcfg)) == 6


def test_peaks_table():
    pk = work.peaks("TPU v5 lite")
    assert pk["int8_ops"] == 393e12 and pk["bf16_flops"] == 197e12
    assert pk["hbm_bytes_per_s"] == 819e9
    for kind in ("cpu", "TPU v4", "source"):
        with pytest.raises(KeyError):
            work.peaks(kind)


def test_cpu_platform_exits_without_result():
    out, err = io.StringIO(), io.StringIO()
    rc = run.run_cell("cnn_iscx", 1, 1.0, False, out=out, err=err)
    assert rc != 0
    assert out.getvalue() == ""
    assert "needs a TPU" in err.getvalue()
