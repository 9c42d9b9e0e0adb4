"""INT8 quantization + serving-model properties (ISSUE 6):

* quantize -> dequantize round-trip error bounded by half a grid step
  (hypothesis sweep over shifts and value ranges),
* the int8 GEMM backends are bit-identical on random shapes through the
  public dispatch surface (``backend=`` names and the platform default),
* a trained-and-quantized model stays within a fixed accuracy delta of
  its float parent on the synthetic fixture corpus, and the quantized
  checkpoint round-trips through ``save_quantized``/``load_quantized``,
* the RNN cell's table lookup returns ``T[i]`` for every index without a
  gather, and the integer RNN equals a numpy forward that indexes it.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.model_engine import serving
from repro.kernels.int8_matmul import ops
from repro.models import traffic
from repro.configs.fenix_models import fenix_rnn_tiny
from repro.quant.quantize import (dequantize_array, int8_apply, lut_lookup,
                                  quantize_array, quantize_traffic,
                                  tanh_table)
from repro.quant.ref import int8_rnn_ref

# ---------------------------------------------------------------------------
# quantize -> dequantize round trip
# ---------------------------------------------------------------------------


@settings(max_examples=20, deadline=None)
@given(shift=st.integers(-4, 12), scale_exp=st.integers(-6, 6),
       seed=st.integers(0, 1000))
def test_quantize_dequantize_bounded_error(shift, scale_exp, seed):
    """|dequantize(quantize(x)) - x| <= 2^-(shift+1) — half a grid step —
    for every x inside the int8-representable range at that shift."""
    rng = np.random.default_rng(seed)
    lim = 127.0 * 2.0 ** -shift
    x = rng.uniform(-lim, lim, 64) * min(2.0 ** scale_exp, 1.0)
    err = np.abs(dequantize_array(quantize_array(x, shift), shift) - x)
    assert err.max() <= 2.0 ** -(shift + 1) + 1e-12


def test_quantize_saturates():
    """Out-of-range values clip to +-127 on the grid, never wrap."""
    x = np.asarray([-1e9, -300.0, 300.0, 1e9])
    q = quantize_array(x, 0)
    assert q.dtype == np.int8
    assert (q == np.asarray([-127, -127, 127, 127])).all()


def test_quantize_int32_grid():
    """Biases quantize onto the int32 accumulator grid losslessly for
    values far beyond int8 range."""
    x = np.asarray([-1000.5, 0.25, 12345.0])
    q = quantize_array(x, 4, np.int32)
    assert q.dtype == np.int32
    np.testing.assert_allclose(dequantize_array(q, 4), x, atol=2.0 ** -5)


# ---------------------------------------------------------------------------
# GEMM backend dispatch
# ---------------------------------------------------------------------------


@settings(max_examples=10, deadline=None)
@given(m=st.integers(1, 80), k=st.integers(1, 80), n=st.integers(1, 80),
       shift=st.sampled_from([None, 3, 7]), seed=st.integers(0, 10 ** 6))
def test_int8_matmul_ref_equals_pallas(m, k, n, shift, seed):
    rng = np.random.default_rng(seed)
    a = jnp.asarray(rng.integers(-127, 128, (m, k)), jnp.int8)
    b = jnp.asarray(rng.integers(-127, 128, (k, n)), jnp.int8)
    bias = jnp.asarray(rng.integers(-500, 500, (n,)), jnp.int32)
    ref = ops.int8_matmul(a, b, bias, shift, backend="ref")
    pal = ops.int8_matmul(a, b, bias, shift, backend="pallas")
    assert ref.dtype == pal.dtype
    assert bool(jnp.all(ref == pal))


def test_backend_validation():
    from repro.kernels.backend import resolve

    with pytest.raises(ValueError, match="matmul_backend"):
        ops.int8_matmul(jnp.zeros((2, 2), jnp.int8),
                        jnp.zeros((2, 2), jnp.int8), backend="mxu")
    assert resolve("pallas", "matmul_backend") == "pallas"


def test_default_backend_follows_platform(monkeypatch):
    """backend=None is "ref" off a TPU and the compiled kernel on one."""
    from repro.kernels import backend as kernel_backend

    rng = np.random.default_rng(0)
    a = jnp.asarray(rng.integers(-127, 128, (5, 9)), jnp.int8)
    b = jnp.asarray(rng.integers(-127, 128, (9, 7)), jnp.int8)
    want = ops.int8_matmul(a, b, backend="ref")
    assert kernel_backend.resolve(None, "matmul_backend") == "ref"
    assert bool(jnp.all(ops.int8_matmul(a, b) == want))
    monkeypatch.setattr(kernel_backend, "on_tpu", lambda: True)
    assert kernel_backend.resolve(None, "matmul_backend") == "pallas_tpu"


# ---------------------------------------------------------------------------
# quantized model vs float parent on the fixture corpus
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def trained():
    """One trained tiny model per module: float params + quantized model
    + the eval split, off the pcap-ingested synthetic fixture corpus."""
    from repro.data.synthetic_traffic import windows_from_flows

    mcfg = serving.model_config("int8_cnn_tiny")
    flows = serving.synthetic_corpus(n_flows=160, seed=5)
    params, qp, _ = serving.train_quantized(mcfg, flows, steps=600, seed=5)
    x, y, _ = windows_from_flows(flows, seed=99)
    return mcfg, params, qp, x[:512], y[:512]


def test_quantized_accuracy_within_delta_of_float(trained):
    """Post-training INT8 quantization costs at most 5 macro-F1 points
    on the fixture corpus (the paper reports ~0.5% top-1 loss, §6)."""
    from repro.baselines.common import macro_f1

    mcfg, params, qp, x, y = trained
    pf = np.asarray(jnp.argmax(
        traffic.apply(params, mcfg, jnp.asarray(x)), -1))
    f1_float = macro_f1(y, pf, mcfg.num_classes)
    res = serving.evaluate_quantized(qp, mcfg, x, y)
    assert f1_float > 0.6          # the float model actually learned
    assert res["macro_f1"] >= f1_float - 0.05
    cm = np.asarray(res["confusion"])
    assert cm.shape == (mcfg.num_classes, mcfg.num_classes)
    assert cm.sum() == len(y)


def test_quantized_eval_backend_invariant(trained):
    """evaluate_quantized on the pallas backend returns the identical
    confusion matrix (int8_apply is bit-identical across backends)."""
    mcfg, _, qp, x, y = trained
    r_ref = serving.evaluate_quantized(qp, mcfg, x[:64], y[:64], "ref")
    r_pal = serving.evaluate_quantized(qp, mcfg, x[:64], y[:64], "pallas")
    assert r_ref["confusion"] == r_pal["confusion"]
    assert (r_ref["pred"] == r_pal["pred"]).all()


def test_quantized_checkpoint_round_trip(tmp_path, trained):
    """save_quantized -> load_quantized -> identical logits, and
    build_model(model_dir=...) serves the restored weights."""
    mcfg, _, qp, x, _ = trained
    d = str(tmp_path / "ckpt")
    serving.save_quantized(d, qp, mcfg)
    qp2, mcfg2 = serving.load_quantized(d)
    assert mcfg2 == mcfg
    xj = jnp.asarray(x[:32])
    assert bool(jnp.all(int8_apply(qp, mcfg, xj)
                        == int8_apply(qp2, mcfg2, xj)))
    m = serving.build_model("int8_cnn_tiny", model_dir=d)
    assert m.num_classes == mcfg.num_classes
    assert bool(jnp.all(m.infer(xj)
                        == jnp.argmax(int8_apply(qp, mcfg, xj), -1)))


def test_load_quantized_missing_dir(tmp_path):
    with pytest.raises(FileNotFoundError):
        serving.load_quantized(str(tmp_path / "nope"))


def test_build_model_validation():
    with pytest.raises(ValueError, match="bylen"):
        serving.build_model("bylen", matmul_backend="ref")
    with pytest.raises(ValueError, match="unknown model"):
        serving.build_model("int8_transformer")
    with pytest.raises(ValueError, match="matmul_backend"):
        serving.build_model("int8_cnn_tiny", matmul_backend="mxu")


def test_fenix_config_backend_overrides_model_object(trained):
    """FenixConfig(matmul_backend=...) rewrites the backend of an
    explicitly passed EngineModel, so one config knob flips a whole
    conformance run."""
    from repro.core.fenix import FenixConfig, FenixSystem
    from repro.core.model_engine.inference import ByLenModel, EngineModel

    mcfg, _, qp, _, _ = trained
    model = EngineModel(mcfg, qp, backend="ref")
    sys_ = FenixSystem(FenixConfig(matmul_backend="pallas"), model)
    assert sys_.model.backend == "pallas"
    assert dataclasses.replace(sys_.model, backend="ref") == model
    with pytest.raises(ValueError, match="EngineModel"):
        FenixSystem(FenixConfig(matmul_backend="pallas"), ByLenModel())


# ---------------------------------------------------------------------------
# the RNN cell's tanh table lookup
# ---------------------------------------------------------------------------

IDX = jnp.arange(512, dtype=jnp.int32)


def _lookup(table: np.ndarray):
    """(every entry looked up, the lookup's jaxpr as text)."""
    fn = lut_lookup(jnp.asarray(table, jnp.int8))
    return np.asarray(fn(IDX)), str(jax.make_jaxpr(fn)(IDX))


@pytest.mark.parametrize("sh", range(3, 13))
def test_lut_lookup_exact_on_tanh_tables(sh):
    """The table ``quantize_traffic`` builds at output shift ``sh`` (6-12
    are those a calibration can give, |tanh| <= 1) reads back entry for
    entry, without a gather."""
    table = tanh_table(sh)
    got, jaxpr = _lookup(table)
    assert got.dtype == np.int8
    np.testing.assert_array_equal(got, table)
    assert "gather" not in jaxpr


def _tables():
    rng = np.random.default_rng(14)
    yield "flat", np.full(512, 3)
    yield "flat_at_-127", np.full(512, -127)
    yield "one_step_full_range", np.repeat([-127, 127], [300, 212])
    yield "every_value", np.linspace(-127, 127, 512).round()
    for seed in range(4):
        t = np.sort(rng.integers(-127, 128, 512))
        if seed % 2:                      # reach both ends
            t[0], t[-1] = -127, 127
        yield f"random_{seed}", t
    yield "sparse_steps", np.sort(rng.choice([-127, -3, 0, 1, 90, 127], 512))
    dip = tanh_table(6)
    dip[300] = dip[299] - 1
    yield "one_dip", dip
    yield "shuffled", np.random.default_rng(3).permutation(tanh_table(6))


@pytest.mark.parametrize("table", [t for _, t in _tables()],
                         ids=[n for n, _ in _tables()])
def test_lut_lookup_exact_on_any_table(table):
    """Flat, full-range, random non-decreasing, and stepping-down tables
    all read back entry for entry without a gather."""
    got, jaxpr = _lookup(table)
    np.testing.assert_array_equal(got, table)
    assert "gather" not in jaxpr


@pytest.mark.parametrize("backend", ["ref", "pallas"])
def test_int8_rnn_tiny_logits_equal_numpy_table_forward(backend):
    cfg = fenix_rnn_tiny()
    rng = np.random.default_rng(14)
    payload = np.stack([rng.integers(40, 1500, (256, cfg.seq_len)),
                        rng.integers(0, 100_000, (256, cfg.seq_len))],
                       axis=-1).astype(np.int32)
    qp = quantize_traffic(traffic.init(cfg, seed=14), cfg,
                          jnp.asarray(payload[:64]))
    assert int(qp["cell/lut_preshift"]) >= 0
    want, h = int8_rnn_ref(qp, cfg, payload)
    assert len(np.unique(h)) > 20       # many table entries were read
    got = np.asarray(int8_apply(qp, cfg, jnp.asarray(payload),
                                backend=backend))
    np.testing.assert_array_equal(got, want)
