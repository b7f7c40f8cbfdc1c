"""Native C++ deploy path: .pdnative artifact + PJRT runner.

Covers the runner plumbing with a fake PJRT plugin (the reference's
fake-device test pattern) on CPU, and end-to-end numerics on TPU when a real
plugin + device are reachable (ref:paddle/fluid/inference/api/
analysis_predictor_tester.cc is the parity model)."""
import ctypes
import os

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import nn
from paddle_tpu.jit import InputSpec
from paddle_tpu.native import pdnative


class _AddW(nn.Layer):
    """y = x + w: output shape == input shape == weight shape, so the fake
    plugin's echo semantics (output := first argument) are well-typed."""

    def __init__(self):
        super().__init__()
        self.w = self.create_parameter([2, 8])

    def forward(self, x):
        return x + self.w


@pytest.fixture(scope="module")
def artifact(tmp_path_factory):
    d = tmp_path_factory.mktemp("native")
    path = str(d / "addw")
    m = _AddW()
    paddle.jit.save(m, path, input_spec=[InputSpec([2, 8], "float32")])
    w = np.asarray(m.w._data)
    return path, w


def test_pdnative_container_roundtrip(artifact):
    path, w = artifact
    assert os.path.exists(path + ".pdnative")
    art = pdnative.read(path + ".pdnative")
    assert art["stablehlo"][:4] in (b"ML\xefR",)  # MLIR bytecode magic
    assert len(art["compile_options"]) > 0
    kinds = [a.is_weight for a in art["args"]]
    assert kinds.count(True) == 1 and kinds.count(False) == 1
    wspec = next(a for a in art["args"] if a.is_weight)
    assert wspec.shape == (2, 8) and wspec.dtype == np.float32
    np.testing.assert_array_equal(
        np.frombuffer(wspec.data, np.float32).reshape(2, 8), w)
    (out,) = art["outputs"]
    assert out.shape == (2, 8) and out.dtype == np.float32


def test_native_predictor_fake_plugin(artifact):
    path, w = artifact
    plugin = pdnative.build_fake_plugin()
    pred = pdnative.NativePredictor(path + ".pdnative", plugin)
    try:
        assert pred.input_specs == [((2, 8), np.dtype(np.float32))]
        assert pred.output_specs == [((2, 8), np.dtype(np.float32))]
        x = np.random.default_rng(0).standard_normal((2, 8)).astype(np.float32)
        (y,) = pred.run(x)
        # fake plugin echoes argument 0 of the exported main = the weight
        np.testing.assert_array_equal(y, w)
    finally:
        pred.close()


def test_native_predictor_input_validation(artifact):
    path, _ = artifact
    pred = pdnative.NativePredictor(path + ".pdnative",
                                    pdnative.build_fake_plugin())
    try:
        with pytest.raises(ValueError, match="expected 1 inputs"):
            pred.run()
        with pytest.raises(ValueError, match="shape"):
            pred.run(np.zeros((3, 8), np.float32))
    finally:
        pred.close()


def test_create_errors_are_reported(tmp_path, artifact):
    path, _ = artifact
    lib = pdnative._lib()
    # bad artifact
    bad = tmp_path / "bad.pdnative"
    bad.write_bytes(b"NOTMAGIC" + b"\0" * 16)
    h = lib.pt_infer_create(b"/nonexistent.so", str(bad).encode())
    assert not h
    assert b"magic" in lib.pt_infer_last_error()
    # good artifact, bad plugin
    h = lib.pt_infer_create(b"/nonexistent.so",
                            (path + ".pdnative").encode())
    assert not h
    assert b"dlopen" in lib.pt_infer_last_error()


def test_dynamic_spec_skips_pdnative(tmp_path):
    m = nn.Linear(8, 4)
    path = str(tmp_path / "dyn")
    paddle.jit.save(m, path, input_spec=[InputSpec([None, 8], "float32")])
    assert os.path.exists(path + ".pdmodel")
    assert not os.path.exists(path + ".pdnative")
    # an EXPLICIT native request with dynamic dims must fail loudly
    with pytest.raises(ValueError, match="fully-static"):
        paddle.jit.save(m, str(tmp_path / "dyn2"),
                        input_spec=[InputSpec([None, 8], "float32")],
                        native=True)


def _tpu_plugin():
    p = pdnative.default_plugin_path()
    if p is None or not os.path.exists(p):
        return None
    if os.environ.get("PADDLE_TPU_NATIVE_TPU_TEST") != "1":
        return None  # needs a live chip this process does not hold; opt-in
    return p


@pytest.mark.skipif(_tpu_plugin() is None,
                    reason="real PJRT plugin test is opt-in "
                           "(PADDLE_TPU_NATIVE_TPU_TEST=1)")
def test_native_predictor_real_plugin(artifact):
    path, w = artifact
    plugin = _tpu_plugin()
    pred = pdnative.NativePredictor(path + ".pdnative", plugin)
    try:
        x = np.random.default_rng(1).standard_normal((2, 8)).astype(np.float32)
        (y,) = pred.run(x)
        np.testing.assert_allclose(y, x + w, rtol=1e-5, atol=1e-5)
    finally:
        pred.close()


def test_cpp_demo_app(artifact, tmp_path):
    """Compile the C++ demo against libpaddle_tpu_native.so and run it with
    the fake plugin — the full C/C++ deploy recipe, end to end."""
    import subprocess

    from paddle_tpu import native

    path, _ = artifact
    so = native.load()._name  # the exact .so this session built/loaded
    here = os.path.dirname(os.path.abspath(native.__file__))
    demo_src = os.path.join(here, "csrc", "testing", "pt_infer_demo.cc")
    demo = str(tmp_path / "demo")
    subprocess.run(["g++", "-std=c++17", demo_src, so, "-o", demo],
                   check=True, capture_output=True)
    r = subprocess.run([demo, pdnative.build_fake_plugin(),
                        path + ".pdnative"],
                       capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stderr
    assert "ok" in r.stdout and "output 0" in r.stdout


def test_gpt_exports_tpu_pdnative(tmp_path):
    """The flagship model cross-lowers to a TPU-platform deploy artifact
    from a CPU host (jax.export platforms=['tpu'])."""
    from paddle_tpu.models.gpt import GPTForCausalLM, gpt_tiny

    m = GPTForCausalLM(gpt_tiny())
    m.eval()
    path = str(tmp_path / "gpt")
    paddle.jit.save(m, path, input_spec=[InputSpec([2, 16], "int32")])
    art = pdnative.read(path + ".pdnative")
    assert art["platform"] == "tpu"
    assert sum(1 for a in art["args"] if a.is_weight) == len(
        m.state_dict())
    (out,) = art["outputs"]
    assert out.shape == (2, 16, m.cfg.vocab_size)


def test_create_options_reach_plugin(artifact, tmp_path, monkeypatch):
    """create_options must arrive at PJRT_Client_Create as typed
    NamedValues, with the PYTHON type deciding the NamedValue type — a
    digit-only string option must stay kString (a plugin may reject
    mistyped values)."""
    path, _ = artifact
    dump = tmp_path / "opts.txt"
    monkeypatch.setenv("FAKE_PJRT_DUMP_OPTIONS", str(dump))
    pred = pdnative.NativePredictor(
        path + ".pdnative", pdnative.build_fake_plugin(),
        create_options={"local_only": True, "topology": "v5e:1x1x1",
                        "rank": 0xFFFF_FFFF, "session_id": "12345"})
    pred.close()
    got = dict(l.split("=", 1) for l in dump.read_text().splitlines())
    assert got["local_only"] == "i:1"
    assert got["topology"] == "s:v5e:1x1x1"
    assert got["rank"] == f"i:{0xFFFF_FFFF}"
    assert got["session_id"] == "s:12345"  # digits, but typed str in Python


def test_create_options_env_fallback_and_overflow(artifact, tmp_path,
                                                  monkeypatch):
    """pt_infer_create (no explicit options) honors the env var with
    guess-typing; an out-of-range integer fails loudly instead of being
    silently clamped."""
    path, _ = artifact
    dump = tmp_path / "opts.txt"
    monkeypatch.setenv("FAKE_PJRT_DUMP_OPTIONS", str(dump))
    monkeypatch.setenv("PADDLE_TPU_PJRT_CREATE_OPTIONS",
                       "priority=3;name=svc")
    pred = pdnative.NativePredictor(path + ".pdnative",
                                    pdnative.build_fake_plugin())
    pred.close()
    got = dict(l.split("=", 1) for l in dump.read_text().splitlines())
    assert got["priority"] == "i:3"
    assert got["name"] == "s:svc"
    monkeypatch.setenv("PADDLE_TPU_PJRT_CREATE_OPTIONS",
                       "rank=99999999999999999999999")
    with pytest.raises(RuntimeError, match="out-of-range"):
        pdnative.NativePredictor(path + ".pdnative",
                                 pdnative.build_fake_plugin())
