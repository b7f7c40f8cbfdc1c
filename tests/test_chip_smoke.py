"""``chip_smoke.py`` off the chip: it refuses to run, and its phases rehearse.

The script itself needs a TPU (and says so with a non-zero exit). Its phase
functions take the sizes as a ``Plan``, so this file drives the SAME code at
a tiny size on the CPU backend — Pallas kernels interpreted, four of
conftest's virtual devices for the mesh phases — which is rehearsals 1 and 2
of ``/opt/skills/guides/on-chip-measurement`` §2 kept as tests. What only a
chip can show (a ``tpu_custom_call`` in the step, no interpret mode) is
what ``chip=False`` leaves out.
"""
import importlib.util
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(ROOT, "chip_smoke.py")


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", SMOKE)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod  # dataclasses resolve annotations there
    try:
        spec.loader.exec_module(mod)
        yield mod
    finally:
        del sys.modules[spec.name]


@pytest.fixture
def tiny(smoke, monkeypatch):
    """A tiny plan. On the chip each phase ends by dropping every compiled
    program (``jax.clear_caches``) to free HBM; in this process that would
    throw away what the rest of the suite has compiled, so it is a no-op
    here."""
    import jax

    monkeypatch.setattr(jax, "clear_caches", lambda: None)
    return smoke.Plan(
        hidden=128, heads=4, layers=2, vocab=1024, max_len=128,
        slots=4, arena_blocks=24, block=16,
        requests=((12, 6), (10, 8), (40, 6), (36, 8)), parity=(0, 2),
        train_layers=2, train_batch=4, train_seq=64, kernel_sq=16)


def _phases(capsys):
    return [json.loads(line) for line in capsys.readouterr().out.splitlines()
            if line.startswith("{")]


@pytest.mark.parametrize("argv", [[], ["--chips", "4"]],
                         ids=["one-chip", "four-chips"])
def test_no_tpu_exits_nonzero_before_any_phase(argv):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, SMOKE, *argv], env=env, cwd=ROOT,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode != 0
    assert r.stdout.strip() == ""  # no phase line, no result
    assert '"ok"' not in r.stdout + r.stderr
    assert "needs a TPU" in r.stderr


def test_full_plan_is_gpt_1p3b(smoke):
    """The chip runs the repo's own largest config, not a copy that can
    drift from it."""
    from paddle_tpu.models.gpt import gpt_1p3b

    assert smoke.gpt_config(smoke.FULL) == gpt_1p3b()
    per_token = 2 * 24 * 2048 * 2  # K and V, 24 layers, bf16
    assert per_token == 192 * 1024
    arena = smoke.FULL.arena_blocks * smoke.FULL.block * per_token
    assert 7.9 * 2 ** 30 < arena <= 8 * 2 ** 30


def test_rehearse_one_chip_phases(smoke, tiny, capsys):
    smoke.run(tiny, chips=1, seed=0, chip=False)
    recs = {r["phase"]: r for r in _phases(capsys)}
    assert list(recs) == ["serve", "serve-kernel", "serve-kernel-int8",
                          "parity", "train"]
    assert recs["serve"]["route"] == "gather@single"
    assert recs["serve"]["tokens_vs_generate"]["compared"] == 2
    assert all(r["ok"] for r in recs["parity"]["judged"])
    for name in ("serve-kernel", "serve-kernel-int8"):
        assert recs[name]["route"] == "kernel@single"
        assert recs[name]["prefill_programs"] == 2  # one per bucket
        assert recs[name]["decode_programs"] == 1
    assert recs["serve-kernel-int8"]["quant_kv"] is True
    assert recs["serve-kernel-int8"]["arena_bytes"] \
        < recs["serve-kernel"]["arena_bytes"]
    assert recs["train"]["losses"][-1] < recs["train"]["losses"][0]


def test_rehearse_four_chip_phases(smoke, tiny, capsys):
    """The mesh phases on four virtual devices: wrong meshes and sharding
    rules show here, not on four chips at four times the price."""
    smoke.run(tiny, chips=4, seed=0, chip=False)
    recs = {r["phase"]: r for r in _phases(capsys)}
    assert list(recs) == ["mesh-serve/one-device", "mesh-serve/gather",
                          "mesh-serve/kernel", "parity",
                          "mesh-train/one-device", "mesh-train/dp4"]
    assert recs["mesh-serve/one-device"]["kv_pool_devices"] == [1]
    assert recs["mesh-serve/gather"]["route"] == "gather@model4"
    assert recs["mesh-serve/kernel"]["route"] == "kernel@model4"
    assert recs["mesh-train/dp4"]["all_reduces"] > 0
    assert recs["mesh-train/one-device"]["all_reduces"] == 0


def test_a_failed_check_ends_the_run(smoke):
    with pytest.raises(SystemExit) as ei:
        smoke.check(False, "a phase failed")
    assert ei.value.code not in (0, None)


def test_parity_judges_a_divergence_against_float32_logits(smoke, tiny,
                                                           capsys):
    """Equal continuations need no judge; a divergence passes only as a
    near-tie of the float32 reference logits; ``gate=False`` reports it."""
    import numpy as np

    import paddle_tpu as pt
    from paddle_tpu.core.tensor import Tensor

    model = smoke.serving_model(tiny, 0)
    prompts = smoke.make_prompts(tiny, 0)
    with pt.no_grad():
        logits = np.asarray(model(Tensor(prompts[0][None]))._data[0, -1],
                            np.float32)
    best, worst = int(logits.argmax()), int(logits.argmin())
    same = {0: [best, 5, 6]}
    parity = smoke.Parity()
    assert parity.compare("x", prompts, same, same) == {
        "compared": 1, "exact": 1, "diverged": 0}
    assert parity.cases == []

    derailed = {0: [worst, 5, 6]}  # a token nowhere near the best
    assert parity.compare("a vs b", prompts, same, derailed)["diverged"] == 1
    with pytest.raises(SystemExit, match="no near-tie"):
        parity.judge(model, tiny)
    row = _phases(capsys)[-1]["judged"][0]
    assert row["tokens"] == [best, worst] and row["at"] == 0
    assert row["ok"] is False and row["below_best_in_bf16_steps"] > 4

    parity = smoke.Parity()
    parity.compare("int8 vs bf16", prompts, same, derailed, gate=False)
    parity.judge(model, tiny)  # reported, not held
    assert _phases(capsys)[-1]["judged"][0]["ok"] is True
    assert pt.get_flags("flash_attention_min_seqlen")[
        "flash_attention_min_seqlen"] == -1  # the judge put it back
