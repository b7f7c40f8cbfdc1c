"""With the timed path broken underneath, a run comes out NOT correct.

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests -q -p no:cacheprovider

Each test skips only the harness's look for a chip (``run_tiny.run`` calls
the same ``run_cell`` as ``benchmark/run.py``) and drives the rest of a run
at a tiny size, sound first and then broken. Not collected by
``pytest tests/``.
"""
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import run_tiny  # noqa: E402


def test_a_sound_serving_run_is_correct_and_an_altered_token_is_not(
        monkeypatch):
    assert run_tiny.run("tiny-chat", 2 ** 31 + 21, 3.0, False)["correct"]
    from paddle_tpu.serving.engine import ServingEngine

    sound = ServingEngine.decode_step

    def altered(self, active=None):
        # every 7th decode step hands back another token than it computed
        out = np.array(sound(self, active))
        self._bench_steps = getattr(self, "_bench_steps", 0) + 1
        if self._bench_steps % 7 == 0:
            out = (out + 1) % 1000
        return out

    monkeypatch.setattr(ServingEngine, "decode_step", altered)
    out = run_tiny.run("tiny-chat", 2 ** 31 + 21, 3.0, False)
    assert out["correct"] is False and out["failed"] == 0


def test_a_sound_training_run_is_correct_and_a_step_that_keeps_its_state_is_not(
        monkeypatch):
    assert run_tiny.run("tiny-train", 2 ** 31 + 22, 1.0, False)["correct"]
    from paddle_tpu.optimizer import AdamW

    # the optimizer hands parameters and moments back unchanged
    monkeypatch.setattr(AdamW, "_update",
                        lambda self, param, grad, slots, lr, step:
                        (param, slots))
    out = run_tiny.run("tiny-train", 2 ** 31 + 22, 1.0, False)
    assert out["correct"] is False


def test_a_window_that_compiles_is_not_correct(monkeypatch):
    """A program compiled inside the window (here: a prefill bucket the
    warm-up was told nothing of) makes the run not correct."""
    from benchmark.harness import serve

    monkeypatch.setattr(serve, "warm_buckets",
                        lambda engine, mix, max_len: [16])
    assert run_tiny.run("tiny-chat", 2 ** 31 + 23, 3.0, False)[
        "correct"] is False
