"""A tiny cell of the decoder-hybrid-decoder family (Mamba layers, window
attention, one paged cache with a cross-attention reader, a gated memory
unit) through the harness: sound it is correct; with a window ring written
one row early (over the token before), and as its own control (int8 weights,
int8 KV), it is not.

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests -q -p no:cacheprovider

The cell has files of its own under ``tests/data`` (``BENCHMARK.flash.json``,
``configs/tiny-flash.json``, ``cells/tiny-flash.json``) and the tiny
closed-loop mix that is there. Its limits were set as the chip's are: above
the sound runs, below the control (readings in ``cells/tiny-flash.json``).
"""
import json
import os
import sys
import time

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import run_tiny  # noqa: E402


def _cell(overrides=None):
    from benchmark.harness import spec

    with open(os.path.join(run_tiny.DATA, "BENCHMARK.flash.json")) as f:
        bench = json.load(f)
    return spec.Cell("tiny-flash", bench=bench, overrides=overrides,
                     data_dir=run_tiny.DATA)


def _run(seed, overrides=None, trace=False, seconds=3.0):
    from benchmark import run as R

    R.environment()
    from benchmark.harness import common

    device = dict(common.device_record(), kind="TPU v5 lite")
    return json.loads(R.run_cell(_cell(overrides), seed, seconds, trace,
                                 device, t_proc=time.monotonic()))


def test_sound_is_correct_and_a_ring_written_one_row_early_is_not(
        monkeypatch):
    sound = _run(2 ** 31 + 61)
    assert sound["correct"] and sound["failed"] == 0
    import jax.numpy as jnp

    from paddle_tpu.serving import engine as E

    # the decode step writes a window layer's new K/V one row early: over
    # the row of the token before, which the window then lacks
    attend = E._WindowDecodeView.update_and_attend

    def one_row_early(self, q, k, v):
        early = E._WindowDecodeView(
            self.entry, jnp.maximum(self.positions - 1, 0), self.window)
        o, new = attend(early, q, k, v)
        return o, E._WindowDecodeView(new.entry, self.positions,
                                      self.window)

    monkeypatch.setattr(E._WindowDecodeView, "update_and_attend",
                        one_row_early)
    broken = _run(2 ** 31 + 61)
    assert broken["correct"] is False and broken["failed"] == 0


@pytest.mark.parametrize("seed", [71, 72, 2 ** 31 + 73])
def test_the_control_is_not_correct(seed):
    out = _run(seed, overrides=_cell().config["control"])
    assert out["correct"] is False and out["failed"] == 0


def test_a_traced_run_reports_the_new_per_layer_metrics():
    """On the CPU the device plane is empty, so the trace's readers give
    nothing and the line leaves them out; what the counters feed is there:
    one tail token a prefill, one paged layer's bytes a token."""
    out = _run(2 ** 31 + 62, trace=True)
    assert out["correct"]
    m = out["metrics"]
    assert 0 < m["prefill_tail_tokens_pct"]["value"] < 13  # prompts 8..48
    # K and V, 2 stored heads of width 32, bf16; 40 blocks over 39 usable
    assert m["arena_bytes_per_token"]["value"] == 2 * 2 * 32 * 2 * 40 / 39
    assert m["state_store_gb"]["value"] > 0
    assert "flash_decode_step_roofline" not in m


def test_the_roofline_counts_follow_the_configuration():
    from benchmark.roofline import flash_decode_step as D
    from benchmark.roofline import flash_prefill as P

    with open(os.path.join(run_tiny.ROOT, "benchmark", "configs",
                           "phi4-mini-flash-serve.json")) as f:
        cfg = json.load(f)
    assert D.layer_counts(cfg) == {"mamba": 9, "window": 8, "full": 1,
                                   "gmu": 7, "cross": 7}
    # the tied table is the head: 3.85 B weights a token is multiplied by
    assert round(D.matmul_params(cfg) / 1e9, 2) == 3.85
    assert D.kv_row_bytes(cfg) == 5120
    assert D.ssm_bytes_per_lane(cfg) == 9 * (327680 + 30720)
    peaks = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}
    least = D.least_seconds(cfg, 7.70e9, 5120, 134000, 32, peaks)
    assert least["bound"] == "memory" and least["readers"] == 8
    assert 0.0165 < least["seconds"] < 0.0180  # ISSUE 31: 17 ms
    short, long = P.flops(cfg, 1024), P.flops(cfg, 8192)
    assert 7.9 < long["body"] / short["body"] < 8.1  # linear in the prompt
    assert long["tail"] < 1.01 * short["tail"] + 8 * 15360 * 8192
    assert long["tail"] < 0.001 * long["body"]
