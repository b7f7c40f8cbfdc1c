"""A tiny cell of the latent-attention expert family (one latent row a
token in the paged pool, absorbed decode, sigmoid-routed experts beside a
shared one, four hyper-connected streams) through the harness: sound it is
correct; as its own control (int8 weights) it is not.

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests -q -p no:cacheprovider

The cell has files of its own under ``tests/data`` (``BENCHMARK.latent.json``,
``configs/tiny-latent.json``, ``cells/tiny-latent.json``) and the tiny
closed-loop mix that is there. Its limits were set as the chip's are: above
the sound runs, below the control (readings in ``cells/tiny-latent.json``,
which also say why this tiny configuration is float32).
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

    with open(os.path.join(run_tiny.DATA, "BENCHMARK.latent.json")) as f:
        bench = json.load(f)
    return spec.Cell("tiny-latent", bench=bench, overrides=overrides,
                     data_dir=run_tiny.DATA)


def _run(seed, overrides=None, trace=False, seconds=3.0):
    from benchmark import run as R

    R.environment()
    from benchmark.harness import common

    device = dict(common.device_record(), kind="TPU v5 lite")
    return json.loads(R.run_cell(_cell(overrides), seed, seconds, trace,
                                 device, t_proc=time.monotonic()))


@pytest.mark.parametrize("seed", [61, 2 ** 31 + 63])
def test_sound_is_correct(seed):
    out = _run(seed)
    assert out["correct"] and out["failed"] == 0


@pytest.mark.parametrize("seed", [71, 2 ** 31 + 73])
def test_the_control_is_not_correct(seed):
    out = _run(seed, overrides=_cell().config["control"])
    assert out["correct"] is False and out["failed"] == 0


def test_a_traced_run_reports_what_the_counters_feed():
    """On the CPU the device plane is empty, so the trace's readers give
    nothing and the line leaves them out; what the counters feed is there:
    the experts' load, and one latent row's bytes a token."""
    out = _run(62, trace=True)
    m = out["metrics"]
    assert out["correct"] and 0 < m["expert_load_imbalance_pct"]["value"]
    # 3 layers x (64 + 16) values, float32; 40 blocks over 39 usable
    assert m["arena_bytes_per_token"]["value"] == 3 * 80 * 4 * 40 / 39
    for name in ("latent_decode_roofline", "expert_ffn_roofline",
                 "latent_moe_decode_step_roofline",
                 "latent_moe_prefill_mfu_pct",
                 "latent_prefill_device_ms_per_ktoken"):
        assert name not in m


def test_the_roofline_counts_follow_the_configuration():
    from benchmark.roofline import latent_moe as R

    with open(os.path.join(run_tiny.ROOT, "benchmark", "configs",
                           "xing4-29b-a4b-serve.json")) as f:
        cfg = json.load(f)
    p = R.params(cfg)
    assert round(p["attention"] / 1e6, 2) == 28.41     # ISSUE 33's table
    assert round(p["expert"] / 1e6, 2) == 11.01
    assert round(p["dense_mlp"] / 1e6, 2) == 99.09
    assert round(p["mixers"] / 1e6, 2) == 0.69
    assert R.row_bytes(cfg) == 1152
    # a token meets 1.1 G parameters' worth of operations twice over
    assert 1.09e9 < 2 * R.active_params_per_token(cfg) < 1.11e9
    # a 4.5k-token prefill: some 6 TFLOP, a fifth of it attention
    whole = R.prefill_flops(cfg, 4500)
    attn = 6 * R.attention_flops_per_key(cfg) * 4500 * 4501 / 2
    assert 5.5e12 < whole < 6.5e12 and 0.15 < attn / whole < 0.25
    peaks = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}
    step = R.decode_step_least(cfg, 9.59e9, 6912, 310000, 64, 320, peaks)
    assert step["bound"] == "memory" and 0.0125 < step["seconds"] < 0.0140
    rows = R.latent_decode_least(cfg, 6912, 310000, peaks)
    assert rows["bound"] == "memory" and 0.0025 < rows["seconds"] < 0.0027
    ffn = R.expert_ffn_least(cfg, 5 * 63, peaks)
    assert 0.0083 < ffn["seconds"] < 0.0086


def test_the_bucket_is_read_off_the_prefills_flash_kernel():
    from benchmark.roofline import latent_moe as R

    ops = [("%f = bf16[2048,3584]{1,0} fusion(...)", 1.0, 0.1),
           ("%latent_prefill_flash.7 = bf16[32,6144,128]{2,1,0} custom-call("
            "bf16[32,6144,192]{2,1,0} %a, ...)", 1.1, 0.1),
           ("%latent_prefill_flash.9 = bf16[32,1024,128]{2,1,0} custom-call("
            "bf16[32,1024,192]{2,1,0} %a, ...)", 9.0, 0.1)]
    assert R.bucket_of(ops, 0.9, 1.4) == 6144
    assert R.bucket_of(ops[:1], 0.9, 1.4) is None


def test_per_ktoken_time_counts_each_prefill_at_its_flash_bucket():
    """``latent_prefill_device_ms_per_ktoken`` over a trace of two
    prefills (6,144 positions in 120 ms, 1,024 in 30): 150 ms over 7,168
    positions, whatever other ``[n, hidden]`` shapes the programs hold."""
    import types

    from benchmark.layer_metrics import \
        latent_prefill_device_ms_per_ktoken as reader

    flash = ("%latent_prefill_flash.{} = bf16[32,{},128]{{2,1,0}} "
             "custom-call(bf16[32,{},192]{{2,1,0}} %a, ...)")
    dev = {"modules": [("jit_prefill(111)", 1.0, 0.12),
                       ("jit_prefill(222)", 2.0, 0.03),
                       ("jit_step(333)", 3.0, 0.016)],
           "ops": [("%f = bf16[4096,3584]{1,0} fusion(...)", 1.0, 0.01),
                   ("%g = bf16[24576,3584]{1,0} fusion(...)", 1.01, 0.01),
                   (flash.format(7, 6144, 6144), 1.05, 0.02),
                   (flash.format(9, 1024, 1024), 2.01, 0.01)]}
    tr = types.SimpleNamespace(window=(0.5, 4.0), devices={0: dev})
    got = reader.read({"trace": tr})
    assert abs(got - 1e6 * 0.15 / 7168) < 1e-9
    assert reader.read({"trace": None}) is None
