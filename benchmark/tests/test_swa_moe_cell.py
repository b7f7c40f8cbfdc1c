"""A tiny cell of the window-and-experts family (four window rings a lane
that hold rotated keys beside one paged full layer without positions, a
gated attention, a share of 4 of 16 sigmoid-routed experts held) through
the harness, on the kernel routes its chip cell asks for (the banded flash
prefill kernel and the paged decode kernel, interpreted): sound it is
correct; as its own control (int8 weights, the paged pool int8, the held
experts on the int8 grid) it is not; with the timed path broken (a window
one row short, the sliding layers unrotated, the gate left out) it is not;
the cell's readers read what the counters and a trace feed.

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests -q -p no:cacheprovider

The cell has files of its own under ``tests/data``
(``BENCHMARK.swa_moe.json``, ``configs/tiny-swa-moe.json``,
``cells/tiny-swa-moe.json``) and the tiny closed-loop mix that is there. Its
limits were set as the chip's are: above the sound runs, below the control
(readings in ``cells/tiny-swa-moe.json``; float32 for ``tiny-latent``'s
reason).
"""
import json
import os
import sys
import time
import types

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import run_tiny  # noqa: E402


def _cell(overrides=None):
    from benchmark.harness import spec

    with open(os.path.join(run_tiny.DATA, "BENCHMARK.swa_moe.json")) as f:
        bench = json.load(f)
    return spec.Cell("tiny-swa-moe", bench=bench, overrides=overrides,
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


@pytest.mark.parametrize("broken", ["window_one_row_short",
                                    "sliding_layers_unrotated",
                                    "gate_left_out"])
def test_a_broken_timed_path_is_not_correct(broken, monkeypatch):
    """The PROGRAM computes something else than the configuration states,
    the reference what it states: a window of 15 rows (rings, prefill band
    and decode mask alike); no rotary in the sliding layers; no gate on the
    attention's output."""
    from paddle_tpu.models import trinity as T

    if broken == "window_one_row_short":
        sound = T.WindowLayerState
        monkeypatch.setattr(T, "WindowLayerState", lambda heads, dim, window,
                            **kw: sound(heads, dim, window - 1, **kw))
    elif broken == "sliding_layers_unrotated":
        monkeypatch.setattr(T, "_rotary", lambda x, pos, freq: x)
    else:
        monkeypatch.setattr(T.TrinityAttention, "gate", lambda self, x: 1.0)
    out = _run(2 ** 31 + 65)
    assert out["correct"] is False and out["failed"] == 0


def test_a_traced_run_reports_what_the_counters_feed():
    """On the CPU the device plane is empty, so the trace's readers give
    nothing and the line leaves them out; what the counters feed is there:
    the share of the rings' rows that hold a key (prompts of 8-48 tokens
    under a window of 16: nearly all), one paged layer's bytes a token,
    and the rings' bytes."""
    out = _run(62, trace=True)
    m = out["metrics"]
    assert out["correct"]
    assert 80 < m["window_ring_live_pct"]["value"] <= 100
    # 1 paged layer x K and V x 2 heads x 32 values, float32; 40 blocks,
    # 39 usable
    assert m["arena_bytes_per_token"]["value"] == 2 * 2 * 32 * 4 * 40 / 39
    # 4 sliding layers x 4 lanes x K and V x [2, 16, 32] float32
    assert m["state_store_gb"]["value"] == 4 * 4 * 2 * 2 * 16 * 32 * 4 / 1e9
    for name in ("swa_prefill_roofline", "swa_moe_prefill_mfu_pct",
                 "swa_moe_decode_step_roofline",
                 "swa_moe_expert_ffn_roofline"):
        assert name not in m


def _config():
    with open(os.path.join(run_tiny.ROOT, "benchmark", "configs",
                           "trinity-large-preview-serve.json")) as f:
        return json.load(f)


def test_the_configuration_keeps_every_published_width():
    """Every number of the catalog's ``config`` under the same key, but
    those in ``reduced``, which state the published number beside them."""
    cfg = _config()
    published = {
        "global_attn_every_n_layers": 4, "head_dim": 128,
        "hidden_size": 3072, "intermediate_size": 12288,
        "load_balance_coeff": 5e-05, "max_position_embeddings": 262144,
        "moe_intermediate_size": 3072, "n_group": 1,
        "num_attention_heads": 48, "num_dense_layers": 6,
        "num_expert_groups": 1, "num_experts": 256,
        "num_experts_per_tok": 4, "num_hidden_layers": 60,
        "num_key_value_heads": 8, "num_limited_groups": 1,
        "num_shared_experts": 1, "rms_norm_eps": 1e-05, "rope_theta": 10000,
        "route_scale": 2.448, "sliding_window": 4096, "topk_group": 1,
        "vocab_size": 200192}
    for key, value in published.items():
        if key in cfg["reduced"]:
            assert cfg["published"][key] == value and cfg[key] < value
            assert key in cfg["reduced_why"]
        else:
            assert cfg[key] == value
    assert set(cfg["reduced"]) == set(cfg["published"]) \
        == set(cfg["reduced_why"])
    assert cfg["layer_types"] == ["sliding_attention"] * 3 \
        + ["full_attention", "sliding_attention"]
    assert cfg["serving"]["engine"] == {
        "num_slots": 32, "num_blocks": 24576, "kv_block_size": 16,
        "max_model_len": 16384, "paged_kernel": True}


def test_the_roofline_counts_follow_the_configuration():
    from benchmark.roofline import swa_moe as R

    cfg = _config()
    p = R.params(cfg)
    assert round(p["attention"] / 1e6, 2) == 62.91     # ISSUE 44's table
    assert round(p["dense_mlp"] / 1e6, 2) == 113.25
    assert round(p["expert"] / 1e6, 2) == 28.31
    assert round(p["router"] / 1e6, 2) == 0.79
    s = R.sizes(cfg)
    assert (s["held"], s["routed"], s["k"]) == (32, 256, 4)
    assert (s["sliding"], s["full"], s["dense"], s["expert"]) == (4, 1, 1, 4)
    # the file's own arithmetic: what the bytes section states
    b = cfg["bytes"]
    assert p["attention"] + p["dense_mlp"] == b["dense_layer_params"]
    layer = p["attention"] + p["router"] + 33 * p["expert"]
    assert layer == b["expert_layer_params_with_32_held"]
    assert b["dense_layer_params"] + 4 * layer + 2 * 25024 * 3072 \
        == b["weights_params"]
    # the pairs: the band is linear in the prompt, the causal half square
    assert R.pairs(100, 4096) == R.pairs(100) == 5050
    assert R.pairs(4096, 4096) == R.pairs(4096)
    assert R.pairs(16384, 4096) == 4096 * 4097 / 2 + 12288 * 4096
    assert R.pairs(16384, 4096) / R.pairs(16384) < 0.44
    # a token meets 0.60 G parameters here with half an expert a layer
    assert 0.59e9 < R.active_params_per_token(cfg, 0.5) < 0.61e9
    # a 7,168-token prefill: 11 TFLOP, a quarter of it attention
    whole = R.prefill_flops(cfg, 7168, 0.5)
    attn = R.attention_flops(cfg, 7168)
    assert 10e12 < whole < 12e12 and 0.2 < attn / whole < 0.3
    peaks = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}
    step = R.decode_step_least(cfg, 8.644e9, 4096, 245000, 4 * 32 * 3500,
                               32, 4 * 12.6, 0.5, peaks)
    assert step["bound"] == "memory" and 0.0070 < step["seconds"] < 0.0085
    assert set(step["parts"]) == {"weights", "experts", "rings", "pool"}
    assert abs(step["parts"]["weights"] - (8.644e9 - 25024 * 3072 * 2
                                           - 128 * p["expert"] * 2)) < 1
    assert step["parts"]["rings"] == 4 * 32 * 3500 * 4096
    ffn = R.expert_ffn_least(cfg, 4 * 12.6, peaks)
    assert 0.0034 < ffn["seconds"] < 0.0036


def _run_record(counters, trace=None, polls=()):
    cell = types.SimpleNamespace(config=_config())
    return {"cell": cell, "counters": counters, "trace": trace,
            "polls": list(polls),
            "program": {"weight_bytes": 8.644e9, "kv_bytes_per_token": 4096,
                        "block_size": 16},
            "peaks": {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}}


_COUNTERS = {"moe.assignments": 128 * 160, "moe.local_assignments": 16 * 160,
             "moe.experts_touched": 12 * 160, "moe.layer_steps": 160,
             "window.rows_live": 40 * 4 * 32 * 3000,
             "window.rows_read": 40 * 4 * 32 * 4096}


def test_the_counter_readers():
    from benchmark.layer_metrics import window_ring_live_pct as ring
    from benchmark.roofline import swa_moe as R

    run = _run_record(_COUNTERS)
    assert abs(ring.read(run) - 100 * 3000 / 4096) < 1e-9
    assert R.steps_counted(run) == 40
    assert R.local_picks(run) == 4 * 16 / 128
    assert R.experts_touched_per_step(run) == 4 * 12.0
    assert R.ring_rows_live_per_step(run) == 4 * 32 * 3000
    # a program without the counters (the parent's) has nothing to read
    assert ring.read(_run_record({})) is None
    assert ring.read(_run_record({"moe.assignments": 5})) is None
    assert R.local_picks(_run_record({})) is None
    assert R.ring_rows_live_per_step(_run_record({})) is None
    assert R.ring_rows_live_per_step(
        _run_record({"moe.layer_steps": 4})) is None


def test_the_trace_readers_on_a_made_trace():
    """``jit_step`` of 12 ms holding two ``gmm`` calls of 0.6 ms a layer;
    one prefill of 8,192 positions in 140 ms whose five flash calls (four
    banded, one causal) take 8 ms each: each reader's share follows by
    hand."""
    from benchmark.layer_metrics import swa_moe_decode_step_roofline as step
    from benchmark.layer_metrics import swa_moe_expert_ffn_roofline as ffn
    from benchmark.layer_metrics import swa_moe_prefill_mfu_pct as mfu
    from benchmark.layer_metrics import swa_prefill_roofline as flash
    from benchmark.roofline import swa_moe as R

    ops = [(f"%gmm.{i} = bf16[64,6144]{{1,0}} custom-call(...)",
            1.001 + 0.001 * i, 0.0006) for i in range(8)]
    ops += [(f"%swa_prefill_flash.{i} = bf16[48,8192,128]{{2,1,0}} "
             "custom-call(bf16[48,8192,128]{2,1,0} %a, ...)",
             2.01 + 0.01 * i, 0.008) for i in range(5)]
    # a flash call outside any traced prefill is no part of the share
    ops += [("%swa_prefill_flash.9 = bf16[48,1024,128]{2,1,0} "
             "custom-call(...)", 2.5, 0.001)]
    dev = {"modules": [("jit_step(1)", 1.0, 0.012),
                       ("jit_prefill(2)", 2.0, 0.140)], "ops": ops}
    tr = types.SimpleNamespace(window=(0.5, 3.0), devices={0: dev},
                               window_s=2.5)
    polls = [{"arena.blocks_total": 24576, "arena.blocks_free": 9576,
              "slots.active": 32}]
    run = _run_record(_COUNTERS, tr, polls)
    cfg, peaks = run["cell"].config, run["peaks"]
    (positions, seconds, kernel_s), = R.traced_prefills(run)
    assert positions == 8192 and abs(seconds - 0.140) < 1e-9
    assert abs(kernel_s - 0.040) < 1e-9
    want = R.attention_flops(cfg, 8192) / 197e12
    assert abs(flash.read(run) - 100 * want / 0.040) < 1e-6
    need = R.prefill_flops(cfg, 8192, 0.5)
    assert abs(mfu.read(run) - 100 * need / (0.140 * 197e12)) < 1e-6
    want = R.expert_ffn_least(cfg, 48.0, peaks)["seconds"]
    assert abs(ffn.read(run) - 100 * want / 0.0048) < 1e-6
    assert 0 < flash.read(run) < 100 and 0 < mfu.read(run) < 100
    assert 0 < ffn.read(run) < 100
    import benchmark.harness.readers as readers

    was = readers.T.module_durations
    readers.T.module_durations = lambda tr, module: [0.012]
    try:
        want = R.decode_step_least(cfg, 8.644e9, 4096, 240000,
                                   4 * 32 * 3000, 32, 48.0, 0.5,
                                   peaks)["seconds"]
        assert abs(step.read(run) - 100 * want / 0.012) < 1e-6
        assert 0 < step.read(run) < 100
    finally:
        readers.T.module_durations = was
    # nothing traced, or a program that counts nothing or runs no such
    # kernel (the parent's): nothing to read, and nothing raised
    for reader in (step, ffn, flash, mfu):
        assert reader.read(_run_record(_COUNTERS)) is None
    assert step.read(_run_record({}, tr, polls)) is None
    assert ffn.read(_run_record({}, tr, polls)) is None
    assert mfu.read(_run_record({}, tr, polls)) is None
    bare = types.SimpleNamespace(window=(0.5, 3.0), window_s=2.5, devices={
        0: {"modules": dev["modules"], "ops": ops[:8]}})
    assert flash.read(_run_record(_COUNTERS, bare, polls)) is None
    assert mfu.read(_run_record(_COUNTERS, bare, polls)) is None
