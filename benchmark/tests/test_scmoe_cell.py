"""A tiny cell of the shortcut-connected expert family (two latent pool
entries a layer, softmax routing over routed and zero-compute columns, a
share of 4 of 16 routed experts held) through the harness: sound it is
correct; as its own control (int8 weights, the held experts on the int8
grid) it is not; the cell's readers read what the counters feed.

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests -q -p no:cacheprovider

The cell has files of its own under ``tests/data`` (``BENCHMARK.scmoe.json``,
``configs/tiny-scmoe.json``, ``cells/tiny-scmoe.json``) and the tiny
closed-loop mix that is there. Its limits were set as the chip's are: above
the sound runs, below the control (readings in ``cells/tiny-scmoe.json``;
float32 for ``tiny-latent``'s reason).
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

    with open(os.path.join(run_tiny.DATA, "BENCHMARK.scmoe.json")) as f:
        bench = json.load(f)
    return spec.Cell("tiny-scmoe", bench=bench, overrides=overrides,
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
    the zero-compute experts' share of the picks (8 of 24 columns: a
    third), the rows the grouped matmuls moved for each row a held expert
    took, and two latent rows' bytes a token a layer."""
    out = _run(62, trace=True)
    m = out["metrics"]
    assert out["correct"]
    assert 25 < m["zero_expert_share_pct"]["value"] < 42
    assert 1 <= m["expert_rows_moved_per_local_row"]["value"] < 48
    # 2 layers x 2 entries x (64 + 16) values, float32; 40 blocks, 39 usable
    assert m["arena_bytes_per_token"]["value"] == 4 * 80 * 4 * 40 / 39
    for name in ("scmoe_decode_step_roofline", "scmoe_prefill_mfu_pct",
                 "scmoe_latent_decode_roofline",
                 "scmoe_expert_ffn_roofline"):
        assert name not in m


def _config():
    with open(os.path.join(run_tiny.ROOT, "benchmark", "configs",
                           "longcat-flash-omni-serve.json")) as f:
        return json.load(f)


def test_the_roofline_counts_follow_the_configuration():
    from benchmark.roofline import scmoe as R

    cfg = _config()
    p = R.params(cfg)
    assert round(p["attention"] / 1e6, 2) == 90.57     # ISSUE 39's table
    assert round(p["dense_mlp"] / 1e6, 1) == 226.5
    assert round(p["expert"] / 1e6, 2) == 37.75
    assert round(p["router"] / 1e6, 2) == 4.72
    s = R.sizes(cfg)
    assert (s["held"], s["routed"], s["zero"], s["k"]) == (16, 512, 256, 12)
    # the file's own arithmetic: what the bytes section states
    b = cfg["bytes"]
    beside = 2 * p["attention"] + 2 * p["dense_mlp"] + p["router"]
    assert beside == b["layer_params_beside_experts"]
    assert 4 * (beside + 16 * p["expert"]) + 2 * 16384 * 6144 \
        == b["weights_params"]
    # a token meets 2.59 G parameters here with a quarter of an expert
    assert 2.58e9 < R.active_params_per_token(cfg, 0.25) < 2.60e9
    # a 1,024-token prefill: 5.48 TFLOP, 3% of it attention
    whole = R.prefill_flops(cfg, 1024, 0.25, 4.0)
    attn = 8 * R.attention_flops_per_key(cfg) * 1024 * 1025 / 2
    assert 5.4e12 < whole < 5.6e12 and 0.03 < attn / whole < 0.05
    peaks = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}
    step = R.decode_step_least(cfg, 10.35e9, 9216, 263000, 128, 4 * 13.8,
                               0.25, peaks)
    assert step["bound"] == "memory" and 0.0145 < step["seconds"] < 0.0155
    rows = R.latent_decode_least(cfg, 9216, 263000, peaks)
    assert rows["bound"] == "memory" and 0.0029 < rows["seconds"] < 0.0030
    assert 0.48 < rows["flops"] / 197e12 / rows["seconds"] < 0.52
    ffn = R.expert_ffn_least(cfg, 4 * 13.8, peaks)
    assert 0.0050 < ffn["seconds"] < 0.0052


def _run_record(counters, trace=None, polls=()):
    cell = types.SimpleNamespace(config=_config())
    return {"cell": cell, "counters": counters, "trace": trace,
            "polls": list(polls),
            "program": {"weight_bytes": 10.35e9, "kv_bytes_per_token": 9216,
                        "block_size": 16},
            "peaks": {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}}


def test_the_counter_readers():
    from benchmark.layer_metrics import \
        expert_rows_moved_per_local_row as moved
    from benchmark.layer_metrics import zero_expert_share_pct as zero

    c = {"moe.assignments": 1536 * 40, "moe.zero_assignments": 512 * 40,
         "moe.local_assignments": 32 * 40, "moe.rows_moved": 64 * 40,
         "moe.experts_touched": 14 * 40, "moe.layer_steps": 40}
    run = _run_record(c)
    assert abs(zero.read(run) - 100 / 3) < 1e-9
    assert moved.read(run) == 2.0
    # a program without the counters (the parent's) has nothing to read
    assert zero.read(_run_record({})) is None
    assert moved.read(_run_record({"moe.assignments": 5})) is None
    assert zero.read(_run_record({"moe.assignments": 5})) is None
    from benchmark.roofline import scmoe as R

    assert R.routed_here(run) == (12 * 32 / 1536, 12 * 512 / 1536)
    assert R.experts_touched_per_step(run) == 4 * 14.0
    assert R.routed_here(_run_record({})) is None


def test_the_trace_readers_on_a_made_trace():
    """``jit_step`` of 20 ms holding eight latent calls of 0.8 ms and two
    ``gmm`` calls of 0.5 ms a layer inside a pass; one prefill of 1,024
    positions in 40 ms: each reader's share follows by hand."""
    from benchmark.layer_metrics import scmoe_decode_step_roofline as step
    from benchmark.layer_metrics import scmoe_expert_ffn_roofline as ffn
    from benchmark.layer_metrics import scmoe_latent_decode_roofline as lat
    from benchmark.layer_metrics import scmoe_prefill_mfu_pct as mfu
    from benchmark.roofline import scmoe as R

    ops = [(f"%paged_latent_decode.{i} = bf16[128,64,512]{{2,1,0}} "
            "custom-call(...)", 1.0 + 0.001 * i, 0.0008) for i in range(8)]
    ops += [(f"%gmm.{i} = bf16[64,4096]{{1,0}} custom-call(...)",
             1.010 + 0.001 * i, 0.0005) for i in range(8)]
    ops += [("%latent_prefill_flash.3 = bf16[64,1024,128]{2,1,0} "
             "custom-call(bf16[64,1024,192]{2,1,0} %a, ...)", 2.01, 0.002)]
    dev = {"modules": [("jit_step(1)", 1.0, 0.020),
                       ("jit_prefill(2)", 2.0, 0.040)], "ops": ops}
    tr = types.SimpleNamespace(window=(0.5, 3.0), devices={0: dev},
                               window_s=2.5)
    c = {"moe.assignments": 1536 * 40, "moe.zero_assignments": 512 * 40,
         "moe.local_assignments": 32 * 40, "moe.rows_moved": 64 * 40,
         "moe.experts_touched": 14 * 40, "moe.layer_steps": 40}
    polls = [{"arena.blocks_total": 20480, "arena.blocks_free": 4480,
              "slots.active": 128}]
    run = _run_record(c, tr, polls)
    cfg, peaks = run["cell"].config, run["peaks"]
    want = R.latent_decode_least(cfg, 9216, 256000, peaks)["seconds"]
    assert abs(lat.read(run) - 100 * want / 0.0064) < 1e-6
    want = R.expert_ffn_least(cfg, 56.0, peaks)["seconds"]
    assert abs(ffn.read(run) - 100 * want / 0.004) < 1e-6
    need = R.prefill_flops(cfg, 1024, 0.25, 4.0)
    assert abs(mfu.read(run) - 100 * need / (0.040 * 197e12)) < 1e-6
    assert 0 < mfu.read(run) < 100 and 0 < lat.read(run) < 100
    import benchmark.harness.readers as readers

    was = readers.T.module_durations
    readers.T.module_durations = lambda tr, module: [0.020]
    try:
        want = R.decode_step_least(cfg, 10.35e9, 9216, 256000, 128, 56.0,
                                   0.25, peaks)["seconds"]
        assert abs(step.read(run) - 100 * want / 0.020) < 1e-6
    finally:
        readers.T.module_durations = was
    # nothing traced, or a program that counts nothing: nothing to read
    for reader in (step, ffn, lat, mfu):
        assert reader.read(_run_record(c)) is None
    assert step.read(_run_record({}, tr, polls)) is None
    assert ffn.read(_run_record({}, tr, polls)) is None
    assert mfu.read(_run_record({}, tr, polls)) is None
