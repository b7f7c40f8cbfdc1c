"""A tiny cell of the sparse-attention-and-experts family (K and V rows and
an index key a token under one block table, an indexer of 8 heads that
keeps 8 tokens a query, a share of 4 of 16 softmax-routed experts held)
through the harness, on the kernel routes its chip cell asks for (the
paged index-score kernel, the two prefill kernels and megablox's stand-in,
interpreted): sound it is correct; as its own control (int8 weights, the
held experts on the int8 grid) it is not; with the timed path broken (seven
tokens kept for eight, the index key not normed, no selection at all) it
is not; the cell's readers read what the counters and a trace feed.

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests -q -p no:cacheprovider

The cell has files of its own under ``tests/data``
(``BENCHMARK.sparse_moe.json``, ``configs/tiny-sparse-moe.json``,
``cells/tiny-sparse-moe.json``) and the tiny closed-loop mix that is there.
Its limits were set as the chip's are: above the sound runs, below the
control (readings in ``cells/tiny-sparse-moe.json``; float32 for
``tiny-latent``'s reason).
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

    with open(os.path.join(run_tiny.DATA, "BENCHMARK.sparse_moe.json")) as f:
        bench = json.load(f)
    return spec.Cell("tiny-sparse-moe", bench=bench, overrides=overrides,
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


@pytest.mark.parametrize("broken", ["topk_one_short", "index_key_not_normed",
                                    "every_token_kept"])
def test_a_broken_timed_path_is_not_correct(broken, monkeypatch):
    """The PROGRAM computes something else than the configuration states,
    the reference what it states: 7 tokens kept for 8 (prefill thresholds
    and decode gather alike); the index key without its LayerNorm; the
    mechanism bypassed (every live token read)."""
    from paddle_tpu.models import keye as K

    sound = K.SparseKVLayerState
    if broken == "topk_one_short":
        monkeypatch.setattr(K, "SparseKVLayerState", lambda *a: sound(
            *a[:-1], a[-1] - 1))
    elif broken == "index_key_not_normed":
        monkeypatch.setattr(K, "_layer_norm",
                            lambda x, g, b, eps, dtype: x.astype(dtype))
    else:
        monkeypatch.setattr(K, "SparseKVLayerState", lambda *a: sound(
            *a[:-1], 10 ** 6))
    out = _run(2 ** 31 + 65)
    assert out["correct"] is False and out["failed"] == 0


def test_a_traced_run_reports_what_the_counters_feed():
    """On the CPU the device plane is empty, so the trace's readers give
    nothing and the line leaves them out; what the counters feed is there:
    the share of a lane's live rows that a step's attention reads (8 of
    contexts of 8-80, counted by the view where it gathers), the rows the
    expert path moved, and three pools' bytes a token a layer."""
    out = _run(62, trace=True)
    m = out["metrics"]
    assert out["correct"]
    assert 10 < m["sparse_kv_rows_read_pct"]["value"] < 60
    # 3 layers x (K and V x 2 heads x 32 values + an index key of 16),
    # float32; 40 blocks, 39 usable
    assert m["arena_bytes_per_token"]["value"] == \
        3 * (2 * 2 * 32 + 16) * 4 * 40 / 39
    # the expert layer's own metric: whole passes of ``row_cap`` rows over
    # the rows the 4 held experts of 16 took
    assert m["expert_rows_moved_per_local_row"]["value"] >= 1
    for name in ("sparse_decode_roofline", "index_select_ms_per_step",
                 "sparse_moe_decode_step_roofline",
                 "sparse_moe_prefill_mfu_pct"):
        assert name not in m


def _config():
    with open(os.path.join(run_tiny.ROOT, "benchmark", "configs",
                           "keye-vl-2-30b-a3b-serve.json")) as f:
        return json.load(f)


def test_the_configuration_keeps_every_published_width():
    """Every number of the catalog's ``config`` under the same key, but
    those in ``reduced``, which state the published number beside them;
    the nested groups whole."""
    cfg = _config()
    published = {
        "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128,
        "hidden_act": "silu", "hidden_size": 2048,
        "intermediate_size": 6144, "max_position_embeddings": 262144,
        "max_window_layers": 48, "mlp_only_layers": [],
        "model_type": "KeyeVL2", "moe_intermediate_size": 768,
        "norm_topk_prob": True, "num_attention_heads": 32,
        "num_experts": 128, "num_experts_per_tok": 8,
        "num_hidden_layers": 48, "num_key_value_heads": 4,
        "num_local_experts": 128, "rms_norm_eps": 1e-06,
        "rope_scaling": {"mrope_section": [16, 24, 24],
                         "rope_type": "default", "type": "default"},
        "rope_theta": 10000000,
        "sa_config": {"indexer_head_dim": 64, "indexer_num_heads": 16,
                      "indexer_num_kv_heads": 1, "kv_chunk_size": 512,
                      "q_chunk_size": 512, "topk": 2048},
        "sliding_window": None, "tie_word_embeddings": False,
        "use_sliding_window": False, "vocab_size": 151936}
    for key, value in published.items():
        if key in cfg["reduced"]:
            assert cfg["published"][key] == value and cfg[key] < value
            assert key in cfg["reduced_why"]
        else:
            assert cfg[key] == value
    assert cfg["reduced"] == ["num_hidden_layers", "num_experts",
                              "vocab_size", "max_position_embeddings"]
    assert set(cfg["reduced"]) == set(cfg["published"]) \
        == set(cfg["reduced_why"])
    assert (cfg["num_hidden_layers"], cfg["num_experts"], cfg["vocab_size"],
            cfg["max_position_embeddings"]) == (8, 16, 18992, 30720)
    assert cfg["vocab_size"] * 8 == 151936 and cfg["expert_first"] == 0
    assert cfg["serving"]["engine"] == {
        "num_slots": 32, "num_blocks": 32768, "kv_block_size": 16,
        "max_model_len": 30720, "paged_kernel": True}
    with open(os.path.join(run_tiny.ROOT, "benchmark", "traffic",
                           "batch-longctx.json")) as f:
        mix = json.load(f)
    assert {k: mix[k] for k in mix if k != "who"} == {
        "loop": "closed", "clients": 64, "requests_per_client": 12,
        "shuffle_block": 64,
        "prompt": {"median": 12288, "sigma": 0.6, "min": 4096, "max": 28672},
        "output": {"median": 640, "sigma": 0.5, "min": 256, "max": 1536},
        "shared_prefix": 0, "ramp_s": 12, "drain_s": 0, "check_sample": 3}


PEAKS = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}


def test_the_roofline_counts_follow_the_configuration():
    """ISSUE 48's arithmetic, from the configuration's own keys."""
    from benchmark.roofline import sparse_moe as R

    cfg = _config()
    p, b = R.params(cfg), cfg["bytes"]
    assert p["attention"] == 18874368 == b["attention_params"]
    assert p["indexer"] == 2260992 == b["indexer_params"]
    assert p["router"] == 262144 == b["router_params"]
    assert p["expert"] == 4718592 == b["expert_params"]
    beside = p["attention"] + p["indexer"] + p["router"]
    assert beside == 21397504 == b["layer_params_beside_experts"]
    assert beside + 16 * p["expert"] == 96894976 \
        == b["layer_params_with_16_held"]
    table = 2 * 18992 * 2048
    assert table == 77791232 == b["embedding_and_head_params"]
    assert 8 * 96894976 + table == 852951040 == b["weights_params"]
    assert round(2 * 852951040 / 1e9, 3) == b["weights_gb_bf16_reckoned"]
    t = R.token_bytes(cfg)
    assert (t["kv"], t["index"]) == (2048, 128)
    assert 8 * (t["kv"] + t["index"]) == 17408 == b["arena_bytes_per_token"]
    assert round(524288 * 17408 / 1e9, 3) == b["arena_gb_32768_blocks"]
    s = R.sizes(cfg)
    assert (s["held"], s["routed"], s["k"], s["layers"]) == (16, 128, 8, 8)
    assert (s["index_heads"], s["index_dim"], s["topk"]) == (16, 64, 2048)
    # the pairs: the index scores' are square, the attention's linear
    assert R.pairs(100) == R.kept_pairs(100, 2048) == 5050
    assert R.kept_pairs(2048, 2048) == R.pairs(2048)
    assert R.kept_pairs(28672, 2048) == 2048 * 2049 / 2 + 26624 * 2048
    assert R.kept_pairs(28672, 2048) / R.pairs(28672) < 0.14
    # at 28,672 positions one float32 [s, s] array would be 3.3 GB
    assert round(28672 ** 2 * 4 / 1e9, 1) == 3.3
    # a token meets 0.21 G parameters here with one expert a layer
    assert 0.20e9 < R.active_params_per_token(cfg, 1.0) < 0.22e9
    # a 12,288-token prefill: 9.4 TFLOP: linears 5.1, index scores 1.2,
    # attention over the kept 3.0
    whole = R.prefill_flops(cfg, 12288, 1.0)
    assert 9.2e12 < whole < 9.6e12
    assert 1.1e12 < R.index_flops(cfg, 12288) < 1.3e12
    assert 2.9e12 < R.attention_flops(cfg, 12288) < 3.1e12
    # a decode step at 32 lanes of 14,500 live tokens (ISSUE 48): index
    # keys 59 MB and chosen rows 134 MB a layer; with the weights, 14
    # touched experts a layer and the head 3.0 GB, 3.7 ms
    rows = {"live": 8 * 32 * 14500, "read": 8 * 32 * 2048,
            "index": 8 * 32 * 14500}
    sparse = R.sparse_decode_least(cfg, rows, 32, PEAKS)
    assert round(sparse["parts"]["index_keys"] / 8 / 1e6) == 59
    assert round(sparse["parts"]["kv_rows"] / 8 / 1e6) == 134
    assert sparse["parts"]["queries_and_outputs"] < 5e6
    step = R.decode_step_least(cfg, 1.706e9, rows, 32, 8 * 14, 1.0, PEAKS)
    assert step["bound"] == "memory" and 2.9e9 < step["bytes"] < 3.1e9
    assert 0.0035 < step["seconds"] < 0.0039
    assert set(step["parts"]) == {"weights", "experts", "index_keys",
                                  "kv_rows"}
    assert abs(step["parts"]["weights"] - (1.706e9 - 18992 * 2048 * 2
                                           - 128 * p["expert"] * 2)) < 1
    # a dense read of the same lanes would add 6.5 GB
    assert round((rows["live"] - rows["read"]) * t["kv"] / 1e9, 1) == 6.5
    ffn = R.expert_ffn_least(cfg, 8 * 14, PEAKS)
    assert 0.0012 < ffn["seconds"] < 0.0014


def _run_record(counters, trace=None, polls=()):
    cell = types.SimpleNamespace(config=_config())
    return {"cell": cell, "counters": counters, "trace": trace,
            "polls": list(polls),
            "program": {"weight_bytes": 1.706e9, "kv_bytes_per_token": 17408,
                        "block_size": 16, "num_slots": 32},
            "peaks": PEAKS}


_COUNTERS = {"moe.assignments": 256 * 320, "moe.local_assignments": 32 * 320,
             "moe.experts_touched": 14 * 320, "moe.layer_steps": 320,
             "sparse.layer_steps": 320,
             "sparse.rows_live": 40 * 8 * 32 * 14500,
             "sparse.index_rows_scored": 40 * 8 * 32 * 14500,
             "sparse.rows_read": 40 * 8 * 32 * 2048}


def test_the_counter_readers():
    from benchmark.layer_metrics import sparse_kv_rows_read_pct as share
    from benchmark.roofline import sparse_moe as R

    run = _run_record(_COUNTERS)
    assert abs(share.read(run) - 100 * 2048 / 14500) < 1e-9
    assert R.steps_counted(run) == 40
    assert R.local_picks(run) == 8 * 32 / 256
    assert R.experts_touched_per_step(run) == 8 * 14.0
    assert R.rows_per_step(run) == {"live": 8 * 32 * 14500,
                                    "read": 8 * 32 * 2048,
                                    "index": 8 * 32 * 14500}
    # a program without the counters (the parent's) has nothing to read
    assert share.read(_run_record({})) is None
    assert share.read(_run_record({"moe.assignments": 5})) is None
    assert R.local_picks(_run_record({})) is None
    assert R.rows_per_step(_run_record({})) is None
    assert R.rows_per_step(_run_record({"moe.layer_steps": 4})) is None


def test_the_trace_readers_on_a_made_trace():
    """``jit_step`` of 21 ms holding, a layer, the index kernel (0.5 ms),
    the sort of the ``[32, 30720]`` scores with a copy (0.6 + 0.1 ms), two
    gathers and the attention (0.3, 0.3, 0.2 ms) and two ``gmm`` calls
    (0.1 ms each, which no reader here reads: PERF.md, PR 48); one prefill of
    12,288 positions in 0.9 s: each reader's
    share follows by hand."""
    from benchmark.layer_metrics import index_select_ms_per_step as select
    from benchmark.layer_metrics import sparse_decode_roofline as sparse
    from benchmark.layer_metrics import \
        sparse_moe_decode_step_roofline as step
    from benchmark.layer_metrics import sparse_moe_prefill_mfu_pct as mfu
    from benchmark.roofline import sparse_moe as R

    ops, at = [], 1.0005
    for i in range(8):
        for text, d in (
                (f"%paged_index_scores.{i} = f32[32,8,2,2048]{{3,2,1,0}} "
                 "custom-call(s32[32,1920]{1,0} %bt, ...)", 0.0005),
                (f"%sort.{i} = (f32[32,30720]{{1,0:T(8,128)S(1)}}, "
                 "s32[32,30720]{1,0:T(8,128)S(1)}) sort(%copy, %iota), "
                 "dimensions={1}", 0.0006),
                (f"%copy.{i} = f32[32,30720]{{1,0}} copy(%slice)", 0.0001),
                (f"%fusion.{i} = bf16[65536,4,128]{{2,1,0}} fusion(%k)",
                 0.0003),
                (f"%fusion.{20 + i} = bf16[65536,4,128]{{2,1,0}} "
                 "fusion(%v)", 0.0003),
                (f"%fusion.{40 + i} = (bf16[32,4,8]{{2,1,0}}, "
                 "bf16[32,4,8,1,2048]{4,3,2,1,0}) fusion(%q)", 0.0002),
                (f"%gmm.{i} = bf16[64,1536]{{1,0}} custom-call(...)", 0.0001),
                (f"%gmm.{20 + i} = bf16[64,2048]{{1,0}} custom-call(...)",
                 0.0001),
                # the residual stream is [lanes, hidden] = [32, 2048]: no
                # part of any of the three
                (f"%fusion.{60 + i} = f32[32,2048]{{1,0}} fusion(%x)",
                 0.0002)):
            ops.append((text, at, d))
            at += d + 1e-5
    ops.append(("%sparse_prefill_flash.1 = bf16[32,12288,128]{2,1,0} "
                "custom-call(bf16[32,12288,128]{2,1,0} %q, ...)", 2.1, 0.02))
    # an index kernel outside any traced step is no part of the share
    ops.append(("%paged_index_scores.9 = f32[32,8,2,2048]{3,2,1,0} "
                "custom-call(...)", 3.2, 0.001))
    dev = {"modules": [("jit_step(1)", 1.0, 0.021),
                       ("jit_prefill(2)", 2.0, 0.9)], "ops": ops}
    tr = types.SimpleNamespace(window=(0.5, 3.5), devices={0: dev},
                               window_s=3.0)
    polls = [{"arena.blocks_total": 32767, "arena.blocks_free": 3767,
              "slots.active": 30}]
    run = _run_record(_COUNTERS, tr, polls)
    cfg = run["cell"].config
    steps, seconds = R.step_scope_seconds(run)
    assert steps == 1
    assert abs(seconds["indexer"] - 8 * 0.0005) < 1e-9
    assert abs(seconds["select"] - 8 * 0.0007) < 1e-9
    assert abs(seconds["sparse_attn"] - 8 * 0.0008) < 1e-9
    assert abs(select.read(run) - 5.6) < 1e-6
    rows = R.rows_per_step(run)
    want = R.sparse_decode_least(cfg, rows, 30, PEAKS)["seconds"]
    assert abs(sparse.read(run) - 100 * want / 0.016) < 1e-6
    assert 0 < sparse.read(run) < 100
    (positions, seconds_), = R.traced_prefills(run)
    assert positions == 12288 and abs(seconds_ - 0.9) < 1e-9
    need = R.prefill_flops(cfg, 12288, 1.0)
    assert abs(mfu.read(run) - 100 * need / (0.9 * 197e12)) < 1e-6
    assert 0 < mfu.read(run) < 100
    # a stretch that holds no prefill (this cell's as a rule, 18-23 s
    # into the window): nothing to read, whatever the host's clocks say of
    # the window's prefills; a device's time comes from its trace alone
    none = types.SimpleNamespace(window=(0.5, 1.5), devices={0: dev},
                                 window_s=1.0)
    waited = dict(_COUNTERS, **{"prefill.calls": 30,
                                "prefill.positions_computed": 30 * 16384,
                                "time_us.prefill.wait": 30 * 700000})
    import benchmark.harness.readers as readers

    was = readers.T.module_durations
    readers.T.module_durations = lambda tr, module: [0.020]
    try:
        want = R.decode_step_least(cfg, 1.706e9, rows, 30, 112.0, 1.0,
                                   PEAKS)["seconds"]
        assert abs(step.read(run) - 100 * want / 0.020) < 1e-6
        assert 0 < step.read(run) < 100
        assert R.traced_prefills(_run_record(waited, none, polls)) is None
        assert mfu.read(_run_record(waited, none, polls)) is None
        assert mfu.read(_run_record(_COUNTERS, none, polls)) is None
    finally:
        readers.T.module_durations = was
    # nothing traced, or a program that counts nothing or runs no such
    # kernel (the parent's): nothing to read, and nothing raised
    for reader in (step, sparse, select, mfu):
        assert reader.read(_run_record(_COUNTERS)) is None
    for reader in (step, sparse, mfu):
        assert reader.read(_run_record({}, tr, polls)) is None
    bare = types.SimpleNamespace(window=(0.5, 3.5), window_s=3.0, devices={
        0: {"modules": dev["modules"],
            "ops": [o for o in ops if "paged_index" not in o[0]
                    and "sparse_prefill" not in o[0]]}})
    for reader in (sparse, select, mfu):
        assert reader.read(_run_record(_COUNTERS, bare, polls)) is None
