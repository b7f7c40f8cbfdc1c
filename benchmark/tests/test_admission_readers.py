"""The readers of what an admission costs (PR 37: the children of the
``prefill`` phase, padded positions, blocked lanes, the empty device by its
cause, ``decode.prepare``'s uploads, the turn): each against a hand-made
``run``, each entry as ``BENCHMARK.json`` has it, then all nine in one
tiny traced run through the harness.

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests -q -p no:cacheprovider
"""
import json
import os
import sys
import time

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import run_tiny  # noqa: E402

from benchmark.harness import spec  # noqa: E402

SERVING_CELLS = ["serve-batch-long", "serve-doc-hybrid",
                 "serve-reason-flash", "serve-doc-latent-moe"]
#: name -> (layer, unit, better)
ENTRIES = {
    "prefill_padding_pct": ("engine", "%", "lower"),
    "prefill_host_ms_per_call": ("engine", "ms", "lower"),
    "prefill_wait_ms_per_call": ("engine", "ms", "lower"),
    "decode_lanes_blocked_pct": ("scheduler", "%", "lower"),
    "decode_lanes_active_pct": ("scheduler", "%", "higher"),
    "device_empty_pct": ("engine", "%", "lower"),
    "restart_ms_per_admission": ("engine", "ms", "lower"),
    "decode_upload_kb_per_step": ("engine", "kB", "lower"),
    "turn_ms_per_step": ("scheduler", "ms", "lower"),
}
with open(os.path.join(spec.ROOT, "BENCHMARK.json")) as _f:
    #: the entries as the repo's BENCHMARK.json has them
    METRICS = {m["name"]: m for m in json.load(_f)["per_layer"]
               if m["name"] in ENTRIES}


def _reader(name):
    return spec.load_module(os.path.join(
        spec.BENCH, "layer_metrics", name + ".py")).read


def _run(counters, lanes=32):
    return {"seconds": 10.0, "counters": counters, "hists": {},
            "trace": None, "program": {"num_slots": lanes}}


#: a stretch of 10 s of the pump's own clock (0.2 unlocked, 9.8 locked) at
#: 32 lanes: 700 decode steps that ran 21,000 lanes; 20 admissions in 25
#: compiled calls (one chunked) that computed 30,720 positions for 26,112
#: real tokens in 2.0 s, of which 1.5 s waiting for the device, while 28
#: lanes stood still on average; the device stood empty for 0.12 s before
#: restarts, 0.03 before prefills, and 1.5 s with no work at all
COUNTERS = {
    "engine.steps": 700, "engine.lane_steps": 21_000, "engine.admits": 20,
    "engine.step_uploads": 660, "engine.step_upload_bytes": 57_344_000,
    "engine.restarts.admit": 14, "engine.restarts.retire": 1,
    "tokens.prefill": 26_112, "prefill.calls": 25,
    "prefill.positions_computed": 30_720, "prefill.upload_bytes": 4_000_000,
    "prefill.lane_us_blocked": 56_000_000,
    "time_us.pump.unlocked": 200_000, "time_us.sched.step": 9_800_000,
    "time_us.prefill": 2_000_000, "time_us.prefill.wait": 1_500_000,
    "time_us.prefill.setup": 100_000, "time_us.prefill.upload": 250_000,
    "time_us.prefill.dispatch": 50_000, "time_us.prefill.finish": 90_000,
    "time_us.device.empty.restart": 120_000,
    "time_us.device.empty.admit": 30_000,
    "time_us.device.empty.idle": 1_500_000,
}
EXPECTED = {
    "prefill_padding_pct": 15.0,            # 1 - 26,112 / 30,720
    "prefill_host_ms_per_call": 20.0,       # (2.0 - 1.5) s / 25
    "prefill_wait_ms_per_call": 60.0,       # 1.5 s / 25
    "decode_lanes_blocked_pct": 17.5,       # 56 lane-s of 32 x 10
    "decode_lanes_active_pct": 93.75,       # 21,000 of 700 x 32
    "device_empty_pct": 1.5,                # 0.15 s of 10, `idle` left out
    "restart_ms_per_admission": 6.0,        # 0.12 s / 20
    "decode_upload_kb_per_step": 81.92,     # 57,344,000 B / 700 / 1e3
    "turn_ms_per_step": 7.8 / 0.7,          # (9.8 - 2.0) s / 700
}
#: the counters a reader cannot do without: the parent has none of them
#: but ``turn_ms_per_step``'s, which every program with phases has
NEEDS = {
    "prefill_padding_pct": "prefill.positions_computed",
    "prefill_host_ms_per_call": "time_us.prefill.wait",
    "prefill_wait_ms_per_call": "time_us.prefill.wait",
    "decode_lanes_blocked_pct": "prefill.lane_us_blocked",
    "decode_lanes_active_pct": "engine.lane_steps",
    "device_empty_pct": "time_us.device.empty.",
    "restart_ms_per_admission": "time_us.device.empty.restart",
    "decode_upload_kb_per_step": "engine.step_upload_bytes",
    "turn_ms_per_step": "time_us.sched.step",
}
#: what the parent commit's counters hold of the above
PARENT = {k: v for k, v in COUNTERS.items()
          if not k.startswith(("time_us.prefill.", "time_us.device.",
                               "prefill.", "engine.restarts.",
                               "engine.lane_steps",
                               "engine.step_upload_bytes"))}


def test_benchmark_json_lists_the_nine_with_the_four_serving_cells():
    assert sorted(METRICS) == sorted(ENTRIES)
    for name, (layer, unit, better) in ENTRIES.items():
        m = METRICS[name]
        assert (m["layer"], m["unit"], m["better"]) == (layer, unit, better)
        assert m["source"] == "program_counter", name
        assert m["moves"] == "serve_tokens_per_s", name
        assert m["workloads"] == SERVING_CELLS, name
        assert sorted(m) == ["better", "layer", "moves", "name", "source",
                             "unit", "workloads"]


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader_arithmetic_on_a_hand_made_run(name):
    assert _reader(name)(_run(dict(COUNTERS))) == \
        pytest.approx(EXPECTED[name], rel=1e-12)


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_without_its_counter_a_reader_gives_nothing(name):
    """On the parent commit eight of the nine find nothing to read and do
    not raise; ``turn_ms_per_step`` reads counters the parent has."""
    read = _reader(name)
    missing = {k: v for k, v in COUNTERS.items()
               if not k.startswith(NEEDS[name])}
    assert read(_run(missing)) is None
    assert read(_run({})) is None
    if name == "turn_ms_per_step":
        assert read(_run(dict(PARENT))) == pytest.approx(7.8 / 0.7)
    else:
        assert read(_run(dict(PARENT))) is None


def test_nothing_moved_gives_nothing_and_a_missing_cause_counts_zero():
    still = dict(COUNTERS, **{"engine.steps": 0, "prefill.calls": 0,
                              "engine.admits": 0,
                              "prefill.positions_computed": 0,
                              "time_us.pump.unlocked": 0,
                              "time_us.sched.step": 0})
    for name in EXPECTED:
        assert _reader(name)(_run(still)) is None, name
    # a pump that never ran a synchronous turn has no `.sync` counter, and
    # one that only ever restarted has no `.admit`
    only = {k: v for k, v in COUNTERS.items()
            if k != "time_us.device.empty.admit"}
    assert _reader("device_empty_pct")(_run(only)) == pytest.approx(1.2)
    # a program that does not say how many lanes it has
    assert _reader("decode_lanes_active_pct")(_run(dict(COUNTERS), 0)) is None
    assert _reader("decode_lanes_blocked_pct")(_run(dict(COUNTERS), 0)) is None


def test_a_tiny_traced_run_reports_all_nine():
    """``tiny-batch`` with the nine added in memory to the tiny
    ``BENCHMARK.json``, traced, through ``run.run_cell``: a closed loop
    that admits, restarts and retires all along, so every reader finds
    something to read."""
    from benchmark import run as R

    R.environment()
    from benchmark.harness import common

    with open(os.path.join(run_tiny.DATA, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["per_layer"] += [dict(m, workloads=["tiny-batch"])
                           for m in METRICS.values()]
    cell = spec.Cell("tiny-batch", bench=bench, data_dir=run_tiny.DATA)
    device = dict(common.device_record(), kind="TPU v5 lite")
    out = json.loads(R.run_cell(cell, 2 ** 31 + 37, 6.0, True, device,
                                t_proc=time.monotonic()))
    assert out["correct"] and out["failed"] == 0
    got = {k: v["value"] for k, v in out["metrics"].items()}
    for name in ENTRIES:
        assert name in got, (name, sorted(got))
    assert 0.0 <= got["prefill_padding_pct"] < 50.0
    assert 0.0 < got["decode_lanes_active_pct"] <= 100.0
    assert 0.0 <= got["decode_lanes_blocked_pct"] < 100.0
    assert 0.0 < got["device_empty_pct"] < 100.0
    assert got["prefill_host_ms_per_call"] > 0.0
    assert got["prefill_wait_ms_per_call"] > 0.0
    assert got["turn_ms_per_step"] > 0.0
    assert got["decode_upload_kb_per_step"] > 0.0
    assert got["restart_ms_per_admission"] > 0.0
    assert "lanes_busy_pct" in got  # what the cell reported before stays
