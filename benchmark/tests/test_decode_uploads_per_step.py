"""The reader of ``engine.step_uploads`` (the host-to-device transfers the
decode step's preparation makes): against hand-made ``run``s, then in one
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

NAME = "decode_uploads_per_step"
with open(os.path.join(spec.ROOT, "BENCHMARK.json")) as _f:
    #: the entry as the repo's BENCHMARK.json has it
    METRIC = next(m for m in json.load(_f)["per_layer"] if m["name"] == NAME)
read = spec.load_module(
    os.path.join(spec.BENCH, "layer_metrics", NAME + ".py")).read


def _run(counters):
    return {"seconds": 10.0, "counters": counters, "hists": {},
            "trace": None}


@pytest.mark.parametrize("counters, expected", [
    # 100 steps: the block table on 87 of them, the slot state on 12
    ({"engine.steps": 100, "engine.step_uploads": 99}, 0.99),
    # the counter is there and did not move: every step ran on what the
    # device held
    ({"engine.steps": 100, "engine.step_uploads": 0}, 0.0),
    # the parent commit counts no uploads: nothing to read, no raise
    ({"engine.steps": 100, "tokens.generated": 3200}, None),
    # no decode step in the window
    ({"engine.steps": 0, "engine.step_uploads": 3}, None),
    ({}, None),
])
def test_reader_arithmetic_on_a_hand_made_run(counters, expected):
    assert read(_run(counters)) == expected


def test_the_entry_moves_the_serving_cells_metric():
    assert METRIC["layer"] == "engine" and METRIC["better"] == "lower"
    assert METRIC["source"] == "program_counter"
    assert METRIC["moves"] == "serve_tokens_per_s"
    assert METRIC["workloads"] == ["serve-batch-long"]


def test_a_tiny_traced_run_reports_it_under_one_upload_a_step_or_so():
    """``tiny-batch`` with the metric added in memory to the tiny
    ``BENCHMARK.json``: the program counts its uploads, and a step sends
    at most the block table and the packed slot state."""
    from benchmark import run as R

    R.environment()
    from benchmark.harness import common

    with open(os.path.join(run_tiny.DATA, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["per_layer"].append(dict(METRIC, workloads=["tiny-batch"]))
    cell = spec.Cell("tiny-batch", bench=bench, data_dir=run_tiny.DATA)
    device = dict(common.device_record(), kind="TPU v5 lite")
    out = json.loads(R.run_cell(cell, 2 ** 31 + 25, 6.0, True, device,
                                t_proc=time.monotonic()))
    assert out["correct"] and out["failed"] == 0
    assert 0.0 < out["metrics"][NAME]["value"] <= 2.0
