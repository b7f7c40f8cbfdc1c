"""The reader of ``engine.steps_run_ahead`` (decode steps dispatched while
the step before them was still unread): against hand-made ``run``s, then in
one tiny traced run through the harness.

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

NAME = "decode_run_ahead_pct"
with open(os.path.join(spec.ROOT, "BENCHMARK.json")) as _f:
    #: the entry as the repo's BENCHMARK.json has it
    METRIC = next(m for m in json.load(_f)["per_layer"] if m["name"] == NAME)
read = spec.load_module(
    os.path.join(spec.BENCH, "layer_metrics", NAME + ".py")).read


def _run(counters):
    return {"seconds": 10.0, "counters": counters, "hists": {},
            "trace": None}


@pytest.mark.parametrize("counters, expected", [
    # 3,400 steps of which 14 a second, 630, started from the mirrors
    # after an admission or a retirement
    ({"engine.steps": 3400, "engine.steps_run_ahead": 2770}, 81.47058823529412),
    # every turn synchronous (a constrained request ran all along): the
    # counter is there and did not move
    ({"engine.steps": 500, "engine.steps_run_ahead": 0}, 0.0),
    # a window that read exactly the steps that were dispatched ahead
    ({"engine.steps": 200, "engine.steps_run_ahead": 200}, 100.0),
    # the parent commit dispatches nothing ahead and counts nothing:
    # nothing to read, no raise
    ({"engine.steps": 3400, "engine.step_uploads": 3300}, None),
    # no decode step in the window
    ({"engine.steps": 0, "engine.steps_run_ahead": 1}, None),
    ({}, None),
])
def test_reader_arithmetic_on_a_hand_made_run(counters, expected):
    assert read(_run(counters)) == expected


def test_the_entry_moves_the_serving_cells_metric():
    assert METRIC["layer"] == "engine" and METRIC["better"] == "higher"
    assert METRIC["unit"] == "%" and METRIC["source"] == "program_counter"
    assert METRIC["moves"] == "serve_tokens_per_s"
    assert set(METRIC["workloads"]) >= {
        "serve-batch-long", "serve-doc-hybrid", "serve-reason-flash"}


def test_a_tiny_traced_run_reports_most_steps_dispatched_ahead():
    """``tiny-batch`` with the metric added in memory to the tiny
    ``BENCHMARK.json``: the closed loop keeps the lanes full, so the pump
    runs ahead on every turn but the ones an admission or a retirement
    made start from the mirrors."""
    from benchmark import run as R

    R.environment()
    from benchmark.harness import common

    with open(os.path.join(run_tiny.DATA, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["per_layer"].append(dict(METRIC, workloads=["tiny-batch"]))
    cell = spec.Cell("tiny-batch", bench=bench, data_dir=run_tiny.DATA)
    device = dict(common.device_record(), kind="TPU v5 lite")
    out = json.loads(R.run_cell(cell, 2 ** 31 + 32, 6.0, True, device,
                                t_proc=time.monotonic()))
    assert out["correct"] and out["failed"] == 0
    assert 30.0 < out["metrics"][NAME]["value"] <= 100.0
