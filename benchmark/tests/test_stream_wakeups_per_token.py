"""The reader of ``gateway.stream_wakeups`` (how often a stream consumer
came back from its wait): against hand-made ``run``s, then in one tiny
traced run through the harness.

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

NAME = "stream_wakeups_per_token"
with open(os.path.join(spec.ROOT, "BENCHMARK.json")) as _f:
    #: the entry as the repo's BENCHMARK.json has it
    METRIC = next(m for m in json.load(_f)["per_layer"] if m["name"] == NAME)
read = spec.load_module(
    os.path.join(spec.BENCH, "layer_metrics", NAME + ".py")).read


def _run(counters):
    return {"seconds": 10.0, "counters": counters, "hists": {},
            "trace": None}


@pytest.mark.parametrize("counters, expected", [
    # a wake-up a token, a few for attaches, finishes and the backstop
    ({"tokens.generated": 3200, "gateway.stream_wakeups": 3360}, 1.05),
    # sixty pollers at a thousand a second against 640 tokens a second
    ({"tokens.generated": 6400, "gateway.stream_wakeups": 600000}, 93.75),
    # the counter is there and did not move: nobody streamed
    ({"tokens.generated": 100, "gateway.stream_wakeups": 0}, 0.0),
    # the parent commit counts no wake-ups: nothing to read, no raise
    ({"tokens.generated": 3200, "engine.steps": 100}, None),
    # no token in the window
    ({"tokens.generated": 0, "gateway.stream_wakeups": 7}, None),
    ({}, None),
])
def test_reader_arithmetic_on_a_hand_made_run(counters, expected):
    assert read(_run(counters)) == expected


def test_the_entry_moves_the_serving_cells_metric():
    assert METRIC["layer"] == "gateway" and METRIC["better"] == "lower"
    assert METRIC["source"] == "program_counter"
    assert METRIC["moves"] == "serve_tokens_per_s"
    assert set(METRIC["workloads"]) >= {"serve-batch-long",
                                        "serve-doc-hybrid"}


def test_a_tiny_traced_run_reports_about_a_wakeup_a_token():
    """``tiny-batch`` with the metric added in memory to the tiny
    ``BENCHMARK.json``: the load generator's SSE clients are the
    consumers, and each comes back from its wait about once a token."""
    from benchmark import run as R

    R.environment()
    from benchmark.harness import common

    with open(os.path.join(run_tiny.DATA, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["per_layer"].append(dict(METRIC, workloads=["tiny-batch"]))
    cell = spec.Cell("tiny-batch", bench=bench, data_dir=run_tiny.DATA)
    device = dict(common.device_record(), kind="TPU v5 lite")
    out = json.loads(R.run_cell(cell, 2 ** 31 + 30, 6.0, True, device,
                                t_proc=time.monotonic()))
    assert out["correct"] and out["failed"] == 0
    assert 0.0 < out["metrics"][NAME]["value"] <= 3.0
