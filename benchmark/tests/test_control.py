"""The output check's control comes out NOT correct, at a size a test run
can hold (the readings at the cells' own sizes, on the chip, are in PERF.md
section 2 and ``benchmark/records/limits.md``).

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests -q -p no:cacheprovider

The control is the cell computed one precision lower than its configuration
states. Training: the plain reference put in the program's place with every
matrix product's operands rounded one precision down. The tiny training
cell states float32 (AMP off), so its control is bfloat16; the chip's cells
state bf16 products, so theirs is fp8. (At this size bf16 AMP's own rounding
is too close to fp8's to tell apart by three steps: the test would show
nothing.) Serving: at each position of the same prompts and served tokens, the
token that the fp8 pass of the reference puts first, read under the float32
reference (the check's own statistic; on the chip the engine's int8 paths
serve as the control too, see ``benchmark/control.py``).
"""
import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import run_tiny  # noqa: E402


@pytest.mark.parametrize("seed", [31, 32, 2 ** 31 + 33])
def test_training_control_is_not_correct(seed):
    from benchmark import control

    cell = run_tiny.tiny_cell("tiny-train")
    checks = control.reference_control(
        cell, seed, cell.config["control"]["reference_mode"])
    assert not checks.correct
    failed = {r["check"] for r in checks.rows if not r["ok"]}
    assert "grad_norm_gap" in failed


@pytest.mark.parametrize("seed", [41, 42, 2 ** 31 + 43])
def test_serving_control_is_not_correct(seed):
    from benchmark.harness import traffic
    from benchmark.reference import gpt as ref

    cell = run_tiny.tiny_cell("tiny-chat")
    cfg, lim = cell.config, cell.limits["check"]
    sched = traffic.schedule(cell.mix, seed, 6.0, cfg["vocab_size"])
    rng = np.random.default_rng(seed)
    low = []
    for req in sched["requests"][:12]:
        prompt = traffic.prompt_tokens(sched, req)
        served = rng.integers(0, cfg["vocab_size"],
                              req["max_new_tokens"]).tolist()
        low += ref.control_gaps(seed, cfg, cfg["dtype"], prompt, served,
                                "fp8", **lim["shape"])
    assert len(low) > 100
    assert float(np.mean(low)) > 3 * lim["gap_mean"], np.mean(low)
