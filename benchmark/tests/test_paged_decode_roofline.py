"""The reader of the paged decode kernel's share of its roofline, against
hand-made ``run``s: the kernel's calls are found by the ``pallas_call``'s
name in the HLO instruction's own name, a program that does not run the
kernel (the parent commit, the gather route) reads nothing and does not
raise.

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests -q -p no:cacheprovider
"""
import json
import os

import pytest

from benchmark.harness import spec
from benchmark.harness.trace import Trace

NAME = "paged_decode_roofline"
with open(os.path.join(spec.ROOT, "BENCHMARK.json")) as _f:
    #: the entry as the repo's BENCHMARK.json has it
    METRIC = next(m for m in json.load(_f)["per_layer"] if m["name"] == NAME)
read = spec.load_module(
    os.path.join(spec.BENCH, "layer_metrics", NAME + ".py")).read

KERNEL = ("%paged_decode.7 = bf16[32,16,128]{2,1,0} custom-call(...), "
          "custom_call_target=\"tpu_custom_call\"")
#: names the kernel only as an operand: not the kernel's own event
USER = "%fusion.3 = bf16[32,2048]{1,0} fusion(%paged_decode.7), kind=kLoop"
GATHER = "%fusion.48 = bf16[4096,16,16,128]{3,2,1,0} fusion(...), kind=kLoop"


def _run(ops, steps=2, polls=True):
    """``steps`` whole ``jit_step`` executions of 20 ms in a 1 s window;
    1,000 blocks of 16 tokens in use at 196,608 B a token: 3.146 GB live,
    3.841 ms at 819 GB/s."""
    mods = [("jit_step(123)", 0.1 + 0.1 * i, 0.02) for i in range(steps)]
    tr = Trace({"tpu0": {"ops": ops, "modules": mods}}, [], (0.0, 1.0))
    rows = [{"arena.blocks_total": 2730, "arena.blocks_free": 1730,
             "slots.active": 32}] if polls else []
    return {"trace": tr, "polls": rows, "peaks": {"hbm_bytes_per_s": 819e9},
            "program": {"kv_bytes_per_token": 196608, "block_size": 16}}


def test_two_steps_of_two_calls_each():
    ops = [(KERNEL, 0.1 + 0.1 * (i // 2) + 0.005 * (i % 2), 0.004)
           for i in range(4)] + [(USER, 0.15, 0.01), (GATHER, 0.16, 0.01)]
    least = 196608 * 1000 * 16 / 819e9
    assert read(_run(ops)) == pytest.approx(100.0 * least / 0.008)


@pytest.mark.parametrize("run", [
    _run([(GATHER, 0.1, 0.01), (USER, 0.2, 0.01)]),  # the gather route
    _run([(KERNEL, 0.1, 0.004)], steps=0),           # no whole step traced
    _run([(KERNEL, 0.1, 0.004)], polls=False),       # no poll of the arena
    dict(_run([]), trace=None),                      # an untraced run
], ids=["no-kernel-event", "no-step", "no-polls", "untraced"])
def test_nothing_to_read_is_none_not_an_error(run):
    assert read(run) is None


def test_the_entry_lists_both_serving_cells():
    assert METRIC == {
        "name": NAME, "unit": "%", "better": "higher",
        "source": "device_trace", "layer": "kernels",
        "moves": "serve_tokens_per_s",
        "workloads": ["serve-batch-long", "serve-doc-hybrid"]}
