"""A tiny hybrid (linear + full attention) cell through the harness: sound it
is correct; with a lane's recurrent state NOT reset at admission, and as its
own control (int8 weights, int8 KV), it is not.

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests -q -p no:cacheprovider

The cell has files of its own under ``tests/data`` (``BENCHMARK.hybrid.json``,
``configs/tiny-hybrid.json``, ``cells/tiny-hybrid.json``) and the tiny
closed-loop mix that is there. Its limits were set as the chip's are: above
the sound runs, below the control (readings in ``cells/tiny-hybrid.json``).
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

    with open(os.path.join(run_tiny.DATA, "BENCHMARK.hybrid.json")) as f:
        bench = json.load(f)
    return spec.Cell("tiny-hybrid", bench=bench, overrides=overrides,
                     data_dir=run_tiny.DATA)


def _run(seed, overrides=None, trace=False, seconds=3.0):
    from benchmark import run as R

    R.environment()
    from benchmark.harness import common

    device = dict(common.device_record(), kind="TPU v5 lite")
    return json.loads(R.run_cell(_cell(overrides), seed, seconds, trace,
                                 device, t_proc=time.monotonic()))


def test_sound_is_correct_and_a_lane_not_reset_at_admission_is_not(
        monkeypatch):
    sound = _run(2 ** 31 + 61)
    assert sound["correct"] and sound["failed"] == 0
    from paddle_tpu.serving import engine as E

    # the admitted request starts from what the lane's last tenant left
    monkeypatch.setattr(
        E._SlotStatePrefillView, "read",
        lambda self: tuple(
            __import__("jax").lax.dynamic_slice_in_dim(a, self.slot, 1, 0)
            for a in self.entry))
    broken = _run(2 ** 31 + 61)
    assert broken["correct"] is False and broken["failed"] == 0


@pytest.mark.parametrize("seed", [71, 72, 2 ** 31 + 73])
def test_the_control_is_not_correct(seed):
    out = _run(seed, overrides=_cell().config["control"])
    assert out["correct"] is False and out["failed"] == 0


def test_a_traced_run_reports_the_new_per_layer_metrics():
    """On the CPU the device plane is empty, so the trace's readers give
    nothing and the line leaves them out; what the counters feed is there."""
    out = _run(2 ** 31 + 62, trace=True)
    assert out["correct"]
    assert out["metrics"]["state_store_gb"]["value"] > 0
    assert "lanes_busy_pct" in out["metrics"]


def test_a_traced_prefill_counts_at_the_bucket_its_operations_show():
    """The prefill readers take each traced ``jit_prefill``'s positions from
    the ``[positions, hidden]`` shapes of the operations inside it, once per
    program; an execution the stretch cut is left out."""
    from benchmark.harness import trace as T
    from benchmark.roofline import hybrid_prefill as H

    def op(shape, at):
        return (f"%fusion.1 = bf16[{shape}]{{1,0:T(8,128)(2,1)}} fusion(%p)",
                at, 0.001)

    mods = [("jit_prefill(11)", 1.0, 0.2), ("jit_step(5)", 1.3, 0.05),
            ("jit_prefill(22)", 2.0, 0.3), ("jit_prefill(11)", 3.0, 0.2),
            ("jit_prefill(22)", 4.9, 0.3)]
    ops = [op("2048,64", 1.01), op("2048,64", 1.05), op("2048,128", 1.07),
           op("16,64", 1.31), op("3072,64", 2.1), op("1,3072,64", 2.2)]
    tr = T.Trace({0: {"ops": ops, "modules": mods}}, [], (0.0, 5.0))
    cell = type("Cell", (), {"config": dict(_cell().config, hidden_size=64)})
    got = H.traced_prefills({"trace": tr, "cell": cell})
    assert got == [(2048, 0.2), (3072, 0.3), (2048, 0.2)]
    assert H.traced_prefills({"trace": None, "cell": cell}) is None
    assert H.flops(cell.config, 128) > 2 * H.flops(cell.config, 64) > 0
