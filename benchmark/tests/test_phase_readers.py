"""The readers of the program's phase counters (``time_us.*``, written by
``paddle_tpu.serving.telemetry.phase``): each against a hand-made ``run``,
then all of them in one tiny traced run through the harness.

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests -q -p no:cacheprovider
"""
import json
import os
import sys
import time

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import run_tiny  # noqa: E402

from benchmark.harness import spec, stats  # noqa: E402
from benchmark.harness.trace import Trace  # noqa: E402

with open(os.path.join(spec.ROOT, "BENCHMARK.json")) as _f:
    #: the entries this file is about, as the repo's BENCHMARK.json has them
    PHASE_METRICS = [m for m in json.load(_f)["per_layer"]
                     if m["source"] == "program_span"
                     and m["name"] != "decode_step_host_ms.batch"]
NAMES = [m["name"] for m in PHASE_METRICS]


def _reader(name):
    return spec.load_module(os.path.join(
        spec.BENCH, "layer_metrics", name + ".py")).read


def _run(counters, hists=None, trace=None, seconds=10.0):
    return {"seconds": seconds, "counters": counters, "hists": hists or {},
            "trace": trace}


#: a window of 10 s and 100 decode steps: the pump was unlocked for 0.2 s
#: and in its locked turn for 9.7 s, of which 0.3 s prefill, 8.0 s the
#: decode call (1.0 prepare, 0.5 dispatch, 6.4 wait) and 0.4 s emits
COUNTERS = {"engine.steps": 100, "time_us.pump.unlocked": 200_000,
            "time_us.sched.step": 9_700_000, "time_us.sched.admit": 350_000,
            "time_us.prefill": 300_000, "time_us.decode_step": 8_000_000,
            "time_us.decode.prepare": 1_000_000,
            "time_us.decode.dispatch": 500_000,
            "time_us.decode.wait": 6_400_000, "time_us.sched.emit": 400_000}
#: one chip whose ``jit_step`` ran twice for 50 ms inside the window
TRACE = Trace({0: {"ops": [], "modules": [("jit_step(1)", 1.0, 0.05),
                                          ("jit_step(1)", 1.1, 0.05)]}},
              [], (0.0, 5.0))
EXPECTED = {"pump_unlocked_ms_per_step": 2.0,
            "sched_host_ms_per_step": 10.0,  # 9.7 - 0.3 - 8.0 - 0.4 s
            "emit_ms_per_step": 4.0,
            "prefill_wall_pct": 3.0,
            "decode_prepare_ms": 10.0,
            "decode_dispatch_ms": 5.0,
            "decode_wait_ms": 64.0,
            "decode_sync_excess_ms": 19.0,  # 5 + 64 - 50
            "pump_unaccounted_pct": 1.0}  # 10 - 0.2 - 9.7 s of 10


def test_benchmark_json_lists_the_ten_phase_metrics():
    assert sorted(NAMES) == sorted(list(EXPECTED) +
                                   ["submit_lock_wait_p95_ms"])


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader_arithmetic_on_a_hand_made_run(name):
    got = _reader(name)(_run(dict(COUNTERS), trace=TRACE))
    assert got == pytest.approx(EXPECTED[name], abs=1e-9)


def test_lock_wait_percentile_reads_the_histogram():
    counts = [0] * 97
    counts[stats.hist_bucket_of(0.30)] = 19
    counts[stats.hist_bucket_of(2.0)] = 1
    got = _reader("submit_lock_wait_p95_ms")(
        _run({}, hists={"latency.submit.lock_wait": counts}))
    assert 0.30 * 1e3 / 1.25 <= got <= 0.30 * 1e3 * 1.25


@pytest.mark.parametrize("name", NAMES)
def test_a_program_without_phases_gives_nothing_and_does_not_raise(name):
    """The parent commit has no ``time_us.*`` counter and no
    ``latency.submit.lock_wait``: the reader returns None, traced or not."""
    parent = {"engine.steps": 100, "tokens.generated": 3200}
    assert _reader(name)(_run(parent, trace=TRACE)) is None
    assert _reader(name)(_run(parent)) is None


def test_children_that_never_ran_count_zero_and_no_steps_give_nothing():
    only = {"engine.steps": 10, "time_us.sched.step": 50_000}
    assert _reader("sched_host_ms_per_step")(_run(only)) == 5.0
    idle = dict(COUNTERS, **{"engine.steps": 0})
    assert _reader("decode_wait_ms")(_run(idle)) is None
    assert _reader("decode_sync_excess_ms")(_run(dict(COUNTERS))) is None


def test_flash_share_finds_the_kernels_by_the_programs_names_only():
    """The trace recorded on the chip before the kernels had names
    (``%jvp__.N``): nothing to read. The same events under the names the
    program gives them now: a share of the busy time."""
    from benchmark.harness import trace as T

    read = _reader("flash_time_share_pct")
    tr = T.load(os.path.join(spec.BENCH, "records", "tiny_train.xplane.pb"))
    assert read({"trace": tr}) is None and read({"trace": None}) is None
    for dev in tr.devices.values():
        dev["ops"] = [(n.replace("%jvp__.", "%jvp_flash_fwd_.").replace(
            "%transpose_jvp___.", "%transpose_jvp_flash_bwd_dq__."), a, d)
            for n, a, d in dev["ops"]]
    assert 5.0 < read({"trace": tr}) < 50.0


def test_a_tiny_traced_run_reports_every_phase_metric():
    """``tiny-batch`` with the phase metrics added in memory to the tiny
    ``BENCHMARK.json``, traced, through ``run.run_cell``. On the CPU the
    trace has no device plane, so ``decode_sync_excess_ms`` (which
    subtracts a device time) alone finds nothing to read; the closure
    check must hold at this size too."""
    from benchmark import run as R

    R.environment()
    from benchmark.harness import common

    with open(os.path.join(run_tiny.DATA, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["per_layer"] += [dict(m, workloads=["tiny-batch"])
                           for m in PHASE_METRICS]
    cell = spec.Cell("tiny-batch", bench=bench, data_dir=run_tiny.DATA)
    device = dict(common.device_record(), kind="TPU v5 lite")
    out = json.loads(R.run_cell(cell, 2 ** 31 + 24, 6.0, True, device,
                                t_proc=time.monotonic()))
    assert out["correct"] and out["failed"] == 0
    got = out["metrics"]
    for name in NAMES:
        if name != "decode_sync_excess_ms":
            assert name in got, (name, sorted(got))
    assert abs(got["pump_unaccounted_pct"]["value"]) < 5.0
    parts = sum(got[k]["value"] for k in (
        "decode_prepare_ms", "decode_dispatch_ms", "decode_wait_ms"))
    assert 0.0 < parts and got["prefill_wall_pct"]["value"] > 0.0
    assert "lanes_busy_pct" in got  # what the cell reported before stays
