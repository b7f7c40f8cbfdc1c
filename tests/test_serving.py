"""paddle_tpu.serving: continuous-batching slot engine, paged KV arena,
iteration-level scheduler, submit/stream/cancel API, and the
``inference.Config`` predictor bridge (ISSUE 4); plus the resilience layer
(ISSUE 5): priority admission + starvation preemption, supervisor
rebuild-and-replay recovery with the crash-loop breaker, and graceful
drain / preemption-guard shutdown. The radix prefix cache's supervisor
interaction (ISSUE 6) chaos-tests here; its unit and tier-1 regression
coverage lives in ``tests/test_prefix_cache.py``.

The compiled-engine tests share one module-scoped ``ServingAPI`` so tier-1
pays its prefill/decode compiles once; assertions on trace counters are
written lifetime-safe (every bucket traced at most once, decode traced
exactly once) so test order can never flip them. Heavy churn and
fault-injection cases carry ``slow`` / ``chaos``. Tests that drain or
close an API always build their own instance — a drained API refuses
admissions forever, so the shared fixture must never be drained.
"""
import logging
import os
import queue as pyqueue
import time
import weakref
from types import SimpleNamespace

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.core import compile_cache, flags, resilience
from paddle_tpu.core.tensor import Tensor
from paddle_tpu.models.gpt import GPTForCausalLM, gpt_tiny
from paddle_tpu.serving import (
    ArenaExhaustedError,
    CrashLoopError,
    EngineSupervisor,
    KVArena,
    Request,
    RequestState,
    ReservationExhaustedError,
    Scheduler,
    ServingAPI,
    ServingConfig,
    ServingEngine,
)
from paddle_tpu.serving import metrics as serving_metrics
from paddle_tpu.serving.supervisor import is_transient_serving_error

pytestmark = pytest.mark.serving

MAX_LEN = 64


@pytest.fixture(scope="module")
def model():
    paddle.seed(0)
    m = GPTForCausalLM(gpt_tiny())
    m.eval()
    return m


@pytest.fixture(scope="module")
def api(model):
    a = ServingAPI(model, num_slots=4, kv_block_size=8, max_model_len=MAX_LEN)
    yield a
    a.close()


def _prompt(rng, n):
    return rng.integers(0, 1024, (n,), dtype=np.int32)


def _ref(model, prompt, max_new, stop=None):
    out = model.generate(Tensor(np.asarray(prompt)[None]),
                         max_new_tokens=max_new, stop_token_id=stop)
    return np.asarray(out._data)[0]


# ---------------------------------------------------------------- engine


def test_engine_parity_with_generate(api, model):
    """Greedy decode through the paged-arena slot engine is token-for-token
    identical to the contiguous-cache generate() path."""
    rng = np.random.default_rng(1)
    prompts = [_prompt(rng, n) for n in (5, 11)]
    reqs = [api.submit(p, max_new_tokens=8) for p in prompts]
    api.run_until_idle()
    for p, r in zip(prompts, reqs):
        assert r.state == RequestState.FINISHED
        np.testing.assert_array_equal(r.output_ids(), _ref(model, p, 8))


def test_stop_token_parity_and_early_exit(api, model):
    """A stop-token request ends at the stop hit and matches
    generate(stop_token_id=...) up to its fill tail."""
    rng = np.random.default_rng(2)
    p = _prompt(rng, 6)
    # pick a stop token the greedy decode actually emits mid-stream
    full = _ref(model, p, 12)
    stop = int(full[len(p) + 3])
    ref = _ref(model, p, 12, stop=stop)
    req = api.submit(p, max_new_tokens=12, stop_token_id=stop)
    api.run_until_idle()
    got = req.output_ids()
    assert req.state == RequestState.FINISHED
    assert int(got[-1]) == stop
    assert len(got) < len(p) + 12  # genuinely stopped early
    np.testing.assert_array_equal(got, ref[: len(got)])
    assert np.all(ref[len(got):] == stop)  # generate() fills the tail


def test_admit_retire_never_recompiles(api):
    """The engine invariant: churning admits/retires across occupancy
    patterns adds zero decode traces and retraces no prefill bucket."""
    rng = np.random.default_rng(3)
    api.run_until_idle()
    # make sure the decode step has been traced at least once already
    api.submit(_prompt(rng, 5), max_new_tokens=3)
    api.run_until_idle()
    d0 = api.engine.decode_traces
    cc0 = compile_cache.stats().get("serving.decode_compiles", 0)
    for n_live in (1, 3, 4, 2):
        reqs = [api.submit(_prompt(rng, 4 + 3 * i), max_new_tokens=2 + i)
                for i in range(n_live)]
        api.run_until_idle()
        assert all(r.state == RequestState.FINISHED for r in reqs)
    assert api.engine.decode_traces == d0 == 1
    assert compile_cache.stats().get("serving.decode_compiles", 0) == cc0
    assert all(v == 1 for v in api.engine.prefill_traces.values())
    assert api.engine.active_slots() == 0


def test_mixed_lengths_bounded_by_bucket_count(api):
    """Mixed prompt lengths land in at most len({their buckets}) compiled
    prefill programs (shape bucketing from core.compile_cache)."""
    rng = np.random.default_rng(4)
    lens = (3, 5, 9, 14, 17, 21, 30)
    expected = {compile_cache.prefill_bucket(n, MAX_LEN) for n in lens}
    for n in lens:
        api.submit(_prompt(rng, n), max_new_tokens=2)
    api.run_until_idle()
    traced = set(api.engine.prefill_traces)
    assert expected <= traced  # every needed bucket exists...
    assert len(expected) < len(lens)  # ...and bucketing actually coalesced
    assert all(v == 1 for v in api.engine.prefill_traces.values())


def test_prefill_bucket_ladder():
    m = int(flags.flag("serving_prefill_bucket_min"))
    assert compile_cache.prefill_bucket(1) == m
    assert compile_cache.prefill_bucket(m) == m
    for n in (1, 7, 33, 100):
        assert compile_cache.prefill_bucket(n) >= n
    # clamped to the model's position budget
    assert compile_cache.prefill_bucket(70, max_len=100) <= 100
    # whole-range bucket count stays small (the "handful of compiles" claim)
    assert len({compile_cache.prefill_bucket(n, 2048)
                for n in range(1, 2049)}) <= 16


def test_engine_rejects_oversized_and_empty(api):
    with pytest.raises(ValueError):
        api.submit(np.arange(MAX_LEN, dtype=np.int32), max_new_tokens=1)
    with pytest.raises(ValueError):
        api.submit(np.zeros(0, np.int32), max_new_tokens=4)
    with pytest.raises(ValueError):
        api.submit(np.zeros(4, np.int32), max_new_tokens=0)


# ------------------------------------------------------- cancel / deadline


def test_cancel_mid_decode_frees_slot(api):
    rng = np.random.default_rng(5)
    req = api.submit(_prompt(rng, 5), max_new_tokens=40)
    for _ in range(3):
        api._pump_once()
    assert req.state == RequestState.RUNNING
    assert api.engine.active_slots() == 1
    api.cancel(req)
    assert req.state == RequestState.CANCELLED
    assert api.engine.active_slots() == 0
    a = api.engine.arena.stats()
    assert a["blocks_in_use"] == 0 and a["blocks_reserved"] == 0
    with pytest.raises(RuntimeError, match="cancelled"):
        api.result(req)


def test_cancel_while_queued_costs_no_prefill(api):
    rng = np.random.default_rng(6)
    before = dict(api.engine.prefill_traces)
    admits0 = serving_metrics.stats().get("engine.admits", 0)
    req = api.submit(_prompt(rng, 5), max_new_tokens=4)
    req.cancel()
    api.run_until_idle()
    assert req.state == RequestState.CANCELLED
    assert serving_metrics.stats().get("engine.admits", 0) == admits0
    assert api.engine.prefill_traces == before


def test_deadline_expiry_fails_request_and_frees_slot(api):
    rng = np.random.default_rng(7)
    dl0 = resilience.stats().get("deadline.exceeded", 0)
    req = api.submit(_prompt(rng, 5), max_new_tokens=50, timeout=0.02)
    time.sleep(0.03)
    api.run_until_idle()
    assert req.state == RequestState.FAILED
    assert isinstance(req.error, resilience.DeadlineExceededError)
    # expiry lands on the shared resilience counter dashboards watch
    assert resilience.stats().get("deadline.exceeded", 0) == dl0 + 1
    assert api.engine.active_slots() == 0
    with pytest.raises(resilience.DeadlineExceededError):
        api.result(req)


def test_queue_overload_shedding(api):
    rng = np.random.default_rng(8)
    old = api._max_queue
    api._max_queue = 2
    try:
        shed0 = resilience.stats().get("overload.shed", 0)
        reqs = [api.submit(_prompt(rng, 4), max_new_tokens=2)
                for _ in range(2)]
        with pytest.raises(resilience.QueueOverloadError):
            api.submit(_prompt(rng, 4), max_new_tokens=2)
        assert resilience.stats().get("overload.shed", 0) == shed0 + 1
    finally:
        api._max_queue = old
        for r in reqs:
            r.cancel()
        api.run_until_idle()


def test_stream_yields_generated_tokens(api, model):
    rng = np.random.default_rng(9)
    p = _prompt(rng, 7)
    req = api.submit(p, max_new_tokens=6)
    toks = list(api.stream(req))
    assert req.state == RequestState.FINISHED
    assert toks == req.tokens
    np.testing.assert_array_equal(
        np.concatenate([p, np.asarray(toks, np.int32)]), _ref(model, p, 6))


# --------------------------------------------------------------- KV arena


def test_arena_freelist_reuse_under_churn():
    arena = KVArena(num_layers=1, num_heads=2, head_dim=4,
                    num_blocks=9, block_size=4)
    serving_metrics_before = serving_metrics.stats().get("arena.reuse", 0)
    res = arena.reserve(3)
    first = [res.take() for _ in range(3)]
    assert 0 not in first  # scratch block is never handed out
    assert arena.blocks_in_use() == 3
    res.release()
    assert arena.blocks_free() == 8 and arena.blocks_in_use() == 0
    # LIFO: the churny path re-takes exactly the just-freed blocks
    res2 = arena.reserve(3)
    second = [res2.take() for _ in range(3)]
    assert set(second) == set(first)
    assert serving_metrics.stats().get("arena.reuse", 0) \
        == serving_metrics_before + 3
    res2.release()


def test_arena_two_phase_reservation_accounting():
    arena = KVArena(num_layers=1, num_heads=2, head_dim=4,
                    num_blocks=6, block_size=4)
    res = arena.reserve(3)
    # the budget is claimed up front: only 2 of 5 blocks remain grantable
    assert not arena.can_reserve(3)
    assert arena.can_reserve(2)
    with pytest.raises(ArenaExhaustedError):
        arena.reserve(3)
    # a reservation cannot take past its own budget either
    for _ in range(3):
        res.take()
    with pytest.raises(ArenaExhaustedError):
        res.take()
    res.release()
    assert arena.can_reserve(5)
    # releasing twice is a no-op, not a double-free
    res.release()
    assert arena.blocks_free() == 5


def test_engine_admission_gated_on_arena(model):
    """can_admit() is false when the arena cannot cover the worst case —
    a running request can never be starved of blocks mid-decode."""
    eng = ServingEngine(model, num_slots=2, kv_block_size=8,
                        max_model_len=32, num_blocks=5)  # 4 allocatable
    assert eng.can_admit(8, 24)  # needs all 4 blocks
    slot, _ = eng.admit(np.zeros(8, np.int32), max_new_tokens=24)
    assert not eng.can_admit(1, 1)  # slot free, arena full
    eng.retire(slot)
    assert eng.can_admit(8, 24)


def test_unadmittable_request_rejected_at_submit(model):
    """A request that fits max_model_len but needs more KV blocks than the
    whole arena holds is rejected by validate() — otherwise it would park
    un-admittable at the FCFS head and starve the queue forever."""
    eng = ServingEngine(model, num_slots=2, kv_block_size=8,
                        max_model_len=64, num_blocks=5)  # 4 allocatable
    eng.validate(8, 24)  # exactly the arena: fine
    with pytest.raises(ValueError, match="could never be admitted"):
        eng.validate(8, 56)  # 8 blocks > 4 allocatable, yet total <= 64


def test_foreground_step_failure_fails_all_requests(api, monkeypatch):
    """A decode-step exception during foreground pumping must not strand
    RUNNING requests holding slots and arena blocks: every in-flight
    request fails (error + done_event) and capacity is reclaimed, exactly
    like the background pump's fail_all path."""
    rng = np.random.default_rng(31)
    req = api.submit(_prompt(rng, 5), max_new_tokens=8)
    boom = RuntimeError("decode step died")

    def dead_step():
        raise boom

    monkeypatch.setattr(api.engine, "decode_step", dead_step)
    with pytest.raises(RuntimeError, match="decode step died"):
        api.run_until_idle()
    assert req.state == RequestState.FAILED
    assert req.error is boom
    assert req.done_event.is_set()
    assert api.engine.free_slots() == 4
    a = api.engine.arena.stats()
    assert a["blocks_in_use"] == 0 and a["blocks_reserved"] == 0


# ----------------------------------------------- resilience hooks (unit)


def test_deadline_helpers():
    assert not resilience.Deadline.after(None).expired()
    assert resilience.Deadline.after(None).remaining() == float("inf")
    d = resilience.Deadline.after(0)
    assert d.expired()
    with pytest.raises(resilience.DeadlineExceededError):
        d.check("unit")
    resilience.Deadline.after(60).check("unit")  # far future: no raise


def test_check_overload_limits():
    resilience.check_overload(5, limit=0)  # 0 = unlimited
    resilience.check_overload(5, limit=None, name="")  # flag default 0
    with pytest.raises(resilience.QueueOverloadError):
        resilience.check_overload(3, limit=3, name="unit")
    assert resilience.stats().get("overload.unit.shed", 0) >= 1


# ------------------------------------------------- inference.Config bridge


def test_config_accepts_pdmodel_directory(tmp_path):
    from paddle_tpu import inference

    d = tmp_path / "exported"
    d.mkdir()
    (d / "model.pdmodel").write_bytes(b"")
    cfg = inference.Config(str(d))
    assert cfg.model_prefix == str(d / "model")
    (d / "other.pdmodel").write_bytes(b"")
    with pytest.raises(ValueError, match="exactly one"):
        inference.Config(str(d))
    empty = tmp_path / "empty"
    empty.mkdir()
    with pytest.raises(ValueError, match="exactly one"):
        inference.Config(str(empty))


def test_config_placement_decision():
    from paddle_tpu import inference

    cfg = inference.Config("m.pdmodel")
    assert cfg._resolve_placement() == "cpu"  # no request: report actual
    cfg.enable_use_gpu(100, 0)
    assert cfg._device == ("gpu", 0)
    assert cfg._resolve_placement() == "cpu"  # mismatch logged, runs on XLA
    cfg.enable_tpu()
    assert cfg._device == ("tpu", 0)
    assert cfg._resolve_placement() == "cpu"


def test_engine_predictor_bridge(api, model):
    """inference.Config.enable_serving_engine routes create_predictor
    through the slot engine with generate()'s output contract."""
    from paddle_tpu import inference

    rng = np.random.default_rng(10)
    ids = np.stack([_prompt(rng, 6), _prompt(rng, 6)])
    cfg = inference.Config()
    cfg.enable_serving_engine(model, max_new_tokens=5, num_slots=2,
                              kv_block_size=8, max_model_len=MAX_LEN)
    pred = inference.create_predictor(cfg)
    h = pred.get_input_handle("input_ids")
    h.copy_from_cpu(ids)
    pred.run()
    out = pred.get_output_handle("output_0").copy_to_cpu()
    assert out.shape == (2, 6 + 5)
    for i in range(2):
        np.testing.assert_array_equal(out[i], _ref(model, ids[i], 5))
    pred.close()
    with pytest.raises(ValueError, match="in-memory"):
        c2 = inference.Config()
        c2.enable_serving_engine(None)
        inference.create_predictor(c2)


def test_close_fails_outstanding_requests(model):
    """close() never strands a request: anything still queued fails with a
    clear error, its done_event set and stream sentinel delivered (a
    queued request costs no prefill, so this engine never compiles)."""
    a = ServingAPI(model, num_slots=2, kv_block_size=8, max_model_len=MAX_LEN)
    rng = np.random.default_rng(15)
    req = a.submit(_prompt(rng, 5), max_new_tokens=4)  # stays QUEUED
    a.close()
    assert req.state == RequestState.FAILED
    assert isinstance(req.error, RuntimeError)
    assert req.done_event.is_set()
    with pytest.raises(RuntimeError, match="closed"):
        list(a.stream(req))  # sentinel delivered, then the error surfaces
    with pytest.raises(RuntimeError, match="closed"):
        a.submit(_prompt(rng, 5), max_new_tokens=4)


# ------------------------------------------------------- heavy / chaos


@pytest.mark.slow
def test_slot_churn_stress(model):
    """Many mixed requests through few slots: everything finishes, the
    free list is exercised (reuse counter climbs), and the arena ends
    clean with zero leaked blocks."""
    api = ServingAPI(model, num_slots=2, kv_block_size=8,
                     max_model_len=MAX_LEN)
    try:
        rng = np.random.default_rng(11)
        reuse0 = serving_metrics.stats().get("arena.reuse", 0)
        reqs = [api.submit(_prompt(rng, int(rng.integers(3, 30))),
                           max_new_tokens=int(rng.integers(2, 16)))
                for _ in range(12)]
        api.run_until_idle()
        assert all(r.state == RequestState.FINISHED for r in reqs)
        a = api.engine.arena.stats()
        assert a["blocks_in_use"] == 0 and a["blocks_reserved"] == 0
        assert serving_metrics.stats().get("arena.reuse", 0) > reuse0
        assert api.engine.decode_traces == 1
    finally:
        api.close()


@pytest.mark.slow
def test_background_pump_thread(model):
    api = ServingAPI(model, num_slots=2, kv_block_size=8,
                     max_model_len=MAX_LEN, background=True)
    try:
        rng = np.random.default_rng(12)
        p = _prompt(rng, 5)
        req = api.submit(p, max_new_tokens=6)
        out = api.result(req, timeout=60)
        np.testing.assert_array_equal(out, _ref(model, p, 6))
    finally:
        api.close()


@pytest.mark.chaos
@pytest.mark.slow
def test_step_fault_retried_without_donation(model):
    """With donation off the engine wraps compiled calls in the io retry
    policy: a transient injected step fault is retried and the request
    still completes; with donation on the same config refuses to retry."""
    keep = paddle.get_flags("fault_injection")["fault_injection"]
    paddle.set_flags({"fault_injection": 1})
    try:
        api = ServingAPI(
            model, config=ServingConfig(num_slots=2, kv_block_size=8,
                                        max_model_len=MAX_LEN, donate=False))
        rng = np.random.default_rng(13)
        p = _prompt(rng, 5)
        retries0 = resilience.stats().get("retry.retries", 0)
        resilience.inject_fault("serving_step", times=1,
                                exc=OSError("injected step fault"))
        req = api.submit(p, max_new_tokens=6)
        api.run_until_idle()
        assert req.state == RequestState.FINISHED
        np.testing.assert_array_equal(req.output_ids(), _ref(model, p, 6))
        assert resilience.stats().get("retry.retries", 0) > retries0
        api.close()
    finally:
        resilience.clear_faults()
        paddle.set_flags({"fault_injection": keep})


@pytest.mark.chaos
@pytest.mark.slow
def test_failed_prefill_fails_request_not_engine(model):
    """A prefill failure that exhausts retries fails THAT request cleanly
    (error delivered, done_event set, no leaked arena blocks) and the
    engine keeps serving the next request."""
    keep = {k: paddle.get_flags(k)[k]
            for k in ("fault_injection", "io_retries", "io_retry_backoff")}
    paddle.set_flags({"fault_injection": 1, "io_retries": 2,
                      "io_retry_backoff": 0.001})
    try:
        api = ServingAPI(
            model, config=ServingConfig(num_slots=2, kv_block_size=8,
                                        max_model_len=MAX_LEN, donate=False))
        rng = np.random.default_rng(16)
        p = _prompt(rng, 5)
        resilience.inject_fault("serving_step", times=10,
                                exc=OSError("persistent step fault"))
        req = api.submit(p, max_new_tokens=4)
        api.run_until_idle()
        assert req.state == RequestState.FAILED
        assert isinstance(req.error, OSError)
        assert req.done_event.is_set()
        a = api.engine.arena.stats()
        assert a["blocks_in_use"] == 0 and a["blocks_reserved"] == 0
        resilience.clear_faults()
        req2 = api.submit(p, max_new_tokens=4)  # engine still healthy
        api.run_until_idle()
        assert req2.state == RequestState.FINISHED
        np.testing.assert_array_equal(req2.output_ids(), _ref(model, p, 4))
        api.close()
    finally:
        resilience.clear_faults()
        paddle.set_flags(keep)


# ----------------------------------------------------------- stats wiring


def test_serving_stats_on_shared_surfaces(api):
    rng = np.random.default_rng(14)
    before = serving_metrics.stats()
    req = api.submit(_prompt(rng, 5), max_new_tokens=4)
    api.run_until_idle()
    delta = serving_metrics.stats_delta(before, serving_metrics.stats())
    assert delta.get("tokens.generated", 0) >= 4
    assert delta.get("requests.finished", 0) == 1
    # headline numbers ride the shared memory_stats provider surface
    from paddle_tpu.core import memory_stats

    stats = memory_stats.memory_stats()
    assert "provider.serving.tokens_generated" in stats
    assert stats["provider.serving.tokens_generated"] \
        == serving_metrics.stats().get("tokens.generated", 0)
    # the engine's Meter publishes a live aggregate decode rate
    assert serving_metrics.stats().get("tokens_per_sec", 0) > 0
    assert req.state == RequestState.FINISHED


def test_completed_output_beats_expired_deadline(api):
    """A request whose output is already whole when its deadline expires
    FINISHES with the result — completed work is never discarded."""
    from paddle_tpu.serving.scheduler import Request

    req = Request(np.arange(4, dtype=np.int32), max_new_tokens=8,
                  stop_token_id=3, tokens=[9, 3],
                  deadline=resilience.Deadline.after(0.0))
    assert req.deadline.expired()
    assert api.scheduler._check_boundary(req)
    assert req.state == RequestState.FINISHED and req.error is None


# ------------------------------------------- priority admission (ISSUE 5)


def test_priority_admission_order(api):
    """Lower priority value is admitted first; FCFS within a class."""
    rng = np.random.default_rng(20)
    rs = [api.submit(_prompt(rng, 4), max_new_tokens=2, priority=p)
          for p in (5, 0, 5)]
    api.run_until_idle()
    assert all(r.state == RequestState.FINISHED for r in rs)
    # admission ticks: the priority-0 request went first, then the two
    # priority-5 requests in arrival order
    assert rs[1]._admit_seq < rs[0]._admit_seq < rs[2]._admit_seq


def test_reservation_exhausted_distinct_from_pressure():
    """take() past a reservation's own budget is an under-reservation BUG
    (ReservationExhaustedError, total/taken in the message) — distinct from
    arena *pressure* (base ArenaExhaustedError), which preemption can heal."""
    arena = KVArena(num_layers=1, num_heads=2, head_dim=4,
                    num_blocks=6, block_size=4)
    res = arena.reserve(2)
    for _ in range(2):
        res.take()
    with pytest.raises(ReservationExhaustedError) as ei:
        res.take()
    assert isinstance(ei.value, ArenaExhaustedError)  # still catchable broadly
    assert "all 2 budgeted blocks" in str(ei.value)
    assert "2 taken" in str(ei.value)
    # genuine pressure raises the base class, never the reservation one
    with pytest.raises(ArenaExhaustedError) as pei:
        arena.reserve(5)
    assert not isinstance(pei.value, ReservationExhaustedError)
    res.release()


# --------------------------------------------- supervisor units (ISSUE 5)


def test_transient_serving_error_classifier():
    assert is_transient_serving_error(resilience.ServingDeviceError("x"))
    assert is_transient_serving_error(resilience.ArenaCorruptError("x"))

    class XlaRuntimeError(Exception):  # jaxlib's class, matched by name
        pass

    assert is_transient_serving_error(XlaRuntimeError("device lost"))
    # bugs / IO / validation / interrupts keep the fail-fast (or retry) path
    assert not is_transient_serving_error(OSError("io"))
    assert not is_transient_serving_error(ValueError("bad request"))
    assert not is_transient_serving_error(KeyboardInterrupt())


class _FakeEngine:
    def __init__(self):
        self.rebuilds = 0

    def rebuild(self):
        self.rebuilds += 1


class _FakeSched:
    def __init__(self):
        self.running = []

    def _gauges(self):
        pass


def test_crash_loop_breaker_opens_and_wraps():
    eng = _FakeEngine()
    sup = EngineSupervisor(eng, _FakeSched(), max_rebuilds=2, window=100)
    err = resilience.ServingDeviceError("flaky")
    assert sup.handle(err) and sup.handle(err)
    assert eng.rebuilds == 2
    assert not sup.handle(err)  # third rebuild within the window: breaker
    assert sup.breaker_open
    wrapped = sup.wrap(err)
    assert isinstance(wrapped, CrashLoopError)
    assert wrapped.__cause__ is err
    assert "FLAGS_serving_max_rebuilds" in str(wrapped)
    # non-transient errors are never handled and pass through wrap()
    bug = ValueError("bug")
    assert not sup.handle(bug)
    assert sup.wrap(bug) is bug


def test_crash_loop_breaker_window_slides():
    eng = _FakeEngine()
    sup = EngineSupervisor(eng, _FakeSched(), max_rebuilds=1, window=5)
    err = resilience.ServingDeviceError("flaky")
    assert sup.handle(err)
    for _ in range(5):
        sup.note_step()  # five steps of real progress: the rebuild ages out
    assert sup.handle(err)
    assert eng.rebuilds == 2 and not sup.breaker_open


def test_recovery_failure_fails_staged_requests():
    """If recovery itself dies (the fresh arena allocation failing on a
    still-dead device), requests staged for replay are failed with that
    error — never left slot-less and RUNNING with done_event unset."""

    class DeadEngine:
        def rebuild(self):
            raise MemoryError("fresh arena allocation failed")

    sched = Scheduler(DeadEngine())
    reqs = [Request(np.arange(4, dtype=np.int32), max_new_tokens=4)
            for _ in range(2)]
    for slot, r in enumerate(reqs):
        r.state = RequestState.RUNNING
        r.slot = slot
        sched.running.append(r)
    sup = EngineSupervisor(DeadEngine(), sched, max_rebuilds=3, window=10)
    with pytest.raises(MemoryError):
        sup.handle(resilience.ServingDeviceError("step died"))
    for r in reqs:
        assert r.state == RequestState.FAILED
        assert isinstance(r.error, MemoryError)
        assert r.done_event.is_set()
    assert not sched.running


# ------------------------------------------------ drain / close (ISSUE 5)


def test_drain_zero_grace_fails_stragglers_retriably(model):
    """drain(grace=0) stops admissions and fails anything still in flight
    with the retriable RequestDrainedError (a queued request costs no
    prefill, so this never compiles)."""
    a = ServingAPI(model, num_slots=2, kv_block_size=8, max_model_len=MAX_LEN)
    rng = np.random.default_rng(44)
    req = a.submit(_prompt(rng, 5), max_new_tokens=4)  # stays QUEUED
    d0 = resilience.stats().get("serving.drains", 0)
    s0 = resilience.stats().get("serving.drain_stragglers", 0)
    a.drain(grace=0)
    assert req.state == RequestState.FAILED
    assert isinstance(req.error, resilience.RequestDrainedError)
    assert resilience.stats().get("serving.drains", 0) == d0 + 1
    assert resilience.stats().get("serving.drain_stragglers", 0) == s0 + 1
    with pytest.raises(resilience.RequestDrainedError, match="draining"):
        a.submit(_prompt(rng, 5), max_new_tokens=4)
    a.drain()  # idempotent: no second drain counter, no re-fail
    assert resilience.stats().get("serving.drains", 0) == d0 + 1
    a.close()  # close shares the drain path; the dead request is untouched
    assert isinstance(req.error, resilience.RequestDrainedError)


def test_close_after_failed_pump_single_fail(model, monkeypatch):
    """ISSUE 5 satellite: close() routes through drain(grace=0), and
    close() after a failed pump never double-fails requests — one error,
    one stream sentinel, one done_event edge."""
    a = ServingAPI(model, num_slots=2, kv_block_size=8, max_model_len=MAX_LEN)
    rng = np.random.default_rng(45)
    req = a.submit(_prompt(rng, 5), max_new_tokens=4)
    boom = RuntimeError("pump died")

    def dead_step():
        raise boom

    monkeypatch.setattr(a.scheduler, "step", dead_step)
    with pytest.raises(RuntimeError, match="pump died"):
        a.run_until_idle()
    assert req.state == RequestState.FAILED and req.error is boom
    d0 = resilience.stats().get("serving.drains", 0)
    a.close()  # one shared code path: close == drain(grace=0)
    assert resilience.stats().get("serving.drains", 0) == d0 + 1
    assert req.error is boom  # not replaced by a drain error
    assert req.stream_queue.get_nowait() is None  # exactly one sentinel
    with pytest.raises(pyqueue.Empty):
        req.stream_queue.get_nowait()


def test_drain_all_covers_live_apis(model, monkeypatch):
    import paddle_tpu.serving.api as api_mod

    a = ServingAPI(model, num_slots=2, kv_block_size=8, max_model_len=MAX_LEN)
    b = ServingAPI(model, num_slots=2, kv_block_size=8, max_model_len=MAX_LEN)
    b.close()
    monkeypatch.setattr(api_mod, "_live_apis", weakref.WeakSet((a, b)))
    rng = np.random.default_rng(46)
    req = a.submit(_prompt(rng, 5), max_new_tokens=4)
    assert api_mod.drain_all() == 1  # b is already closed: skipped
    assert req.state == RequestState.FAILED
    assert isinstance(req.error, resilience.RequestDrainedError)
    a.close()


def test_preemption_guard_binds_to_drain(model):
    """SIGTERM (stood in by guard.request()) drains the API at the next
    pump boundary instead of killing it mid-decode: admissions stop and
    stragglers fail with the retriable RequestDrainedError — the serving
    mirror of the training loop's step-boundary finalize."""
    a = ServingAPI(model, num_slots=2, kv_block_size=8, max_model_len=MAX_LEN)
    guard = resilience.PreemptionGuard(install=False)
    assert a.bind_preemption_guard(guard, grace=0.0) is a
    rng = np.random.default_rng(47)
    req = a.submit(_prompt(rng, 5), max_new_tokens=4)  # stays QUEUED
    g0 = serving_metrics.stats().get("api.guard_drains", 0)
    guard.request("test eviction")
    a._pump_once()
    assert req.state == RequestState.FAILED
    assert isinstance(req.error, resilience.RequestDrainedError)
    assert "preemption requested" in str(req.error)
    assert serving_metrics.stats().get("api.guard_drains", 0) == g0 + 1
    with pytest.raises(resilience.RequestDrainedError):
        a.submit(_prompt(rng, 5), max_new_tokens=4)
    a.close()


def test_close_during_inflight_drain_still_sweeps(model, monkeypatch):
    """close() racing an already-running long-grace drain must not return
    with requests still alive: drain() early-returns on the idempotency
    guard, so close() sweeps stragglers itself with its zero grace."""
    a = ServingAPI(model, num_slots=2, kv_block_size=8, max_model_len=MAX_LEN)
    rng = np.random.default_rng(50)
    req = a.submit(_prompt(rng, 5), max_new_tokens=4)  # stays QUEUED
    a._draining = True  # stand-in for a guard drain mid-grace elsewhere
    a.close()
    assert req.state == RequestState.FAILED
    assert isinstance(req.error, resilience.RequestDrainedError)
    assert req.done_event.is_set()


def test_predictor_priority_kwarg_and_close_summary(model, monkeypatch,
                                                    caplog):
    """ISSUE 5 satellite: EnginePredictor.run honors priorities (kwarg
    defaulting to the constructor's class) and close() logs the replay /
    preemption / drain picture."""
    from paddle_tpu.serving.api import EnginePredictor

    pred = EnginePredictor(model, max_new_tokens=2, priority=7,
                           config=ServingConfig(num_slots=2, kv_block_size=8,
                                                max_model_len=MAX_LEN))
    seen = []

    def fake_submit(prompt, max_new_tokens=32, stop_token_id=None,
                    priority=0, sampling=None, adapter=0):
        seen.append(priority)
        r = Request(prompt, max_new_tokens=max_new_tokens, priority=priority)
        r.state = RequestState.FINISHED
        r.tokens = [1] * max_new_tokens
        return r

    monkeypatch.setattr(pred._api, "submit", fake_submit)
    monkeypatch.setattr(pred._api, "run_until_idle", lambda: None)
    ids = np.ones((2, 4), np.int32)
    pred.run([ids])
    assert seen == [7, 7]  # constructor default rides every row
    pred.run([ids], priority=1)
    assert seen[2:] == [1, 1]  # per-run override
    with caplog.at_level(logging.INFO, logger="paddle_tpu.serving"):
        pred.close()
    assert "supervisor replays" in caplog.text
    assert "preemptions" in caplog.text and "drains" in caplog.text


def test_predictor_mid_batch_submit_failure_strands_nothing(model):
    """If a row's submit sheds mid-batch, EnginePredictor.run cancels the
    rows it already queued instead of leaving unreachable handles that
    FCFS would still spend capacity on."""
    from paddle_tpu.serving.api import EnginePredictor

    pred = EnginePredictor(model, max_new_tokens=4,
                           config=ServingConfig(num_slots=1, kv_block_size=8,
                                                max_model_len=MAX_LEN),
                           max_queue=2)
    try:
        ids = np.tile(np.arange(5, dtype=np.int32), (6, 1))
        with pytest.raises(resilience.QueueOverloadError):
            pred.run([ids])
        assert not pred._api.scheduler.has_work()
    finally:
        pred.close()


# -------------------------------------------- chaos serving (ISSUE 5)


@pytest.mark.chaos
@pytest.mark.slow
def test_supervisor_replay_token_parity_mid_decode(model):
    """ISSUE 5 acceptance: a transient device fault injected mid-decode
    recovers through supervisor rebuild+replay with byte-identical final
    output_ids() for every live request, zero new decode compiles across
    fail/rebuild/replay/resume, and a clean arena (blocks_in_use == 0, all
    slots free) once the workload drains."""
    keep = paddle.get_flags("fault_injection")["fault_injection"]
    paddle.set_flags({"fault_injection": 1})
    api = ServingAPI(model, num_slots=4, kv_block_size=8,
                     max_model_len=MAX_LEN)
    try:
        rng = np.random.default_rng(40)
        prompts = [_prompt(rng, n) for n in (5, 9, 12)]
        # unfaulted reference pass through the same engine
        reqs = [api.submit(p, max_new_tokens=10) for p in prompts]
        api.run_until_idle()
        refs = [r.output_ids() for r in reqs]
        cc0 = compile_cache.stats().get("serving.decode_compiles", 0)
        d0 = api.engine.decode_traces
        rp0 = serving_metrics.stats().get("supervisor.replays", 0)
        rb0 = resilience.stats().get("serving.rebuilds", 0)
        # faulted pass: all three live mid-decode when the device dies
        reqs2 = [api.submit(p, max_new_tokens=10) for p in prompts]
        for _ in range(3):
            api._pump_once()
        assert all(r.state == RequestState.RUNNING for r in reqs2)
        resilience.inject_fault("serving_device", times=1)
        api.run_until_idle()
        for ref, r in zip(refs, reqs2):
            assert r.state == RequestState.FINISHED
            np.testing.assert_array_equal(ref, r.output_ids())
        assert serving_metrics.stats().get("supervisor.replays", 0) \
            == rp0 + 3
        assert resilience.stats().get("serving.rebuilds", 0) == rb0 + 1
        # the arena_corrupt fault class recovers through the same path
        reqs3 = [api.submit(p, max_new_tokens=10) for p in prompts]
        for _ in range(2):
            api._pump_once()
        resilience.inject_fault("arena_corrupt", times=1)
        api.run_until_idle()
        for ref, r in zip(refs, reqs3):
            assert r.state == RequestState.FINISHED
            np.testing.assert_array_equal(ref, r.output_ids())
        # no recompiles anywhere in fail/rebuild/replay/resume
        assert api.engine.decode_traces == d0 == 1
        assert compile_cache.stats().get("serving.decode_compiles", 0) == cc0
        # graceful drain leaves the engine empty: zero stranded slots/blocks
        api.drain(grace=5)
        a = api.engine.arena.stats()
        assert a["blocks_in_use"] == 0 and a["blocks_reserved"] == 0
        assert api.engine.active_slots() == 0
    finally:
        resilience.clear_faults()
        api.close()
        paddle.set_flags({"fault_injection": keep})


@pytest.mark.chaos
@pytest.mark.slow
def test_preemption_starvation_regression(model):
    """Oversubscribed mixed-priority workload: a high-priority arrival that
    cannot fit preempts the lowest-priority most-recent victim once the
    starvation threshold trips; EVERY request still completes (the victim
    resumes from its journal token-for-token) and nothing recompiles."""
    keep = paddle.get_flags(
        "serving_starvation_steps")["serving_starvation_steps"]
    paddle.set_flags({"serving_starvation_steps": 2})
    api = ServingAPI(model, num_slots=2, kv_block_size=8,
                     max_model_len=MAX_LEN)
    try:
        rng = np.random.default_rng(41)
        pre0 = serving_metrics.stats().get("scheduler.preemptions", 0)
        low_prompts = [_prompt(rng, 6) for _ in range(2)]
        low = [api.submit(p, max_new_tokens=20, priority=5)
               for p in low_prompts]
        api._pump_once()  # both low-priority admitted: slots full
        assert all(r.state == RequestState.RUNNING for r in low)
        hp = _prompt(rng, 20)
        hi = api.submit(hp, max_new_tokens=30, priority=0)
        api.run_until_idle()
        assert all(r.state == RequestState.FINISHED for r in low + [hi])
        assert serving_metrics.stats().get("scheduler.preemptions", 0) > pre0
        # the most recently admitted of the lowest-priority class was evicted
        assert low[1].preemptions >= 1
        # preempted output is identical to an uninterrupted run
        for p, r in zip(low_prompts, low):
            np.testing.assert_array_equal(r.output_ids(), _ref(model, p, 20))
        np.testing.assert_array_equal(hi.output_ids(), _ref(model, hp, 30))
        assert api.engine.decode_traces == 1  # preempt/resume: no recompile
        a = api.engine.arena.stats()
        assert a["blocks_in_use"] == 0 and a["blocks_reserved"] == 0
    finally:
        api.close()
        paddle.set_flags({"serving_starvation_steps": keep})


@pytest.mark.chaos
@pytest.mark.slow
def test_crash_loop_breaker_end_to_end(model):
    """A persistently dying device stops being rebuilt after the breaker
    budget: in-flight requests fail fast with CrashLoopError (transient
    cause chained) instead of replaying forever, capacity is reclaimed,
    and later pumps surface the same fail-fast error."""
    keep = paddle.get_flags("fault_injection")["fault_injection"]
    paddle.set_flags({"fault_injection": 1})
    api = ServingAPI(model, num_slots=2, kv_block_size=8,
                     max_model_len=MAX_LEN)
    api.supervisor.max_rebuilds = 2
    try:
        rng = np.random.default_rng(42)
        req = api.submit(_prompt(rng, 5), max_new_tokens=8)
        api._pump_once()
        assert req.state == RequestState.RUNNING
        rb0 = serving_metrics.stats().get("supervisor.rebuilds", 0)
        resilience.inject_fault("serving_device", times=100)
        # breaker exhaustion mid-recovery surfaces CrashLoopError to the
        # pumping caller right away (a total failure is not a "recovery")
        with pytest.raises(CrashLoopError):
            api.run_until_idle()
        assert req.state == RequestState.FAILED
        assert isinstance(req.error, CrashLoopError)
        assert isinstance(req.error.__cause__,
                          resilience.ServingDeviceError)
        assert api.supervisor.breaker_open
        assert serving_metrics.stats().get("supervisor.rebuilds", 0) \
            == rb0 + 2
        a = api.engine.arena.stats()
        assert a["blocks_in_use"] == 0 and a["blocks_reserved"] == 0
        assert api.engine.active_slots() == 0
        # after the breaker opens, queued work fails fast through the pump
        req2 = api.submit(_prompt(rng, 5), max_new_tokens=4)
        with pytest.raises(CrashLoopError):
            api.run_until_idle()
        assert isinstance(req2.error, CrashLoopError)
    finally:
        resilience.clear_faults()
        api.close()
        paddle.set_flags({"fault_injection": keep})


@pytest.mark.chaos
@pytest.mark.slow
def test_breaker_mid_replay_death_leaks_nothing(model, monkeypatch):
    """Regression: the engine dying AGAIN during replay — after some
    requests were already re-admitted into the fresh arena — exhausts the
    breaker without leaking those slots/blocks: everything re-admitted is
    retired before the fail-fast sweep."""
    api = ServingAPI(model, num_slots=2, kv_block_size=8,
                     max_model_len=MAX_LEN)
    api.supervisor.max_rebuilds = 1
    try:
        rng = np.random.default_rng(48)
        r1 = api.submit(_prompt(rng, 5), max_new_tokens=8)
        r2 = api.submit(_prompt(rng, 9), max_new_tokens=8)
        api._pump_once()
        assert all(r.state == RequestState.RUNNING for r in (r1, r2))
        real_admit = api.engine.admit
        calls = {"n": 0}

        def flaky_admit(prompt, max_new_tokens, tokens=None):
            calls["n"] += 1
            if calls["n"] == 2:  # first replay succeeds, second one dies
                raise resilience.ServingDeviceError("died during replay")
            return real_admit(prompt, max_new_tokens, tokens=tokens)

        monkeypatch.setattr(api.engine, "admit", flaky_admit)
        # breaker exhaustion mid-recovery is NOT a recovery: handle()
        # returns False so the pump surfaces CrashLoopError instead of
        # counting a total failure as api.recoveries
        assert not api.supervisor.handle(
            resilience.ServingDeviceError("step died"))
        assert api.supervisor.breaker_open
        for r in (r1, r2):
            assert r.state == RequestState.FAILED
            assert isinstance(r.error, CrashLoopError)
            assert r.done_event.is_set()
        a = api.engine.arena.stats()
        assert a["blocks_in_use"] == 0 and a["blocks_reserved"] == 0
        assert api.engine.active_slots() == 0
    finally:
        api.close()


@pytest.mark.slow
def test_preemption_declines_when_eviction_cannot_help(model):
    """Feasibility gate: when higher-priority runners hold the arena and
    evicting every strictly-lower-priority victim still could not seat the
    waiter, nothing is preempted — the victims' prefilled work is not
    thrown away for unreachable capacity."""
    keep = paddle.get_flags(
        "serving_starvation_steps")["serving_starvation_steps"]
    paddle.set_flags({"serving_starvation_steps": 1})
    eng_kw = dict(num_slots=3, kv_block_size=8, max_model_len=MAX_LEN,
                  num_blocks=5)  # 4 allocatable blocks
    api = ServingAPI(model, **eng_kw)
    try:
        rng = np.random.default_rng(49)
        # priority-0 holder: 2 blocks; priority-9 victim candidate: 1 block
        holder = api.submit(_prompt(rng, 8), max_new_tokens=8, priority=0)
        victim = api.submit(_prompt(rng, 4), max_new_tokens=4, priority=9)
        api._pump_once()
        assert all(r.state == RequestState.RUNNING for r in (holder, victim))
        # waiter needs 4 blocks; grantable(1) + victim's budget(1) == 2 < 4
        waiter = api.submit(_prompt(rng, 8), max_new_tokens=24, priority=0)
        for _ in range(4):  # well past the starvation threshold
            api._pump_once()
        assert victim.preemptions == 0  # eviction declined, work preserved
        assert victim.state in (RequestState.RUNNING, RequestState.FINISHED)
        api.run_until_idle()  # capacity frees naturally; everyone completes
        for r in (holder, victim, waiter):
            assert r.state == RequestState.FINISHED
    finally:
        api.close()
        paddle.set_flags({"serving_starvation_steps": keep})


@pytest.mark.chaos
@pytest.mark.slow
def test_supervisor_replay_with_live_shared_prefixes(model, monkeypatch):
    """ISSUE 6 satellite: a ``serving_device`` fault mid-decode while
    several slots SHARE radix-cache prefix blocks rebuilds the arena
    (resetting the tree), replays every journal token-for-token — the
    replays re-inserting and re-sharing the prefix with fresh blocks —
    and leaves zero leaked blocks and consistent refcounts after
    ``drain_all()``."""
    keep = {k: paddle.get_flags(k)[k]
            for k in ("fault_injection", "serving_arena_invariants")}
    paddle.set_flags({"fault_injection": 1, "serving_arena_invariants": 1})
    api = ServingAPI(model, num_slots=4, kv_block_size=8,
                     max_model_len=MAX_LEN, prefix_cache=True)
    try:
        import paddle_tpu.serving.api as api_mod

        # drain_all must only sweep THIS test's api, not the shared
        # module fixture (a drained API refuses admissions forever)
        monkeypatch.setattr(api_mod, "_live_apis", weakref.WeakSet((api,)))
        rng = np.random.default_rng(60)
        shared = _prompt(rng, 24)  # 3 full blocks shared by every request
        prompts = [np.concatenate([shared, _prompt(rng, n)])
                   for n in (4, 6, 9)]
        # unfaulted reference pass through the same engine (and the same
        # cache — the second/third admissions already share blocks)
        reqs = [api.submit(p, max_new_tokens=10) for p in prompts]
        api.run_until_idle()
        refs = [r.output_ids() for r in reqs]
        d0 = api.engine.decode_traces
        rb0 = resilience.stats().get("serving.rebuilds", 0)
        # faulted pass: all three live (and sharing) when the device dies
        reqs2 = [api.submit(p, max_new_tokens=10) for p in prompts]
        for _ in range(3):
            api._pump_once()
        assert all(r.state == RequestState.RUNNING for r in reqs2)
        assert api.engine.arena.refcount(
            api.engine.prefix_cache.match(shared)[0].block) >= 2
        resilience.inject_fault("serving_device", times=1)
        api.run_until_idle()
        for ref, r in zip(refs, reqs2):
            assert r.state == RequestState.FINISHED
            np.testing.assert_array_equal(ref, r.output_ids())
        assert resilience.stats().get("serving.rebuilds", 0) == rb0 + 1
        assert api.engine.decode_traces == d0  # replay never recompiles
        # the replays re-populated the FRESH tree and re-shared it
        assert api.engine.prefix_cache.resident_blocks() >= 3
        assert api.engine.prefix_cache.hits >= 2
        # drain everything: no leaked blocks, refcounts all zero, only
        # cache-resident blocks may remain allocated
        import paddle_tpu.serving as serving_mod

        assert serving_mod.drain_all(grace=5) == 1
        api.engine.check_invariants()
        a = api.engine.arena.stats()
        assert a["blocks_reserved"] == 0
        assert a["blocks_in_use"] == a["blocks_cached"]
        assert api.engine.active_slots() == 0
        assert all(api.engine.arena.refcount(b) == 0
                   for b in range(1, api.engine.arena.num_blocks))
    finally:
        resilience.clear_faults()
        api.close()
        paddle.set_flags(keep)


@pytest.mark.slow
def test_drain_completes_in_flight_within_grace(model):
    """drain(grace) pumps already-admitted work to completion — the graceful
    half of shutdown: the in-flight request finishes with its full (parity-
    checked) output before the engine goes away."""
    api = ServingAPI(model, num_slots=2, kv_block_size=8,
                     max_model_len=MAX_LEN)
    try:
        rng = np.random.default_rng(43)
        p = _prompt(rng, 5)
        req = api.submit(p, max_new_tokens=6)
        api._pump_once()  # admitted and decoding
        assert req.state == RequestState.RUNNING
        api.drain(grace=30)
        assert req.state == RequestState.FINISHED
        np.testing.assert_array_equal(req.output_ids(), _ref(model, p, 6))
        assert api.engine.active_slots() == 0
        with pytest.raises(resilience.RequestDrainedError):
            api.submit(p, max_new_tokens=2)
    finally:
        api.close()


# ------------------------------------------- a prefill's whole-block write


def _row_scatter_reference(entry, table_rows, true_len, kc, vc, block_size):
    """What the full prefill did before it wrote whole blocks: one
    ``(block, offset)`` pair a POSITION, the padded ones to scratch block
    0 (``scatter_rows``, which suffix prefills and the decode step still
    use). Same signature as ``scatter_blocks``: the reference here."""
    import jax.numpy as jnp

    from paddle_tpu.serving.cache_views import scatter_rows

    p_idx = jnp.arange(kc.shape[0])
    row = jnp.where(p_idx < true_len, table_rows[p_idx // block_size], 0)
    return scatter_rows(entry, row, p_idx % block_size, kc, vc)


@pytest.mark.parametrize("p", [64, 44], ids=["whole_blocks", "ragged"])
@pytest.mark.parametrize("quantized", [False, True], ids=["f32", "int8"])
def test_scatter_blocks_matches_row_scatter(quantized, p):
    """``scatter_blocks`` against the row scatter on pools full of noise
    (block 8, 3 heads of 4; a chunk of ``p`` positions of which 37 are
    real, so block 4 of the chunk straddles ``true_len`` and the rest is
    padding; 44 is not a whole number of blocks): every real position bit
    for bit, payload and scales; nothing outside the slot's own blocks
    and scratch block 0 touched, not even the blocks the table names for
    the wholly padded part."""
    import jax.numpy as jnp

    from paddle_tpu.serving.cache_views import scatter_blocks

    rng = np.random.default_rng(p + quantized)
    nb, bs, h, d, true_len = 24, 8, 3, 4, 37
    if quantized:
        entry = tuple(jnp.asarray(rng.integers(-127, 127, (nb, bs, h, d)),
                                  jnp.int8) for _ in range(2))
        entry += tuple(jnp.asarray(rng.random((nb, bs)), jnp.float32)
                       for _ in range(2))
    else:
        entry = tuple(jnp.asarray(rng.standard_normal((nb, bs, h, d)),
                                  jnp.float32) for _ in range(2))
    kc, vc = (jnp.asarray(rng.standard_normal((p, h, d)), jnp.float32)
              for _ in range(2))
    # a table row for EVERY block of the chunk, the padded ones too
    table = rng.permutation(np.arange(1, nb))[:-(-p // bs)].astype(np.int32)
    args = (jnp.asarray(table), jnp.int32(true_len), kc, vc, bs)
    got = [np.asarray(a) for a in scatter_blocks(entry, *args)]
    ref = [np.asarray(a) for a in _row_scatter_reference(entry, *args)]
    was = [np.asarray(a) for a in entry]
    pos = np.arange(true_len)
    own = table[:-(-true_len // bs)]
    others = np.setdiff1d(np.arange(1, nb), own)
    assert len(got) == len(entry)
    for g, r, w in zip(got, ref, was):
        assert g.dtype == w.dtype and g.shape == w.shape
        np.testing.assert_array_equal(g[table[pos // bs], pos % bs],
                                      r[table[pos // bs], pos % bs])
        np.testing.assert_array_equal(g[others], w[others])
    # the reference leaves the straddling block's tail as it was; the
    # block write does not, and nothing reads it (below)
    tail = (own[-1], slice(true_len % bs, None))
    np.testing.assert_array_equal(ref[0][tail], was[0][tail])
    assert not np.array_equal(got[0][tail], was[0][tail])


@pytest.mark.parametrize("quant_kv", [False, True], ids=["f32", "int8"])
def test_block_prefill_under_a_padded_bucket(model, monkeypatch, quant_kv):
    """A 37-token prompt (block 8: 4 whole blocks and 5 positions of a
    fifth) admitted under a bucket of 64 beside a running lane: its
    positions lie in the pool bit for bit as the row scatter lays them,
    the other lane's blocks and every free block are as they were (the
    three wholly padded blocks of the chunk went to scratch block 0),
    and 20 decode steps, which cross the straddling block's end and two
    more, give the tokens of the same prompt under a bucket that fits it
    exactly and under the row scatter."""
    from paddle_tpu.serving import cache_views

    rng = np.random.default_rng(45)
    first, prompt = _prompt(rng, 10), _prompt(rng, 37)

    def serve(bucket_min):
        eng = ServingEngine(model, num_slots=2, kv_block_size=8,
                            max_model_len=MAX_LEN, quant_kv=quant_kv,
                            prefill_bucket_min=bucket_min)
        other, _ = eng.admit(first, max_new_tokens=40)
        before = [np.asarray(a) for e in eng.arena.pools for a in e]
        c0 = serving_metrics.stats().get("prefill.block_writes", 0)
        slot, tok = eng.admit(prompt, max_new_tokens=24)
        writes = serving_metrics.stats().get("prefill.block_writes", 0) - c0
        after = [np.asarray(a) for e in eng.arena.pools for a in e]
        toks = [tok] + [int(eng.decode_step()[slot]) for _ in range(20)]
        return SimpleNamespace(
            eng=eng, before=before, after=after, toks=toks, writes=writes,
            own=eng._bt_host[slot, :5].copy(),
            other=eng._bt_host[other, :2].copy())

    pos = np.arange(37)
    got = serve(64)
    assert list(got.eng.prefill_traces) == [64]
    assert got.writes == len(got.after) == (4 if quant_kv else 2) * len(
        got.eng.arena.pools)
    assert (got.own > 0).all()
    untouched = np.setdiff1d(np.arange(1, got.eng.arena.num_blocks), got.own)
    assert set(got.other) <= set(untouched)
    for b, a in zip(got.before, got.after):
        np.testing.assert_array_equal(a[untouched], b[untouched])

    fit = serve(37)
    assert list(fit.eng.prefill_traces) == [37] and fit.toks == got.toks

    monkeypatch.setattr(cache_views, "scatter_blocks",
                        _row_scatter_reference)
    ref = serve(64)
    assert ref.writes == got.writes  # the engine's count, not the write's
    for a, r, b in zip(got.after, ref.after, ref.before):
        np.testing.assert_array_equal(a[got.own[pos // 8], pos % 8],
                                      r[ref.own[pos // 8], pos % 8])
        # the reference it is: the straddling block's tail as it was
        np.testing.assert_array_equal(r[ref.own[4], 5:], b[ref.own[4], 5:])
    assert ref.toks == got.toks


def test_prefill_block_writes_counts_pools_an_admission(api):
    """``prefill.block_writes``: K and V of every layer, once a full
    prefill call, whatever the prompt's length."""
    pools = sum(len(e) for e in api.engine.arena.pools)
    assert pools == 2 * len(api.engine.arena.pools) > 0
    rng = np.random.default_rng(7)
    api.run_until_idle()
    before = serving_metrics.stats()
    for n in (3, 20):
        api.submit(_prompt(rng, n), max_new_tokens=2)
    api.run_until_idle()
    moved = serving_metrics.stats_delta(before, serving_metrics.stats())
    assert moved["prefill.calls"] == 2
    assert moved["prefill.block_writes"] == 2 * pools
