"""The decode step's per-slot state lives on the device (ISSUE 25): the
step hands the next step's packed state back, the host re-sends its
mirrors only after it wrote them, in one upload, and
``engine.step_uploads`` counts every transfer ``decode.prepare`` makes.

What is held here: a churned run (admissions, retirements, a preemption,
a supervisor rebuild with replay; greedy, sampled and adapter lanes; the
gather and the paged-kernel route) serves the same tokens as the same run
with the device copy thrown away before every step; the device copy,
fetched at the entry of every step, equals the host's mirrors; the
counter moves only when the host wrote; and none of it compiles a second
decode program.
"""
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.core import compile_cache, resilience
from paddle_tpu.models.gpt import GPTForCausalLM, gpt_tiny
from paddle_tpu.serving import (
    LoraAdapter,
    RequestState,
    SamplingParams,
    ServingAPI,
    ServingConfig,
    ServingEngine,
)
from paddle_tpu.serving import metrics as serving_metrics
from paddle_tpu.serving.engine import _ST_TOK

pytestmark = pytest.mark.serving

MAX_LEN = 64
SP = SamplingParams(temperature=0.8, top_k=40, top_p=0.9, seed=17)


@pytest.fixture(scope="module")
def model():
    paddle.seed(0)
    m = GPTForCausalLM(gpt_tiny())
    m.eval()
    return m


def _prompt(rng, n):
    return rng.integers(0, 1024, (n,), dtype=np.int32)


def _uploads():
    return serving_metrics.stats().get("engine.step_uploads", 0)


# --------------------------------------------------- hooks on _step_args


def _always_dirty(engine):
    """Throw the device copy away before every step: the engine then
    re-sends its mirrors each time, as it did before the state was kept.
    The mirrors are whole only with no step in flight, so this run's pump
    also reads every step before it dispatches the next."""
    sound, turn = engine._step_args, engine.decode_turn

    def dirty_first(act):
        engine._touch_slot_state("override")
        return sound(act)

    engine._step_args = dirty_first
    engine.decode_turn = lambda ahead: turn(False)


def _watch_invariant(engine, seen):
    """At the entry of every step, before anything is uploaded: a device
    copy the engine still trusts must equal the host's mirrors. Mismatches
    are collected (an assert inside the pump would be a failed request,
    not a failed test)."""
    sound = engine._step_args

    def checked(act):
        want = engine._pack_slot_state(act)
        # a step dispatched behind one whose tokens are not read yet: the
        # device is ahead of the last-token mirror, by those tokens
        rows = [r for r in range(want.shape[0])
                if r != _ST_TOK or not engine.steps_in_flight]
        if engine._state_dev is not None:
            seen["carried"] += 1
            if not np.array_equal(np.asarray(engine._state_dev)[rows],
                                  want[rows]):
                seen["bad"].append(("carried", seen["steps"]))
        args = sound(act)
        if not np.array_equal(np.asarray(args[3])[rows], want[rows]):
            seen["bad"].append(("sent", seen["steps"]))
        seen["steps"] += 1
        return args

    engine._step_args = checked


# ------------------------------------------------------- the churned run


def _churned_run(model, lora, kernel, hook, chunk=None):
    """Three lanes, eleven requests: two low-priority streams and a
    sampled one fill the lanes, a high-priority arrival preempts, a device
    fault mid-decode makes the supervisor rebuild and replay, then a
    second wave of unequal lengths churns admissions and retirements
    (with ``chunk``, long prompts prefill a chunk a turn while the other
    lanes decode: slots that are claimed but not yet active).
    Returns every request's tokens and what happened on the way."""
    keep = paddle.get_flags(["fault_injection", "serving_starvation_steps"])
    paddle.set_flags({"fault_injection": 1, "serving_starvation_steps": 2})
    api = ServingAPI(model, config=ServingConfig(
        num_slots=3, kv_block_size=8, max_model_len=MAX_LEN,
        lora_rank=4 if lora else None, lora_adapters=2 if lora else None,
        paged_kernel=kernel, chunked_prefill=chunk))
    try:
        hook(api.engine)
        aid = (api.register_adapter(LoraAdapter.random(
            model.cfg, rank=4, seed=7, scale=0.25)) if lora else 0)
        rng = np.random.default_rng(25)
        pre0 = serving_metrics.stats().get("scheduler.preemptions", 0)
        rb0 = resilience.stats().get("serving.rebuilds", 0)
        cc0 = compile_cache.stats().get("serving.decode_compiles", 0)
        reqs = [api.submit(_prompt(rng, 6), max_new_tokens=18, priority=5),
                api.submit(_prompt(rng, 9), max_new_tokens=18, priority=5,
                           sampling=SP),
                api.submit(_prompt(rng, 5), max_new_tokens=18, priority=5,
                           adapter=aid)]
        api._pump_once()  # all three admitted: the lanes are full
        assert all(r.state == RequestState.RUNNING for r in reqs)
        reqs.append(api.submit(_prompt(rng, 20), max_new_tokens=24,
                               priority=0))  # cannot fit: preempts
        for _ in range(6):
            api._pump_once()
        resilience.inject_fault("serving_device", times=1)
        api.run_until_idle()
        for i, n in enumerate((3, 11, 6, 14, 4, 9, 7)):
            reqs.append(api.submit(
                _prompt(rng, 4 + 3 * i), max_new_tokens=n,
                sampling=SP if i % 3 == 1 else None,
                adapter=aid if i % 3 == 2 else 0))
        api.run_until_idle()
        assert all(r.state == RequestState.FINISHED for r in reqs)
        return {
            "tokens": [list(r.tokens) for r in reqs],
            "preemptions": serving_metrics.stats().get(
                "scheduler.preemptions", 0) - pre0,
            "rebuilds": resilience.stats().get("serving.rebuilds", 0) - rb0,
            "decode_traces": api.engine.decode_traces,
            "decode_compiles": compile_cache.stats().get(
                "serving.decode_compiles", 0) - cc0,
        }
    finally:
        resilience.clear_faults()
        api.close()
        paddle.set_flags(keep)


@pytest.mark.parametrize("lora, kernel, chunk", [
    (False, False, None), (True, False, None), (False, True, None),
    (True, True, None), (True, False, 8)],
    ids=["gather", "gather-lora", "kernel", "kernel-lora",
         "gather-lora-chunked"])
def test_churned_run_serves_the_same_tokens_with_the_state_kept(
        model, lora, kernel, chunk):
    """(a) token for token against the run that uploads every step;
    (b) the device copy equals the mirrors at the entry of every step;
    (d) one decode trace in each run, whichever way its state arrived."""
    seen = {"steps": 0, "carried": 0, "bad": []}
    kept = _churned_run(model, lora, kernel,
                        lambda e: _watch_invariant(e, seen), chunk)
    sent = _churned_run(model, lora, kernel, _always_dirty, chunk)
    assert kept["tokens"] == sent["tokens"]
    assert seen["bad"] == []
    # the run did carry its state over most steps, and was churned
    assert seen["carried"] > seen["steps"] // 2 > 10
    for run in (kept, sent):
        assert run["preemptions"] >= 1 and run["rebuilds"] == 1
        assert run["decode_traces"] == 1 and run["decode_compiles"] == 1


# ------------------------------------------------------------ the counter


@pytest.fixture()
def engine(model):
    return ServingEngine(model, num_slots=3, kv_block_size=8,
                         max_model_len=MAX_LEN)


def test_quiet_steps_upload_nothing_and_an_admission_at_most_two(engine):
    """(c) a prompt of 9 writes at positions 9..15 of its second block:
    six steps with no admission, retirement or block growth add nothing to
    ``engine.step_uploads``; the step at 16 sends the grown table alone;
    the step after an admission sends the state and the table."""
    rng = np.random.default_rng(1)
    engine.admit(_prompt(rng, 9), 30)
    engine.decode_step()  # the first step sends state, table and mask
    quiet = _uploads()
    for _ in range(6):
        engine.decode_step()
    assert _uploads() == quiet
    engine.decode_step()  # position 16: a new block
    assert _uploads() == quiet + 1
    slot, _ = engine.admit(_prompt(rng, 5), 30)
    engine.decode_step()
    assert 1 <= _uploads() - (quiet + 1) <= 2
    before = _uploads()
    engine.decode_step()
    assert _uploads() == before
    engine.retire(slot)
    engine.decode_step()
    assert 1 <= _uploads() - before <= 2


def test_a_step_that_raises_leaves_the_state_stale(model, engine):
    """(c) an exception out of the step's call: its outputs never came, so
    the next step starts from the host's mirrors, and the tokens are those
    of an engine that never failed."""
    rng = np.random.default_rng(2)
    p = _prompt(rng, 7)
    ref = ServingEngine(model, num_slots=3, kv_block_size=8,
                        max_model_len=MAX_LEN)
    slot, _ = ref.admit(p, 12)
    want = [int(ref.decode_step()[slot]) for _ in range(8)]

    slot, _ = engine.admit(p, 12)
    got = [int(engine.decode_step()[slot]) for _ in range(3)]
    assert engine._state_dev is not None
    sound = engine._call

    def failing(fn, *args, name, **kw):
        raise RuntimeError("injected: the step's call died")

    engine._call = failing
    with pytest.raises(RuntimeError, match="injected"):
        engine.decode_step()
    assert engine._state_dev is None
    engine._call = sound
    before = _uploads()
    got += [int(engine.decode_step()[slot]) for _ in range(5)]
    assert got == want
    assert _uploads() - before >= 1  # the mirrors went up again


def test_an_active_override_is_stale_before_and_after(engine):
    """A caller's ``active=`` is not the engine's own mask: that step and
    the next one both send the mirrors, and lanes left out do not move."""
    rng = np.random.default_rng(3)
    a, _ = engine.admit(_prompt(rng, 5), 20)
    b, _ = engine.admit(_prompt(rng, 6), 20)
    engine.decode_step()
    pos_b = int(engine._positions[b])
    only_a = np.zeros(engine.num_slots, bool)
    only_a[a] = True
    engine.decode_step(active=only_a)
    assert engine._state_dev is None
    assert int(engine._positions[b]) == pos_b
    engine.decode_step()
    want = engine._pack_slot_state(engine._active)
    np.testing.assert_array_equal(np.asarray(engine._state_dev), want)
    assert int(engine._positions[b]) == pos_b + 1


# ------------------------------------------------- the speculative decoder


def test_spec_decoder_marks_the_state_stale_and_falls_back_soundly(model):
    """(e) the speculative decoder advances the host's mirrors itself: the
    plain step it drives for its sampled lanes (an ``active=`` override)
    starts from them, and every stream equals the speculation-off run."""
    rng = np.random.default_rng(4)
    p1, p2 = _prompt(rng, 5), _prompt(rng, 7)

    def serve(hook=None, **cfg):
        api = ServingAPI(model, config=ServingConfig(
            num_slots=3, kv_block_size=8, max_model_len=MAX_LEN, **cfg))
        try:
            if hook is not None:
                hook(api.engine)
            reqs = [api.submit(p1, max_new_tokens=10, sampling=SP),
                    api.submit(p2, max_new_tokens=10)]
            api.run_until_idle()
            return [list(r.tokens) for r in reqs], api.engine
        finally:
            api.close()

    plain, _ = serve()
    seen = {"steps": 0, "carried": 0, "bad": []}
    spec, engine = serve(lambda e: _watch_invariant(e, seen), spec_k=2)
    assert spec == plain
    assert engine.stats()["spec.emitted"] > 0
    # every plain step under speculation was sent anew, and sent right
    assert seen["steps"] > 0 and seen["carried"] == 0 and seen["bad"] == []


def test_plain_steps_around_a_speculative_one_start_from_its_writes(model):
    """(e) engine level: plain step, speculative step, plain step on one
    greedy lane. The speculative step wrote positions and last token on
    the host only, so the device copy the first plain step left must not
    be trusted by the second: the stream is plain decode's."""
    p = _prompt(np.random.default_rng(5), 6)
    ref = ServingEngine(model, num_slots=2, kv_block_size=8,
                        max_model_len=MAX_LEN)
    slot, _ = ref.admit(p, 20)
    want = [int(ref.decode_step()[slot]) for _ in range(8)]

    eng = ServingEngine(model, num_slots=2, kv_block_size=8,
                        max_model_len=MAX_LEN, spec_k=2)
    slot, _ = eng.admit(p, 20)
    got = [int(eng.decode_step()[slot])]
    assert eng._state_dev is not None
    got += eng.spec_decode_step()[slot]
    assert eng._state_dev is None
    while len(got) < len(want):
        got.append(int(eng.decode_step()[slot]))
    assert got == want[:len(got)] and len(got) >= 4
