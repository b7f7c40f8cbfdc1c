"""One decode step in flight (ISSUE 32): the pump dispatches step N+1 before
it reads step N's tokens.

What is held here, at the tiny presets of the three served models on the
CPU: a pump that runs ahead serves, token for token, what a pump serves
that calls ``decode_step()`` synchronously every turn, over a script that
admits, ends requests by stop token and by budget, cancels, preempts and
expires a deadline while a step is in flight; a request admitted into a
slot that was just freed never gets the token the step in flight computed
for the slot's last tenant; constrained, speculative and ``active=`` turns
stay synchronous; the step is traced once and uploads no more; a device
fault at either half of a step is replayed exactly once; and no step in
flight outlives the work.

Cancellation and expiry are triggered by a request's token COUNT, not by a
turn number: every turn emits exactly one step's tokens in either pump, so
a request flagged once it holds n tokens ends with n + 1 in both.
"""
import time

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.core import resilience
from paddle_tpu.models.gpt import GPTForCausalLM, gpt_tiny
from paddle_tpu.serving import (RequestState, SamplingParams, ServingAPI,
                                ServingConfig)
from paddle_tpu.serving import metrics as serving_metrics
from paddle_tpu.serving.constrain import Constraint

pytestmark = pytest.mark.serving

MODELS = ("gpt", "olmo_hybrid", "phi4flash")
VOCAB = 512
ENGINE = dict(num_slots=3, kv_block_size=8, max_model_len=128)
SP = SamplingParams(temperature=0.8, top_k=40, top_p=0.9, seed=17)


#: the tiny presets of tests/test_olmo_hybrid.py and tests/test_phi4flash.py
OLMO_HYBRID = {
    "vocab_size": 512, "hidden_size": 64, "intermediate_size": 128,
    "num_hidden_layers": 4, "num_attention_heads": 4,
    "num_key_value_heads": 4, "max_position_embeddings": 256,
    "rms_norm_eps": 1e-6,
    "layer_types": ["linear_attention"] * 3 + ["full_attention"],
    "linear_num_key_heads": 4, "linear_num_value_heads": 4,
    "linear_key_head_dim": 8, "linear_value_head_dim": 16,
    "linear_conv_kernel_dim": 4, "linear_allow_neg_eigval": True,
}
PHI4FLASH = {
    "vocab_size": 512, "hidden_size": 64, "intermediate_size": 128,
    "num_hidden_layers": 8, "num_attention_heads": 8,
    "num_key_value_heads": 4, "max_position_embeddings": 256,
    "layer_norm_eps": 1e-5, "sliding_window": 8, "mb_per_layer": 2,
    "mamba_d_state": 8,
}


def _build(name):
    if name == "gpt":
        paddle.seed(0)
        model = GPTForCausalLM(gpt_tiny())
        model.eval()
        return model
    if name == "olmo_hybrid":
        from benchmark.hooks import olmo_hybrid as hook
        cfg = OLMO_HYBRID
    else:
        from benchmark.hooks import phi4flash as hook
        cfg = PHI4FLASH
    return hook.build_model(cfg, 11, "float32", train=False)


@pytest.fixture(scope="module")
def models():
    built = {}

    def get(name):
        if name not in built:
            built[name] = _build(name)
        return built[name]

    return get


@pytest.fixture(scope="module")
def apis(models):
    """One foreground API a model, shared by the tests that leave it open:
    its programs compile once."""
    keep = paddle.get_flags(["fault_injection", "serving_starvation_steps"])
    paddle.set_flags({"fault_injection": 1, "serving_starvation_steps": 2})
    made = {}

    def get(name):
        if name not in made:
            made[name] = ServingAPI(models(name),
                                    config=ServingConfig(**ENGINE))
        return made[name]

    yield get
    for api in made.values():
        api.close()
    resilience.clear_faults()
    paddle.set_flags(keep)


def _count(key):
    return serving_metrics.stats().get(key, 0)


def _prompt(rng, n):
    return rng.integers(0, VOCAB, (n,), dtype=np.int32)


class _Pump:
    """The API's pump, run ahead or held synchronous (the engine's
    ``decode_turn`` answering every turn as if asked ``ahead=False``, which
    is ``decode_step()`` dispatching and collecting in one call)."""

    def __init__(self, api, ahead):
        self.api, self.ahead = api, ahead

    def __enter__(self):
        if not self.ahead:
            turn = self.api.engine.decode_turn
            self.api.engine.decode_turn = lambda ahead: turn(False)
        return self

    def __exit__(self, *exc):
        self.api.engine.__dict__.pop("decode_turn", None)

    def run(self, events=()):
        """Pump until idle; before each turn fire every event whose
        condition holds (``(condition, action)`` pairs, each once)."""
        events = list(events)
        turns = 0
        while self.api.scheduler.has_work():
            for ev in [e for e in events if e[0]()]:
                events.remove(ev)
                ev[1]()
            self.api._pump_once()
            turns += 1
            assert turns < 400
        assert not events, "an event of the script never fired"
        return turns


def _solo(api, prompt, n):
    """A request's tokens served alone by the synchronous pump."""
    with _Pump(api, ahead=False) as pump:
        req = api.submit(prompt, max_new_tokens=n)
        pump.run()
    assert req.state == RequestState.FINISHED
    return list(req.tokens)


def _stop_for(tokens):
    """A stop token that ends the request inside its budget: the first
    token from the fourth on that did not occur before it."""
    for i in range(3, len(tokens) - 1):
        if tokens[i] not in tokens[:i]:
            return tokens[i], i + 1
    pytest.skip("no usable stop token in this model's greedy output")


def _churn(api, ahead, stop):
    """Three lanes, eight requests: three fill the lanes, five queue
    behind them; one ends by its stop token and the rest by budget, one is
    cancelled and one expires with a step in flight, and a high-priority
    arrival preempts a running request, which resumes from its journal.
    Returns every request's state and tokens and the window's counters."""
    rng = np.random.default_rng(32)
    c0 = {k: _count(k) for k in (
        "engine.steps", "engine.steps_run_ahead",
        "engine.lane_steps_discarded", "engine.step_uploads",
        "scheduler.preemptions", "tokens.generated")}
    with _Pump(api, ahead) as pump:
        reqs = {
            "budget": api.submit(_prompt(rng, 6), max_new_tokens=22,
                                 priority=5),
            "stop": api.submit(_prompt(rng, 9), max_new_tokens=12,
                               priority=5, stop_token_id=stop),
            "cancel": api.submit(_prompt(rng, 5), max_new_tokens=30,
                                 priority=5),
            "deadline": api.submit(_prompt(rng, 7), max_new_tokens=30,
                                   priority=5),
            "sampled": api.submit(_prompt(rng, 11), max_new_tokens=16,
                                  priority=5, sampling=SP),
            "late": api.submit(_prompt(rng, 8), max_new_tokens=18,
                               priority=5),
            "short": api.submit(_prompt(rng, 4), max_new_tokens=2,
                                priority=5),
        }
        urgent = _prompt(rng, 10)

        def expire():
            reqs["deadline"].deadline = resilience.Deadline(
                time.monotonic() - 1.0)

        def arrive():
            reqs["urgent"] = api.submit(urgent, max_new_tokens=7,
                                        priority=0)

        pump.run([
            (lambda: len(reqs["cancel"].tokens) >= 4,
             reqs["cancel"].cancel),
            (lambda: len(reqs["deadline"].tokens) >= 3, expire),
            # `late` is the third of three long streams by then and
            # `short` waits: the arrival finds no lane, waits two turns
            # and takes `late`'s, which resumes from its journal
            (lambda: len(reqs["late"].tokens) >= 2, arrive),
        ])
        assert api.engine.steps_in_flight == 0
    return {
        "state": {k: r.state for k, r in reqs.items()},
        "tokens": {k: list(r.tokens) for k, r in reqs.items()},
        "preempted": sum(r.preemptions for r in reqs.values()),
        **{k: _count(k) - v for k, v in c0.items()},
    }


@pytest.mark.parametrize("name", MODELS)
def test_run_ahead_pump_serves_the_synchronous_pumps_tokens(apis, name):
    api = apis(name)
    rng = np.random.default_rng(32)
    _prompt(rng, 6)
    stop, at = _stop_for(_solo(api, _prompt(rng, 9), 12))
    sync = _churn(api, False, stop)
    ahead = _churn(api, True, stop)
    want = dict.fromkeys(sync["state"], RequestState.FINISHED)
    want.update(cancel=RequestState.CANCELLED, deadline=RequestState.FAILED)
    assert sync["state"] == ahead["state"] == want
    assert ahead["tokens"] == sync["tokens"]
    # the script did what it says, in both pumps
    for run in (sync, ahead):
        t = run["tokens"]
        assert len(t["stop"]) == at and t["stop"][-1] == stop
        assert len(t["budget"]) == 22 and len(t["short"]) == 2
        assert len(t["cancel"]) == 5 and len(t["deadline"]) == 4
        assert run["scheduler.preemptions"] == 1 == run["preempted"]
        assert len(t["late"]) == 18 and len(t["urgent"]) == 7
    # the synchronous pump never had a step in flight behind another, and
    # computed no lane-step for a request that had ended
    assert sync["engine.steps_run_ahead"] == 0
    assert sync["engine.lane_steps_discarded"] == 0
    # the run-ahead pump did, on most steps; a request that ended or was
    # preempted left at most one lane-step behind, and the last step of
    # all was dropped whole
    assert ahead["engine.steps_run_ahead"] > ahead["engine.steps"] // 2
    assert 1 <= ahead["engine.lane_steps_discarded"] <= 2 * len(want)
    # what the clients got is what was counted, in both
    for run in (sync, ahead):
        assert run["tokens.generated"] >= sum(
            len(t) for t in run["tokens"].values())
    # one program, however its state arrived, and no more uploads a step:
    # a block table or the packed state goes up once per host write in
    # either pump (the discarded lane-steps may grow a table once more)
    assert api.engine.decode_traces == 1
    assert ahead["engine.step_uploads"] / ahead["engine.steps"] <= \
        sync["engine.step_uploads"] / sync["engine.steps"] + 0.1


@pytest.mark.parametrize("name", MODELS)
def test_request_in_a_freed_slot_never_gets_the_discarded_token(
        models, name):
    """Two lanes, one held by a long stream: `first` ends by budget with
    the next step already dispatched for its lane; `second` takes the slot
    in the next turn's admission pass, while that step is still unread."""
    api = ServingAPI(models(name), config=ServingConfig(
        **dict(ENGINE, num_slots=2)))
    try:
        rng = np.random.default_rng(7)
        p0, p1, p2 = _prompt(rng, 12), _prompt(rng, 6), _prompt(rng, 9)
        want = _solo(api, p2, 6)
        stream = api.submit(p0, max_new_tokens=40)
        first = api.submit(p1, max_new_tokens=4)
        second = api.submit(p2, max_new_tokens=6)
        seen, slot = [], None
        while not second.finished:
            api._pump_once()
            slot = first.slot if first.slot is not None else slot
            seen.append((first.state, len(second.tokens), second.slot,
                         api.engine.steps_in_flight,
                         _count("engine.lane_steps_discarded")))
        took_over = next(i for i, s in enumerate(seen) if s[1] >= 1)
        before, at = seen[took_over - 1], seen[took_over]
        # the turn before: `first` finished with a step in flight behind it
        assert before[:4] == (RequestState.FINISHED, 0, None, 1)
        # the turn of the take-over read that step, counted `first`'s lane
        # of it as computed for nobody, and gave `second`, in that very
        # slot by then, only its prefill's token
        assert at[:4] == (RequestState.FINISHED, 1, slot, 0)
        assert at[4] - before[4] == 1
        assert second.state == RequestState.FINISHED
        assert list(second.tokens) == want
        api.cancel(stream)
        api.run_until_idle()
        assert api.engine.steps_in_flight == 0
    finally:
        api.close()


class _AllBut(Constraint):
    """Every token but one is allowed, whatever came before."""

    def __init__(self, vocab, banned):
        self.vocab_size = vocab
        self.banned = banned

    def initial(self):
        return 0

    def advance(self, state, token):
        return state + 1

    def allowed(self, state):
        mask = np.ones(self.vocab_size, bool)
        mask[self.banned] = False
        return mask


@pytest.mark.parametrize("case", ["constrained", "active_override",
                                  "speculative", "beside_constrained"])
def test_turns_that_must_stay_synchronous_do(models, apis, case):
    rng = np.random.default_rng(3)
    ahead0 = _count("engine.steps_run_ahead")
    if case == "active_override":
        eng = apis("gpt").engine
        slot, _ = eng.admit(_prompt(rng, 6), 8)
        mask = np.zeros(eng.num_slots, bool)
        mask[slot] = True
        pos = int(eng._positions[slot])
        tok = eng.decode_step(active=mask)[slot]
        assert eng.steps_in_flight == 0
        assert int(eng._positions[slot]) == pos + 1
        assert int(eng._last_tok[slot]) == tok
        # with a step in flight an override is refused, not queued
        eng.decode_dispatch()
        with pytest.raises(RuntimeError, match="synchronous"):
            eng.decode_step(active=mask)
        eng.decode_collect()
        eng.retire(slot)
        assert _count("engine.steps_run_ahead") == ahead0
        return
    if case == "speculative":
        api = ServingAPI(models("gpt"), config=ServingConfig(
            **ENGINE, spec_k=2))
    else:
        api = apis("gpt")
    free = _solo(api, _prompt(np.random.default_rng(3), 7), 8)
    ahead0 = _count("engine.steps_run_ahead")
    try:
        kw = {}
        if case != "speculative":
            kw["constraint"] = _AllBut(api.engine.vocab, free[2])
        plain = None
        if case == "beside_constrained":
            # an unconstrained stream decodes beside it: while the
            # constrained one runs, no turn runs ahead for either
            plain = api.submit(_prompt(rng, 5), max_new_tokens=30)
            for _ in range(3):
                api._pump_once()
            assert api.engine.steps_in_flight == 1
            ahead0 = _count("engine.steps_run_ahead")
        req = api.submit(_prompt(np.random.default_rng(3), 7),
                         max_new_tokens=8, **kw)
        while not req.finished:
            api._pump_once()
            assert api.engine.steps_in_flight == 0
        assert _count("engine.steps_run_ahead") == ahead0
        assert req.state == RequestState.FINISHED
        if case == "speculative":
            assert list(req.tokens) == free
        else:
            assert list(req.tokens)[:2] == free[:2]
            assert free[2] not in req.tokens
        if plain is not None:
            # the constrained request gone, the pump runs ahead again
            api.run_until_idle()
            assert _count("engine.steps_run_ahead") > ahead0
            assert plain.state == RequestState.FINISHED
    finally:
        if case == "speculative":
            api.close()


@pytest.mark.parametrize("name", MODELS)
@pytest.mark.parametrize("site", ["dispatch", "dispatch_ahead", "collect"])
def test_a_device_fault_at_either_half_is_replayed_exactly_once(
        apis, name, site):
    """The fault is armed from inside the half it is to strike, on that
    half's nth call, so it fires in that call's own probe."""
    api = apis(name)
    eng = api.engine
    rng = np.random.default_rng(41)
    prompts = [_prompt(rng, 6), _prompt(rng, 10)]
    want = [_solo(api, p, 10) for p in prompts]
    half = "decode_collect" if site == "collect" else "decode_dispatch"
    # the first turn dispatches twice (the step, then the one behind it)
    # and collects once; later turns once each, the dispatch first
    nth = {"dispatch": 1, "dispatch_ahead": 3, "collect": 3}[site]
    sound, calls = getattr(eng, half), []

    def armed(*a):
        calls.append(eng.steps_in_flight)
        if len(calls) == nth:
            resilience.inject_fault(
                "serving_step", times=1,
                exc=resilience.ServingDeviceError("injected: " + site))
        return sound(*a)

    setattr(eng, half, armed)
    c0 = {k: _count(k) for k in ("supervisor.rebuilds",
                                 "supervisor.replays")}
    f0 = resilience.stats().get("fault.serving_step", 0)
    try:
        reqs = [api.submit(p, max_new_tokens=10) for p in prompts]
        api.run_until_idle()
    finally:
        eng.__dict__.pop(half, None)
        resilience.clear_faults()
    assert resilience.stats().get("fault.serving_step", 0) - f0 == 1
    # struck where it was aimed: a dispatch with no step in flight, a
    # dispatch behind an unread step, a collect with the next step out
    assert calls[nth - 1] == {"dispatch": 0, "dispatch_ahead": 1,
                              "collect": 2}[site]
    assert _count("supervisor.rebuilds") - c0["supervisor.rebuilds"] == 1
    assert _count("supervisor.replays") - c0["supervisor.replays"] == 2
    # no token emitted twice, none lost
    assert [r.state for r in reqs] == [RequestState.FINISHED] * 2
    assert [list(r.tokens) for r in reqs] == want
    assert eng.steps_in_flight == 0 and eng.decode_traces == 1


@pytest.mark.parametrize("aligned", [False, True],
                         ids=["partial_last_block", "block_aligned"])
def test_the_over_run_leaves_shared_prefix_blocks_as_they_were(
        models, aligned):
    """With the radix prefix cache on, a prompt's full blocks are shared by
    reference the moment its prefill ends. A request that ends with a step
    in flight has one more K/V row written, at its last token's position:
    past every full prompt block. The shared blocks' rows read the same
    before and after, the refcounts add up, and a later request over the
    same prefix is served from them token for token."""
    import jax

    api = ServingAPI(models("gpt"), config=ServingConfig(
        **ENGINE, prefix_cache=True))
    flag = paddle.get_flags("serving_arena_invariants")
    paddle.set_flags({"serving_arena_invariants": 1})
    try:
        eng, bs = api.engine, ENGINE["kv_block_size"]
        rng = np.random.default_rng(9)
        # two full blocks, then none or five tokens of a third
        prompt = _prompt(rng, 2 * bs + (0 if aligned else 5))
        with _Pump(api, ahead=False) as pump:
            first = api.submit(prompt, max_new_tokens=6)
            pump.run()
        shared = [n.block for n in eng.prefix_cache.match(prompt)]
        assert len(shared) == 2

        def rows():
            return [np.asarray(jax.device_get(leaf[np.asarray(shared)]))
                    for leaf in jax.tree_util.tree_leaves(eng.arena.pools)]

        before = rows()
        d0 = _count("engine.lane_steps_discarded")
        again = [api.submit(prompt, max_new_tokens=n) for n in (6, 3)]
        api.run_until_idle()
        assert _count("engine.lane_steps_discarded") - d0 >= 1
        assert [list(r.tokens) for r in again] == [
            list(first.tokens), list(first.tokens)[:3]]
        for was, now in zip(before, rows()):
            np.testing.assert_array_equal(was, now)
        eng.check_invariants()
        assert eng.steps_in_flight == 0
    finally:
        paddle.set_flags(flag)
        api.close()


@pytest.mark.parametrize("how", ["idle", "close", "fail_all", "rebuild"])
def test_no_step_in_flight_outlives_the_work(models, how):
    api = ServingAPI(models("gpt"), config=ServingConfig(**ENGINE))
    try:
        rng = np.random.default_rng(5)
        reqs = [api.submit(_prompt(rng, 6), max_new_tokens=n)
                for n in (6, 9)]
        for _ in range(3):
            api._pump_once()
        assert api.engine.steps_in_flight == 1
        if how == "idle":
            api.run_until_idle()
            assert all(r.state == RequestState.FINISHED for r in reqs)
        elif how == "close":
            api.close()
            assert all(isinstance(r.error, resilience.RequestDrainedError)
                       for r in reqs)
        elif how == "fail_all":
            api.scheduler.fail_all(RuntimeError("engine gone"))
            assert all(r.state == RequestState.FAILED for r in reqs)
        else:
            api.engine.rebuild()
        assert api.engine.steps_in_flight == 0
    finally:
        api.close()
