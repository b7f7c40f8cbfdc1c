"""Phi-4-mini-flash (Mamba layers, window attention, one full-attention
cache that cross-attention layers read, gated memory units) on the normal
serving path, at the tiny preset: the model and the engine against the plain
reference (``benchmark/reference/phi4flash.py``), LOGITS and not tokens.

Tolerances. Program and reference both compute in float32 here (conftest
pins full matmul precision), so they differ by summation order alone: the
largest difference seen is 1.3e-5 on logits whose standard deviation is 1.0.
``TOL`` = 1e-4 leaves 8 times that and is far under what each breakage of
``test_tolerance_fails_what_is_wrong`` moves the logits by (each is held to
more than ten times ``TOL``)."""
import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.core import compile_cache
from paddle_tpu.models import phi4flash as P
from paddle_tpu.ops import selective_scan as ssm
from paddle_tpu.serving import (RequestState, ServingAPI, ServingConfig,
                                ServingEngine)
from paddle_tpu.serving import metrics as serving_metrics

from benchmark.hooks import phi4flash as hook
from benchmark.reference import phi4flash as ref
from benchmark.weights import phi4flash as W

SEED = 11
CFG = {
    "vocab_size": 512, "hidden_size": 64, "intermediate_size": 128,
    "num_hidden_layers": 8, "num_attention_heads": 8,
    "num_key_value_heads": 4, "max_position_embeddings": 256,
    "layer_norm_eps": 1e-5, "sliding_window": 8, "mb_per_layer": 2,
    "mamba_d_state": 8,
}
HALF = CFG["num_hidden_layers"] // 2
ENGINE = dict(num_slots=3, kv_block_size=8, max_model_len=256)
TOL = 1e-4


def _build(**cfg):
    return hook.build_model(dict(CFG, **cfg), SEED, "float32", train=False)


@pytest.fixture(scope="module")
def weights():
    return W.all_weights(SEED, CFG, "float32")


def _prompt(rng, n):
    return rng.integers(0, CFG["vocab_size"], (n,), dtype=np.int32)


class Spy:
    """A model whose ``serving_head`` also hands every logits array it
    computes inside a compiled serving program back to the host."""

    def __init__(self, **cfg):
        self.model, self.seen = _build(**cfg), []
        head = self.model.serving_head

        def spy(h_last):
            out = head(h_last)
            jax.debug.callback(lambda a: self.seen.append(np.asarray(a)),
                               out)
            return out

        self.model.serving_head = spy

    def last(self):
        jax.effects_barrier()
        return self.seen[-1]


def _serve(spy, engine, prompts, steps):
    """Admit every prompt, then ``steps`` decode steps with all of them
    live. Returns per prompt (slot, tokens, logits of every token)."""
    lanes = []
    for p in prompts:
        slot, first = engine.admit(p, steps + 1)
        lanes.append((slot, [int(first)], [spy.last()[0]]))
    for _ in range(steps):
        out = engine.decode_step()
        rows = spy.last()
        for slot, toks, logits in lanes:
            toks.append(int(out[slot]))
            logits.append(rows[slot])
    return lanes


def _reference_rows(weights, prompt, toks, cfg=CFG):
    """The reference's logits at the positions that gave ``toks``."""
    full = ref.logits(weights, cfg, list(prompt) + toks[:-1])
    return np.asarray(full[len(prompt) - 1:])


def _worst(weights, prompts, lanes, cfg=CFG):
    return max(float(np.max(np.abs(np.stack(logits) - _reference_rows(
        weights, p, toks, cfg)))) for p, (_, toks, logits)
        in zip(prompts, lanes))


# ------------------------------------------------------------- the model


def test_layer_kinds_and_what_the_model_declares():
    cfg = P.phi4flash_tiny()
    kinds = [cfg.kind_of(i) for i in range(8)]
    assert kinds == [P.MAMBA, P.SWA, P.MAMBA, P.SWA, P.MAMBA, P.FULL,
                     P.GMU, P.CROSS]
    big = P.Phi4FlashConfig()
    assert [i for i in range(32) if big.kind_of(i) == P.FULL] == [17]
    assert sum(big.kind_of(i) == P.SWA for i in range(32)) == 8
    assert sum(big.kind_of(i) == P.CROSS for i in range(32)) == 7
    assert sum(big.kind_of(i) == P.MAMBA for i in range(32)) == 9
    assert big.mamba_dt_rank == 160 and big.d_inner == 5120
    spec = _build().serving_spec()
    assert [s.kind for s in spec.layers] == [
        "recurrent", "window", "recurrent", "window", "recurrent", "kv",
        "none", "shared"]
    assert spec.prefill_tail == HALF + 2 and spec.layers[7].source == HALF + 1
    full = spec.layers[5]
    assert (full.num_heads, full.kv_heads, full.head_dim) == (8, 2, 16)


def test_model_forward_matches_reference(weights):
    ids = _prompt(np.random.default_rng(0), 60)
    got = _build()(paddle.to_tensor(ids[None]))._data[0]
    want = ref.logits(weights, CFG, ids)
    assert got.shape == want.shape == (60, CFG["vocab_size"])
    assert float(jnp.max(jnp.abs(got - want))) < TOL


def test_teacher_forced_pass_reads_the_same_rows(weights):
    ids = [int(t) for t in _prompt(np.random.default_rng(1), 90)]
    full = ref.logits(weights, CFG, ids)
    rows = ref.teacher_forced_logits(SEED, CFG, "float32", ids[:60],
                                     ids[60:], pad_to=32, cap=64)
    assert float(jnp.max(jnp.abs(rows - full[59:89]))) < TOL


@pytest.mark.parametrize("wrong", ["window_7", "window_9", "no_lambda",
                                   "cross_reads_window_layer",
                                   "bfloat16_scan_state"])
def test_tolerance_fails_what_is_wrong(weights, wrong, monkeypatch):
    """Each of these must move the logits by far more than ``TOL``: a window
    one key short or long, the learned part of ``lam`` left out, a cross
    layer that reads the K/V of a window layer instead of the full one, a
    scan whose state is rounded to bfloat16."""
    ids = _prompt(np.random.default_rng(2), 60)
    model, cfg, w = _build(), CFG, weights
    if wrong.startswith("window"):
        cfg = dict(CFG, sliding_window=int(wrong[-1]))
    elif wrong == "no_lambda":
        w = dict(weights, layers=[
            {k: jnp.zeros_like(v) if k in ("lq1", "lk1", "lq2", "lk2")
             else v for k, v in p.items()} for p in weights["layers"]])
    elif wrong == "bfloat16_scan_state":
        update = ssm._update

        def rounded(state, *a):
            state, y = update(state, *a)
            return state.astype(jnp.bfloat16).astype(jnp.float32), y

        monkeypatch.setattr(ssm, "_update", rounded)
    # op by op, not through the jit cache: that would hand back (and keep)
    # a trace made with another ``_update``
    keep = paddle.get_flags(["eager_jit_ops"])
    paddle.set_flags({"eager_jit_ops": False})
    try:
        got = model(paddle.to_tensor(ids[None]))._data[0]
    finally:
        paddle.set_flags(keep)
    if wrong == "cross_reads_window_layer":
        seen, attend = {}, ref.diff_attention

        def record(q, k, v, p, index, *a):
            seen[index] = (k, v)
            return attend(q, k, v, p, index, *a)

        monkeypatch.setattr(ref, "diff_attention", record)
        sz = W.sizes(CFG)
        table = weights["embed"]["embed"]
        x, mem = table[jnp.asarray(ids)], {}
        for i, p in enumerate(weights["layers"]):
            x, mem = ref.block(x, p, W.kind_of(CFG, i), i, sz, 1e-5,
                               sz["sliding_window"], mem, i == HALF)
            if i == HALF + 1:
                mem = dict(mem, k=seen[HALF - 1][0], v=seen[HALF - 1][1])
        want = ref._head(x, weights["final"], table, 1e-5)
    else:
        want = ref.logits(w, cfg, ids)
    assert float(jnp.max(jnp.abs(got - want))) > 10 * TOL


@pytest.mark.parametrize("t", [1, 15, 16, 17, 70])
def test_chunked_scan_matches_token_serial(t):
    b, d, n = 2, 24, 8
    ks = jax.random.split(jax.random.key(t), 7)
    x = jax.random.normal(ks[0], (b, t, d))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, t, d)) - 2.0)
    bm = jax.random.normal(ks[2], (b, t, n))
    cm = jax.random.normal(ks[3], (b, t, n))
    a = -jnp.exp(jax.random.normal(ks[4], (d, n)))
    skip = jax.random.normal(ks[5], (d,))
    s0 = jax.random.normal(ks[6], (b, n, d))  # a non-zero start

    def serial(upto):
        s, ys = s0, []
        for i in range(upto):
            y, s = ssm.selective_step(x[:, i], dt[:, i], bm[:, i], cm[:, i],
                                      a, skip, s)
            ys.append(y)
        return jnp.stack(ys, 1), s

    y1, s1 = serial(t)
    y2, s2 = ssm.selective_scan(x, dt, bm, cm, a, skip, s0)
    assert float(jnp.max(jnp.abs(y1 - y2))) < 1e-5
    assert float(jnp.max(jnp.abs(s1 - s2))) < 1e-5
    # padded past a true length: outputs up to it and the state AT it
    m = t // 2 + 1
    y3, s3 = ssm.selective_scan(x, dt, bm, cm, a, skip, s0, valid_len=m)
    y4, s4 = serial(m)
    assert float(jnp.max(jnp.abs(y3[:, :m] - y4))) < 1e-5
    assert float(jnp.max(jnp.abs(s3 - s4))) < 1e-5
    # the step is one explicit update of every (state, channel) pair
    s_ref = jnp.exp(dt[:, 0, None, :] * a.T) * s0 \
        + (dt[:, 0] * x[:, 0])[:, None, :] * bm[:, 0, :, None]
    assert float(jnp.max(jnp.abs(serial(1)[1] - s_ref))) < 1e-6


# ------------------------------------------------------------ the engine


def test_engine_prefill_then_decode_logits_past_the_window(weights):
    """Two lanes of unequal length (one prompt shorter than a prefill
    bucket, both several windows long), 20 decode steps: every token's
    logits against the reference's full forward. The window of 8 has turned
    over more than twice by the end."""
    spy = Spy()
    engine = ServingEngine(spy.model, config=ServingConfig(**ENGINE))
    assert len(engine.arena.pools) == 1 and len(engine.arena.slot_state) == 5
    rng = np.random.default_rng(3)
    prompts = [_prompt(rng, 37), _prompt(rng, 21)]
    lanes = _serve(spy, engine, prompts, 20)
    assert _worst(weights, prompts, lanes) < TOL
    for slot, _, _ in lanes:
        engine.retire(slot)
    # a prompt shorter than the window
    short = [_prompt(rng, 5)]
    assert _worst(weights, short, _serve(spy, engine, short, 12)) < TOL


def test_the_tail_runs_on_one_token_a_prefill_and_changes_nothing(weights):
    """``prefill.tail_tokens`` counts one token a prefill, and the logits
    equal those of an engine whose model declares no tail (every layer on
    every token)."""
    rng = np.random.default_rng(4)
    prompts = [_prompt(rng, 33), _prompt(rng, 20)]
    runs = []
    for tail in (True, False):
        spy = Spy()
        if not tail:
            declared = spy.model.serving_spec
            spy.model.serving_spec = lambda: dataclasses.replace(
                declared(), prefill_tail=None)
        before = dict(serving_metrics.stats())
        engine = ServingEngine(spy.model, config=ServingConfig(**ENGINE))
        lanes = _serve(spy, engine, prompts, 4)
        moved = {k: serving_metrics.stats()[k] - before.get(k, 0)
                 for k in ("prefill.body_tokens", "prefill.tail_tokens")}
        assert moved["prefill.body_tokens"] == 53
        assert moved["prefill.tail_tokens"] == (2 if tail else 53)
        runs.append(lanes)
    for (_, ta, la), (_, tb, lb) in zip(*runs):
        assert ta == tb
        assert float(np.max(np.abs(np.stack(la) - np.stack(lb)))) < TOL
    assert _worst(weights, prompts, runs[0]) < TOL


def test_lane_reuse_block_count_and_no_recompile(weights):
    spy = Spy()
    engine = ServingEngine(spy.model, config=ServingConfig(**ENGINE))
    rng = np.random.default_rng(5)
    pa, pb = _prompt(rng, 40), _prompt(rng, 23)
    (slot, _, _), = _serve(spy, engine, [pa], 2)  # warm A's bucket
    engine.retire(slot)
    (slot, tb, lb), = _serve(spy, engine, [pb], 18)  # B in a fresh lane 0
    assert slot == 0
    # blocks held = ceil(context / 8) for ONE layer, whatever the window
    # layers hold: B's context is 23 + 18 tokens written so far
    assert int(engine._slot_filled[0]) == math.ceil((23 + 18) / 8)
    assert engine.arena.blocks_in_use() == int(engine._slot_filled[0])
    one_layer = 2 * engine.arena.num_blocks * 8 * 2 * 16 * 4  # K, V; f32
    assert engine.arena.bytes_total() == one_layer
    engine.retire(slot)
    assert engine.arena.blocks_in_use() == 0
    warm = {k: compile_cache.stats().get(k, 0) for k in (
        "serving.decode_compiles", "serving.prefill_compiles")}
    resets0 = serving_metrics.stats().get("state.resets", 0)
    # A, then B in the lane A just left: A's Mamba state and A's window are
    # still in it, and B's logits are what they were in the fresh lane
    (slot, _, _), = _serve(spy, engine, [pa], 14)
    engine.retire(slot)
    (slot, tb2, lb2), = _serve(spy, engine, [pb], 18)
    assert slot == 0 and tb2 == tb
    assert float(np.max(np.abs(np.stack(lb) - np.stack(lb2)))) < 1e-5
    assert _worst(weights, [pb], [(slot, tb2, lb2)]) < TOL
    assert serving_metrics.stats()["state.resets"] - resets0 == 2
    assert {k: compile_cache.stats().get(k, 0) for k in warm} == warm
    assert engine.decode_traces == 1
    assert set(engine.prefill_traces.values()) == {1}
    g = serving_metrics.gauges()
    assert g["arena.paged_layers"] == 1 and g["arena.kv_readers"] == 2
    lanes = ENGINE["num_slots"]
    assert g["state.window_bytes"] == 2 * lanes * 2 * 8 * 2 * 16 * 4
    assert g["state.ssm_bytes"] == 3 * lanes * (8 * 128 * 4 + 3 * 128 * 4)
    assert g["state.bytes_total"] == g["state.window_bytes"] \
        + g["state.ssm_bytes"] == engine.arena.state_bytes_total()


def test_paged_kernels_serve_the_grouped_heads(weights):
    """``paged_kernel=True``: the decode step's full layer and its cross
    reader go through the Pallas decode kernel (interpreted here), 8 query
    heads over 2 K/V heads, the prefill's full layer through the prefill
    kernel where it runs on every row."""
    rng = np.random.default_rng(6)
    prompts = [_prompt(rng, 26), _prompt(rng, 9)]
    for tail in (True, False):
        spy = Spy()
        if not tail:
            declared = spy.model.serving_spec
            spy.model.serving_spec = lambda: dataclasses.replace(
                declared(), prefill_tail=None)
        engine = ServingEngine(spy.model, config=ServingConfig(
            **ENGINE, paged_kernel=True))
        assert engine.kernel_route() == "kernel@single"
        lanes = _serve(spy, engine, prompts, 10)
        # online against full-width softmax: test_paged_kernel.py's bound
        assert _worst(weights, prompts, lanes) < 2 * TOL


def test_served_through_the_scheduler_and_preempted(weights):
    keep = paddle.get_flags(["serving_starvation_steps"])
    paddle.set_flags({"serving_starvation_steps": 2})
    rng = np.random.default_rng(7)
    p1, p2 = _prompt(rng, 30), _prompt(rng, 12)
    try:
        api = ServingAPI(_build(), config=ServingConfig(
            num_slots=1, kv_block_size=8, max_model_len=256))
        plain = api.submit(p1, max_new_tokens=16)
        api.run_until_idle()
        low = api.submit(p1, max_new_tokens=16, priority=5)
        for _ in range(5):
            api._pump_once()
        assert low.state == RequestState.RUNNING and 0 < len(low.tokens) < 16
        high = api.submit(p2, max_new_tokens=4, priority=0)
        api.run_until_idle()
        assert high.state == low.state == RequestState.FINISHED
        assert low.preemptions >= 1
        assert list(low.tokens) == list(plain.tokens)
        api.close()
    finally:
        paddle.set_flags(keep)


@pytest.mark.parametrize("option, kw", [
    ("prefix_cache", dict(prefix_cache=True)),
    ("kv_tiering", dict(kv_tiering=True)),
    ("spec_k", dict(spec_k=2)),
    ("chunked_prefill", dict(chunked_prefill=8)),
])
def test_options_that_assume_blocks_are_refused_by_name(option, kw):
    with pytest.raises(ValueError, match=option):
        ServingEngine(_build(), config=ServingConfig(**ENGINE, **kw))


def test_disaggregated_handoff_is_refused():
    from paddle_tpu.serving.disagg import DisaggReplicaPool

    with pytest.raises(ValueError, match="disaggregated"):
        DisaggReplicaPool(_build(), prefill_replicas=1, decode_replicas=1)


def test_a_shared_layer_must_name_a_paged_layer_before_it():
    model = _build()
    declared = model.serving_spec()
    layers = list(declared.layers)
    layers[7] = dataclasses.replace(layers[7], source=3)  # a window layer
    model.serving_spec = lambda: dataclasses.replace(
        declared, layers=tuple(layers))
    with pytest.raises(ValueError, match="shares the cache of layer 3"):
        ServingEngine(model, config=ServingConfig(**ENGINE))


def test_int8_weights_and_kv_are_served(weights):
    """The control's path: the quantizer finds every linear the model
    declares, the int8 arena holds the full layer's K/V (its readers read
    it dequantized), the window store stays in the served dtype."""
    spy = Spy()
    engine = ServingEngine(spy.model, config=ServingConfig(
        **ENGINE, quant_weights=True, quant_kv=True))
    layers = spy.model.model.layers
    assert str(layers[0].mixer.in_proj.weight._data.dtype) == "int8"
    assert str(layers[7].mixer.q_proj.weight._data.dtype) == "int8"
    assert len(engine.arena.pools[0]) == 4
    assert str(engine.arena.slot_state[1][0].dtype) == "float32"
    prompts = [_prompt(np.random.default_rng(8), 50)]
    worst = _worst(weights, prompts, _serve(spy, engine, prompts, 12))
    assert 10 * TOL < worst < 0.5  # near the reference, not on it
