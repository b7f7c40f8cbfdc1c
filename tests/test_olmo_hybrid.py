"""Olmo-Hybrid (gated-delta linear attention beside full attention) on the
normal serving path: the model against its plain reference, the chunkwise
rule against the token-serial one, and prefill + decode through the paged
arena and the slot-indexed state store, judged by the benchmark's measure."""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.core import compile_cache
from paddle_tpu.ops import gated_delta as gd
from paddle_tpu.serving import (RequestState, ServingAPI, ServingConfig,
                                ServingEngine)
from paddle_tpu.serving import metrics as serving_metrics

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "refs"))
import olmo_hybrid_ref as ref  # noqa: E402

from benchmark.hooks import olmo_hybrid as hook  # noqa: E402
from benchmark.reference import olmo_hybrid as bench_ref  # noqa: E402
from benchmark.weights import olmo_hybrid as W  # noqa: E402

SEED = 11
CFG = {
    "vocab_size": 512, "hidden_size": 64, "intermediate_size": 128,
    "num_hidden_layers": 4, "num_attention_heads": 4,
    "num_key_value_heads": 4, "max_position_embeddings": 256,
    "rms_norm_eps": 1e-6,
    "layer_types": ["linear_attention"] * 3 + ["full_attention"],
    "linear_num_key_heads": 4, "linear_num_value_heads": 4,
    "linear_key_head_dim": 8, "linear_value_head_dim": 16,
    "linear_conv_kernel_dim": 4, "linear_allow_neg_eigval": True,
}
ENGINE = dict(num_slots=2, kv_block_size=8, max_model_len=256)


@pytest.fixture(scope="module")
def model():
    return hook.build_model(CFG, SEED, "float32", train=False)


@pytest.fixture(scope="module")
def weights():
    return W.all_weights(SEED, CFG, "float32")


def _prompt(rng, n):
    return rng.integers(0, CFG["vocab_size"], (n,), dtype=np.int32)


def _serve(engine, prompt, n):
    """Admit ``prompt`` and decode ``n`` tokens; returns (slot, tokens)."""
    slot, first = engine.admit(prompt, n)
    toks = [int(first)]
    while len(toks) < n:
        toks.append(int(engine.decode_step()[slot]))
    return slot, toks


def _gaps(weights, prompt, toks):
    """The benchmark's measure: at every served token, the reference's best
    logit minus the reference's logit of the token that was served."""
    full = ref.logits(weights, CFG, list(prompt) + toks)
    rows = full[len(prompt) - 1:len(prompt) - 1 + len(toks)]
    picked = jnp.take_along_axis(rows, jnp.asarray(toks)[:, None], -1)[:, 0]
    return np.asarray(jnp.max(rows, -1) - picked)


def test_model_forward_matches_reference(model, weights):
    ids = _prompt(np.random.default_rng(0), 150)
    got = model(paddle.to_tensor(ids[None]))._data[0]
    want = ref.logits(weights, CFG, ids)
    assert got.shape == want.shape == (150, CFG["vocab_size"])
    assert float(jnp.max(jnp.abs(got - want))) < 1e-4


def test_benchmark_reference_equals_the_repos(weights):
    ids = _prompt(np.random.default_rng(1), 90)
    a = bench_ref.logits(weights, CFG, ids)
    b = ref.logits(weights, CFG, ids)
    assert float(jnp.max(jnp.abs(a - b))) < 1e-6
    # and the layer-by-layer pass the check uses reads the same rows
    served = [int(t) for t in ids[60:]]
    rows = bench_ref.teacher_forced_logits(
        SEED, CFG, "float32", [int(t) for t in ids[:60]], served,
        pad_to=32, cap=64)
    assert float(jnp.max(jnp.abs(rows - b[59:89]))) < 1e-5


@pytest.mark.parametrize("t", [1, 63, 64, 65, 200])
def test_chunkwise_rule_matches_token_serial(t):
    b, h, dk, dv = 2, 3, 8, 16
    ks = jax.random.split(jax.random.key(t), 6)
    q = gd.l2norm(jax.random.normal(ks[0], (b, t, h, dk))) * dk ** -0.5
    k = gd.l2norm(jax.random.normal(ks[1], (b, t, h, dk)))
    v = jax.random.normal(ks[2], (b, t, h, dv))
    g = -0.5 * jnp.exp(jax.random.normal(ks[3], (b, t, h)))
    beta = 2.0 * jax.nn.sigmoid(jax.random.normal(ks[4], (b, t, h)))
    s0 = jax.random.normal(ks[5], (b, h, dv, dk))  # a non-zero start
    o1, s1 = gd.gated_delta_serial(q, k, v, g, beta, s0)
    o2, s2 = gd.gated_delta_chunked(q, k, v, g, beta, s0)
    assert float(jnp.max(jnp.abs(o1 - o2))) < 2e-5
    assert float(jnp.max(jnp.abs(s1 - s2))) < 2e-5
    # padded past a true length: outputs up to it and the state AT it
    n = t // 2 + 1
    o3, s3 = gd.gated_delta_chunked(q, k, v, g, beta, s0, valid_len=n)
    o4, s4 = gd.gated_delta_serial(q[:, :n], k[:, :n], v[:, :n], g[:, :n],
                                   beta[:, :n], s0)
    assert float(jnp.max(jnp.abs(o3[:, :n] - o4))) < 2e-5
    assert float(jnp.max(jnp.abs(s3 - s4))) < 2e-5


def test_engine_prefill_then_decode_by_the_benchmarks_measure(model,
                                                              weights):
    """Prefill (chunkwise, padded to a bucket, state written to the lane),
    then decode through the paged cache of the full-attention layer and
    the state store of the linear ones."""
    engine = ServingEngine(model, config=ServingConfig(**ENGINE))
    assert engine.recurrent and len(engine.arena.pools) == 1
    assert len(engine.arena.slot_state) == 3
    rng = np.random.default_rng(2)
    for plen, n in ((70, 24), (5, 12), (129, 20)):
        prompt = _prompt(rng, plen)
        slot, toks = _serve(engine, prompt, n)
        engine.retire(slot)
        gaps = _gaps(weights, prompt, toks)
        assert gaps.max() < 1e-3, (plen, gaps)


def test_lane_reuse_inactive_lanes_and_no_recompile(model):
    engine = ServingEngine(model, config=ServingConfig(**ENGINE))
    rng = np.random.default_rng(3)
    pa, pb = _prompt(rng, 40), _prompt(rng, 23)
    slot, _ = _serve(engine, pa, 2)           # warm A's prefill bucket
    engine.retire(slot)
    engine.rebuild()
    slot, alone = _serve(engine, pb, 10)      # B alone, in a fresh store
    assert slot == 0
    engine.retire(slot)
    engine.rebuild()
    warm = {k: compile_cache.stats().get(k, 0) for k in (
        "serving.decode_compiles", "serving.prefill_compiles")}
    resets0 = serving_metrics.stats().get("state.resets", 0)
    # B in the lane A just left (A's state is still in it)
    sa, _ = _serve(engine, pa, 14)
    engine.retire(sa)
    sb, reused = _serve(engine, pb, 10)
    assert sa == sb == 0 and reused == alone
    # an inactive lane's state is untouched by a step; an active one moves
    s1, _ = engine.admit(pa, 8)
    assert s1 == 1
    engine.retire(s1)
    before = [[np.asarray(a) for a in e] for e in engine.arena.slot_state]
    for _ in range(3):
        engine.decode_step()
    after = [[np.asarray(a) for a in e] for e in engine.arena.slot_state]
    for e0, e1 in zip(before, after):
        for a0, a1 in zip(e0, e1):
            np.testing.assert_array_equal(a0[1], a1[1])
            assert not np.array_equal(a0[0], a1[0])
    assert serving_metrics.stats()["state.resets"] - resets0 == 3
    assert serving_metrics.gauges()["state.bytes_total"] \
        == engine.arena.state_bytes_total() > 0
    assert {k: compile_cache.stats().get(k, 0) for k in warm} == warm


def test_preempted_request_regenerates_the_same_tokens(model):
    """Preemption is by recomputation: the victim is prefilled again over
    prompt + what it had generated, from a zero state."""
    keep = paddle.get_flags(["serving_starvation_steps"])
    paddle.set_flags({"serving_starvation_steps": 2})
    rng = np.random.default_rng(4)
    p1, p2 = _prompt(rng, 30), _prompt(rng, 12)
    try:
        api = ServingAPI(model, config=ServingConfig(
            num_slots=1, kv_block_size=8, max_model_len=256))
        plain = api.submit(p1, max_new_tokens=16)
        api.run_until_idle()
        pre0 = serving_metrics.stats().get("scheduler.preemptions", 0)
        low = api.submit(p1, max_new_tokens=16, priority=5)
        for _ in range(5):
            api._pump_once()
        assert low.state == RequestState.RUNNING and 0 < len(low.tokens) < 16
        high = api.submit(p2, max_new_tokens=4, priority=0)
        api.run_until_idle()
        assert high.state == low.state == RequestState.FINISHED
        assert serving_metrics.stats()["scheduler.preemptions"] - pre0 >= 1
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
def test_options_that_assume_blocks_refuse_a_recurrent_layer(model, option,
                                                             kw):
    with pytest.raises(ValueError, match=option):
        ServingEngine(model, config=ServingConfig(**ENGINE, **kw))


def test_int8_weights_and_kv_serve_the_hybrid(weights):
    """The control's path: the quantizer finds the linears the model
    declares, the int8 arena holds the full-attention layer's K/V."""
    m = hook.build_model(CFG, SEED, "float32", train=False)
    engine = ServingEngine(m, config=ServingConfig(
        **ENGINE, quant_weights=True, quant_kv=True))
    assert str(m.model.layers[0].mixer.q_proj.weight._data.dtype) == "int8"
    assert len(engine.arena.pools[0]) == 4
    prompt = _prompt(np.random.default_rng(5), 50)
    slot, toks = _serve(engine, prompt, 12)
    gaps = _gaps(weights, prompt, toks)
    assert gaps.max() < 0.5  # int8: near the reference, not on it


def test_disaggregated_handoff_refuses_a_recurrent_layer(model):
    from paddle_tpu.serving.disagg import DisaggReplicaPool

    with pytest.raises(ValueError, match="disaggregated"):
        DisaggReplicaPool(model, prefill_replicas=1, decode_replicas=1)
