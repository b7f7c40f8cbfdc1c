"""Quantized serving (ISSUE 11): int8 weight-only decode, the int8 KV
arena with per-block scale pools, and the quantized draft.

The contract under test (docs/quantization.md "Parity policy"):

* **flag-off is bit-identical** — all three quant flags default off and
  the unquantized engine behaves exactly as before (2-tuple float pools,
  no weight_scale buffers, generate() parity);
* **structural invariants are exact** — a weight-quantized engine is
  token-for-token identical to generate() on the same quantized model; a
  quantized draft never changes emitted tokens; COW copies scale pools
  with their payload; rebuild+replay reconstructs quantized state;
* **tolerance vs the float baseline is documented** — greedy streams
  and teacher-forced top-1 agreement must clear the >=90% per-token
  gate (measured 100% on this tiny model — the gate is the contract,
  not the expectation); int8 round-trips obey their absmax/254 bound;
* **the memory win is real** — the int8 arena seats >=1.9x a bf16
  arena's slots at equal bytes_total() (scale pools charged), and the
  per-namespace byte/dtype breakdown is observable;
* **zero recompiles** — quantize-on-scatter / dequant-in-kernel live
  inside the same per-bucket programs; churn adds no compiles.
"""
import logging

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import quantization
from paddle_tpu.core import resilience
from paddle_tpu.core.tensor import Tensor
from paddle_tpu.models.gpt import GPTForCausalLM, gpt_tiny
from paddle_tpu.models.serving_seam import (
    quantize_serving_weights,
    serving_compute_dtype,
)
from paddle_tpu.serving import (
    EnginePredictor,
    RequestState,
    ServingAPI,
    ServingConfig,
)
from paddle_tpu.serving import metrics as serving_metrics
from paddle_tpu.serving.kv_arena import KVArena

pytestmark = pytest.mark.serving

MAX_LEN = 96
BS = 8
#: the documented per-token tolerance gate vs the float baseline
PARITY_GATE = 0.9


@pytest.fixture(scope="module")
def model():
    paddle.seed(0)
    m = GPTForCausalLM(gpt_tiny())
    m.eval()
    return m


def _copy(model):
    """A fresh instance carrying ``model``'s float weights — quantizing
    engines mutate their model in place, so every quantized engine in
    this suite gets its own copy and the float fixture stays float."""
    m = GPTForCausalLM(model.cfg.__class__(**vars(model.cfg)))
    m.eval()
    m.set_state_dict(dict(model.state_dict()))
    return m


def _prompt(rng, n):
    return rng.integers(0, 1024, (n,), dtype=np.int32)


def _ref(model, prompt, max_new):
    out = model.generate(Tensor(np.asarray(prompt)[None]),
                         max_new_tokens=max_new)
    return np.asarray(out._data)[0]


def _cfg(**kw):
    kw.setdefault("num_slots", 4)
    kw.setdefault("kv_block_size", BS)
    kw.setdefault("max_model_len", MAX_LEN)
    return ServingConfig(**kw)


def _run(api, prompts, max_new):
    reqs = [api.submit(p, max_new_tokens=max_new) for p in prompts]
    api.run_until_idle()
    for r in reqs:
        assert r.state == RequestState.FINISHED
    return [r.output_ids() for r in reqs]


def _gen_match(out, ref, plen):
    """Per-token agreement over GENERATED tokens only — output_ids() and
    generate() both return prompt + generation, and prompt tokens match
    by construction (counting them would floor the gate at
    plen/(plen+new) and make it vacuous)."""
    out, ref = np.asarray(out), np.asarray(ref)
    assert len(out) > plen
    return float((out[plen:] == ref[plen:]).mean())


# ------------------------------------------------------------ quantizers


def test_quantize_weight_per_channel_correctness():
    """The single weight quantizer: per-channel scales keep the declared
    axis, round-trip error is bounded by scale/2 per element, and a
    negative channel_axis quantizes the same channels as its positive
    twin (the normalization fix — it used to reduce over every axis)."""
    rng = np.random.default_rng(0)
    w = rng.normal(0, 0.3, (24, 16)).astype(np.float32)
    w[:, 3] *= 50.0  # a hot output channel must not poison the others
    q, scale = quantization.quantize_weight(w, channel_axis=1)
    assert q.dtype == np.int8 and scale.shape == (1, 16)
    deq = quantization.dequantize_weight(q, scale)
    assert np.all(np.abs(deq - w) <= scale / 2 + 1e-7)
    # the hot channel's scale is its own, not the tensor max's
    assert scale[0, 3] > 10 * scale[0, 0]
    q0, s0 = quantization.quantize_weight(w, channel_axis=0)
    assert s0.shape == (24, 1)
    qn, sn = quantization.quantize_weight(w, channel_axis=-1)
    np.testing.assert_array_equal(qn, q)
    np.testing.assert_array_equal(sn, scale)


def test_quantize_kv_round_trip_error_bound():
    """Per-token symmetric int8 KV: |dequant - x| <= absmax/254 per
    element, scales are per leading index, payload is int8."""
    import jax.numpy as jnp

    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.normal(0, 2.0, (6, 4, 8)).astype(np.float32))
    q, scale = quantization.quantize_kv(x)
    assert q.dtype == jnp.int8 and scale.shape == (6,)
    deq = quantization.dequantize_kv(q, scale, jnp.float32)
    amax = np.abs(np.asarray(x)).max(axis=(-2, -1))
    bound = amax / 254.0 + 1e-6
    err = np.abs(np.asarray(deq) - np.asarray(x)).max(axis=(-2, -1))
    assert np.all(err <= bound)


def test_quantize_serving_weights_single_quantizer_and_idempotent(model,
                                                                  monkeypatch):
    """The serving path routes every layer through
    quantization.quantize_weight (no duplicate absmax math in gpt.py),
    registers f32 [1, out] scales as buffers, and a second call is a
    no-op — a gateway's replicas share one model instance."""
    m = _copy(model)
    calls = []
    real = quantization.quantize_weight

    def counting(w, channel_axis=None):
        calls.append(channel_axis)
        return real(w, channel_axis=channel_axis)

    monkeypatch.setattr(quantization, "quantize_weight", counting)
    n = quantize_serving_weights(m)
    # 4 linears per block (qkv/proj/up/down), every call per-channel
    assert n == len(calls) == 4 * m.cfg.num_layers
    assert all(c == 1 for c in calls)
    assert quantize_serving_weights(m) == 0 and len(calls) == n
    lin = m.gpt.layers[0].attn.qkv
    assert str(lin.weight._data.dtype) == "int8"
    assert str(lin.weight_scale._data.dtype) == "float32"
    assert tuple(lin.weight_scale.shape) == (1, lin.weight.shape[1])
    # the scale buffers ride functional_state into the compiled programs
    _, buffers = m.functional_state()
    assert any(k.endswith("weight_scale") for k in buffers)
    assert serving_compute_dtype(m) == "float32"


# ------------------------------------------------- flag-off / default path


def test_quant_flags_default_off_and_engine_unchanged(model):
    """All three flags default off; the default engine keeps 2-tuple
    float pools, quantizes nothing, and reproduces generate() exactly."""
    for f in ("serving_quant_weights", "serving_quant_kv",
              "serving_quant_draft"):
        assert paddle.get_flags(f)[f] is False
    rng = np.random.default_rng(2)
    prompts = [_prompt(rng, n) for n in (5, 11)]
    api = ServingAPI(model, _cfg())
    try:
        assert not api.engine.quant_weights and not api.engine.quant_kv
        assert len(api.engine.arena.pools[0]) == 2
        assert str(api.engine.arena.pools[0][0].dtype) == "float32"
        outs = _run(api, prompts, 10)
        for p, out in zip(prompts, outs):
            np.testing.assert_array_equal(out, _ref(model, p, 10))
        assert getattr(model.gpt.layers[0].attn.qkv, "weight_scale",
                       None) is None
    finally:
        api.close()


# ------------------------------------------------------------ parity gates


def test_weight_only_engine_exact_vs_quantized_generate(model):
    """Structural invariant: the weight-quantized engine and generate()
    on the SAME quantized model share one numerics contract — token-for-
    token identical. Tolerance gate: both clear >=90% agreement with the
    float baseline, greedy and teacher-forced."""
    import jax.numpy as jnp

    qm = _copy(model)
    api = ServingAPI(qm, _cfg(quant_weights=True))
    try:
        assert api.engine.quant_weights
        rng = np.random.default_rng(3)
        prompts = [_prompt(rng, n) for n in (5, 9, 14)]
        outs = _run(api, prompts, 12)
        for p, out in zip(prompts, outs):
            np.testing.assert_array_equal(out, _ref(qm, p, 12))  # exact
            ref = _ref(model, p, 12)
            assert _gen_match(out, ref, len(p)) >= PARITY_GATE
            # teacher-forced per-position top-1 agreement on the float
            # baseline's own greedy context
            lq = qm(Tensor(ref[None, :-1].astype(np.int32)))._data
            lf = model(Tensor(ref[None, :-1].astype(np.int32)))._data
            tf = (np.asarray(jnp.argmax(lq, -1))
                  == np.asarray(jnp.argmax(lf, -1))).mean()
            assert tf >= PARITY_GATE
    finally:
        api.close()


def test_kv_quant_engine_tolerance_gate(model):
    """Int8 KV decode clears the documented per-token gate vs the float
    engine (generate() has no paged-int8 path, so the float baseline is
    the reference)."""
    api = ServingAPI(model, _cfg(quant_kv=True))
    try:
        assert api.engine.arena.quantized
        assert len(api.engine.arena.pools[0]) == 4
        rng = np.random.default_rng(4)
        prompts = [_prompt(rng, n) for n in (6, 10, 17)]
        outs = _run(api, prompts, 12)
        for p, out in zip(prompts, outs):
            assert _gen_match(out, _ref(model, p, 12),
                              len(p)) >= PARITY_GATE
        api.engine.check_invariants()
    finally:
        api.close()


def test_combined_weight_and_kv_quant_churn_zero_recompiles(model):
    """Both modes together: the tolerance gate holds, and admit/retire
    churn across mixed lengths adds ZERO compiled programs after warmup
    — quantize/dequant is traced into the same per-bucket programs."""
    qm = _copy(model)
    api = ServingAPI(qm, _cfg(quant_weights=True, quant_kv=True))
    try:
        rng = np.random.default_rng(5)
        warm = _run(api, [_prompt(rng, 6)], 4)  # warm bucket + step
        traces0 = (api.engine.decode_traces,
                   dict(api.engine.prefill_traces))
        prompts = [_prompt(rng, n) for n in (5, 7, 9, 6, 8)]
        outs = _run(api, prompts, 10)
        for p, out in zip(prompts, outs):
            assert _gen_match(out, _ref(model, p, 10),
                              len(p)) >= PARITY_GATE
        assert api.engine.decode_traces == traces0[0] == 1
        assert dict(api.engine.prefill_traces) == traces0[1]
    finally:
        api.close()


# ------------------------------------------------ prefix cache / COW / arena


def test_prefix_cache_hit_and_cow_with_scales(model):
    """The radix cache over the int8 arena: shared prefixes attach by
    reference (suffix-only prefill), a fully-cached block-aligned prompt
    COWs its last block — and the COW copies the scale rows with the
    payload, so cache-on output equals cache-off output token-for-token
    under quantization. Refcount/structure invariants audited."""
    rng = np.random.default_rng(6)
    sys_p = _prompt(rng, 2 * BS)  # block-aligned shared prefix
    tails = [_prompt(rng, 5) for _ in range(2)]
    prompts = [np.concatenate([sys_p, t]) for t in tails] + [sys_p.copy()]

    off = ServingAPI(model, _cfg(quant_kv=True, prefix_cache=False))
    try:
        base = _run(off, prompts, 10)
    finally:
        off.close()

    api = ServingAPI(model, _cfg(quant_kv=True, prefix_cache=True))
    try:
        outs = _run(api, prompts, 10)
        for a, b in zip(outs, base):
            np.testing.assert_array_equal(a, b)
        st = api.engine.stats()
        assert st["prefix.hits"] >= 2       # tail shares + aligned reuse
        assert st["cow_traces"] == 1        # the aligned prompt COW'd
        api.engine.check_invariants()
        a = api.engine.arena.stats()
        assert a["blocks_in_use"] == a["blocks_cached"]  # only cache holds
    finally:
        api.close()


def test_cow_copies_scale_pools_unit(model):
    """Direct audit of the compiled COW program on a quantized arena:
    every array of each pool entry — int8 K/V payload AND both scale
    pools — lands in the destination block."""
    api = ServingAPI(model, _cfg(quant_kv=True))
    try:
        import jax.numpy as jnp

        arena = api.engine.arena
        src, dst = 3, 5
        seeded = []
        for li, entry in enumerate(arena.pools):
            new = []
            for ai, arr in enumerate(entry):
                fill = (li + 1) * 10 + ai + 1
                new.append(arr.at[src].set(
                    jnp.full(arr.shape[1:], fill, arr.dtype)))
                seeded.append(fill)
            arena.pools[li] = tuple(new)
        api.engine._cow_copy(src, dst)
        for li, entry in enumerate(arena.pools):
            for ai, arr in enumerate(entry):
                fill = (li + 1) * 10 + ai + 1
                got = np.asarray(arr[dst])
                assert np.all(got == fill), (li, ai)
        arena.check_invariants()
    finally:
        api.close()


def test_arena_seats_1p9x_bf16_slots_at_equal_bytes():
    """The acceptance gate: at equal bytes_total() (scale pools charged
    to the int8 side) the quantized arena seats >=1.9x the bf16 arena's
    slots. Probed at 32 slots so block flooring doesn't mask the real
    ratio 2*H*D/(H*D+4)."""
    cfg = gpt_tiny()
    heads, hdim = cfg.num_heads, cfg.hidden_size // cfg.num_heads
    blocks_per_slot = -(-MAX_LEN // BS)
    slots = 32
    nb = slots * blocks_per_slot + 1
    bf16 = KVArena(cfg.num_layers, heads, hdim, nb, BS, dtype="bfloat16")
    q = KVArena(cfg.num_layers, heads, hdim, nb, BS, quantized=True)
    per_block_q = q.bytes_total() / nb
    slots_q = (int(bf16.bytes_total() // per_block_q) - 1) // blocks_per_slot
    assert slots_q / slots >= 1.9, (slots_q, slots)
    # and the breakdown is honest: scale bytes nonzero, dtype int8
    by = q.bytes_by_namespace()["primary"]
    assert by["dtype"] == "int8" and by["scale_bytes"] > 0
    assert by["kv_bytes"] + by["scale_bytes"] == q.bytes_total()
    # pin the shape arithmetic the --quantized bench probes with (it must
    # never instantiate device pools just to count bytes)
    row = BS * heads * hdim
    assert q.bytes_total() == nb * cfg.num_layers * 2 * (row + BS * 4)
    assert bf16.bytes_total() == nb * cfg.num_layers * 2 * row * 2


def test_adopting_pools_without_scales_fails_invariants(model):
    """A quantized pool set adopted without its scale pools (the silent-
    corruption shape the COW audit exists for) is caught structurally."""
    api = ServingAPI(model, _cfg(quant_kv=True))
    try:
        arena = api.engine.arena
        arena.set_pools([(e[0], e[1]) for e in arena.pools])  # drop scales
        with pytest.raises(RuntimeError, match="without its scales"):
            arena.check_invariants()
    finally:
        api.close()


def test_bytes_breakdown_covers_draft_namespace(model):
    """stats()/bytes_by_namespace break bytes and dtype out per namespace
    — the draft namespace included — and the engine publishes them as
    arena.* gauges."""
    qm = _copy(model)
    draft = _copy(model)
    api = ServingAPI(qm, _cfg(quant_weights=True, quant_kv=True,
                              spec_k=3, draft_model=draft,
                              quant_draft=True))
    try:
        by = api.engine.arena.bytes_by_namespace()
        assert set(by) == {"primary", "draft"}
        for ns in by.values():
            assert ns["quantized"] and ns["dtype"] == "int8"
            assert ns["scale_bytes"] > 0
        st = api.engine.arena.stats()
        assert st["kv_bytes"] == sum(d["bytes"] for d in by.values())
        g = serving_metrics.gauges()
        assert g["arena.bytes.draft"] == by["draft"]["bytes"]
        assert g["arena.dtype.primary"] == "int8"
        assert g["arena.scale_bytes"] == sum(d["scale_bytes"]
                                             for d in by.values())
        assert g["quant.weights"] == 1 and g["quant.kv"] == 1
        assert g["quant.draft"] == 1
    finally:
        api.close()


# ------------------------------------------------------------ quantized draft


def test_quantized_draft_is_output_neutral(model):
    """An int8-quantized draft changes speed, never tokens: output stays
    bit-identical to the float target's greedy stream (verification is
    target-greedy by construction), the mode reports draft-int8, and the
    per-mode acceptance telemetry lands."""
    draft = _copy(model)  # tied weights -> near-total acceptance
    api = ServingAPI(model, _cfg(spec_k=3, draft_model=draft,
                                 quant_draft=True))
    try:
        spec = api.engine.spec
        assert spec.quant_draft and spec.mode() == "draft-int8"
        assert str(
            draft.gpt.layers[0].attn.qkv.weight._data.dtype) == "int8"
        rng = np.random.default_rng(7)
        prompts = [_prompt(rng, n) for n in (6, 10, 13)]
        outs = _run(api, prompts, 12)
        for p, out in zip(prompts, outs):
            np.testing.assert_array_equal(out, _ref(model, p, 12))
        assert spec.proposed > 0
        st = spec.stats()
        assert st["spec.mode"] == "draft-int8"
        g = serving_metrics.gauges()
        assert g["quant.draft_acceptance"] == st["spec.acceptance_rate"]
        api.engine.check_invariants()
    finally:
        api.close()


# ----------------------------------------------------------- chaos / replay


@pytest.mark.chaos
def test_replay_parity_with_quant_on(model):
    """Supervisor rebuild+replay reconstructs quantized state exactly: a
    transient device fault mid-decode on a weights+KV-quantized engine
    resumes token-for-token (vs its own unfaulted run), rebuilds exactly
    once, keeps the rebuilt arena quantized, and leaves it clean."""
    keep = paddle.get_flags("fault_injection")["fault_injection"]
    paddle.set_flags({"fault_injection": 1})
    qm = _copy(model)
    api = ServingAPI(qm, _cfg(quant_weights=True, quant_kv=True))
    try:
        rng = np.random.default_rng(8)
        prompts = [_prompt(rng, n) for n in (5, 9)]
        refs = _run(api, prompts, 14)  # unfaulted quantized reference
        rb0 = resilience.stats().get("serving.rebuilds", 0)
        reqs = [api.submit(p, max_new_tokens=14) for p in prompts]
        for _ in range(3):
            api._pump_once()
        assert all(r.state == RequestState.RUNNING for r in reqs)
        resilience.inject_fault("serving_device", times=1)
        api.run_until_idle()
        for ref, r in zip(refs, reqs):
            assert r.state == RequestState.FINISHED
            np.testing.assert_array_equal(ref, r.output_ids())
        assert resilience.stats().get("serving.rebuilds", 0) == rb0 + 1
        assert api.engine.arena.quantized
        assert len(api.engine.arena.pools[0]) == 4
        assert api.engine.decode_traces == 1  # recovery never retraced
        api.drain(grace=5)
        a = api.engine.arena.stats()
        assert a["blocks_in_use"] == 0 and a["blocks_reserved"] == 0
    finally:
        resilience.clear_faults()
        api.close()
        paddle.set_flags({"fault_injection": keep})


# ---------------------------------------------------------- observability


def test_predictor_close_logs_quant_summary(model, caplog):
    """EnginePredictor.close() reports the quantized-serving memory
    picture (per-namespace bytes/dtype, scale pools broken out) next to
    the prefix/speculation lines."""
    qm = _copy(model)
    pred = EnginePredictor(qm, max_new_tokens=4,
                           config=_cfg(num_slots=2, quant_weights=True,
                                       quant_kv=True))
    rng = np.random.default_rng(9)
    ids = np.stack([_prompt(rng, 8), _prompt(rng, 8)])
    out = pred.run([ids])[0]
    np.testing.assert_array_equal(
        out, np.asarray(qm.generate(Tensor(ids), max_new_tokens=4)._data))
    with caplog.at_level(logging.INFO, logger="paddle_tpu.serving"):
        pred.close()
    summary = [rec.getMessage() for rec in caplog.records
               if "EnginePredictor" in rec.getMessage()]
    assert summary
    line = summary[-1]
    assert "quantized serving [weights=1 kv=1 draft=0]" in line
    assert "primary int8" in line and "scales" in line


def test_serving_stats_cli_reports_quant_flags():
    """tools/serving_stats.py config mode (no jax init) surfaces the
    quant flag trio."""
    import json
    import subprocess
    import sys
    import os

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    out = subprocess.run(
        [sys.executable, os.path.join(repo, "tools", "serving_stats.py"),
         "--json"], capture_output=True, text=True, timeout=60, cwd=repo)
    assert out.returncode == 0, out.stderr
    rep = json.loads(out.stdout)
    for k in ("serving_quant_weights", "serving_quant_kv",
              "serving_quant_draft"):
        assert k in rep and rep[k] == 0
