"""Xing4.0 (latent attention over a one-row-a-token paged cache, sigmoid
routed experts beside a shared one, four hyper-connected residual streams,
rotary at per-lane positions) on the normal serving path, at the tiny
preset: the model and the engine against the plain reference
(``benchmark/reference/xing4.py``), LOGITS and not tokens.

Tolerances. Program and reference both compute in float32 here (conftest
pins full matmul precision), so they differ by summation order alone: the
largest difference seen is 6e-6 on logits whose standard deviation is 1.0.
``TOL`` = 1e-4 leaves 15 times that and is far under what each breakage of
``test_tolerance_fails_what_is_wrong`` moves the logits by (each is held to
more than ten times ``TOL``), the bfloat16-for-float32 variant among them.
Routing is discrete: were a token's fourth and fifth expert scores to lie
within rounding of each other, program and reference could choose
differently and part by far more than ``TOL``; on these seeds none does."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.models import xing4 as X
from paddle_tpu.models.serving_seam import LatentKVLayerState
from paddle_tpu.ops import grouped_matmul as gm
from paddle_tpu.ops import hyper_connection as HC
from paddle_tpu.serving import ServingConfig, ServingEngine
from paddle_tpu.serving import metrics as serving_metrics

from benchmark.hooks import xing4 as hook
from benchmark.reference import xing4 as ref
from benchmark.weights import xing4 as W

SEED = 11
CFG = {
    "vocab_size": 512, "hidden_size": 64, "intermediate_size": 128,
    "moe_intermediate_size": 32, "num_hidden_layers": 3,
    "num_attention_heads": 4, "q_lora_rank": 32, "kv_lora_rank": 32,
    "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
    "n_routed_experts": 8, "n_shared_experts": 1, "num_experts_per_tok": 2,
    "first_k_dense_replace": 1, "hc_mult": 4, "hc_sinkhorn_iters": 20,
    "hc_eps": 1e-6, "mhc_h_res_clamp_min": -30, "mhc_h_res_clamp_max": 30,
    "rms_norm_eps": 1e-6, "rope_theta": 10000, "routed_scaling_factor": 2,
    "norm_topk_prob": True, "max_position_embeddings": 256,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 64,
                     "mscale": 1, "mscale_all_dim": 1,
                     "original_max_position_embeddings": 4096,
                     "type": "yarn"},
}
ENGINE = dict(num_slots=3, kv_block_size=8, max_model_len=256)
TOL = 1e-4


def _build(dtype="float32", **kw):
    return hook.build_model(CFG, SEED, dtype, train=False, **kw)


@pytest.fixture(scope="module")
def weights():
    return W.all_weights(SEED, CFG, "float32", with_mtp=True)


def _prompt(rng, n):
    return rng.integers(0, CFG["vocab_size"], (n,), dtype=np.int32)


class Spy:
    """A model whose ``serving_head`` also hands every logits array it
    computes inside a compiled serving program back to the host."""

    def __init__(self, **kw):
        self.model, self.seen = _build(**kw), []
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


def _worst(weights, prompts, lanes):
    worst = 0.0
    for p, (_, toks, logits) in zip(prompts, lanes):
        full = ref.logits(weights, CFG, list(p) + toks[:-1])
        worst = max(worst, float(np.max(np.abs(
            np.stack(logits) - np.asarray(full[len(p) - 1:])))))
    return worst


# ------------------------------------------------------------- the model


def test_what_the_model_declares():
    big = X.Xing4Config()
    assert big.row_width == 576 and big.expert_count == 64
    assert [big.is_dense(i) for i in range(4)] == [True, True, False, False]
    assert abs(X.softmax_scale(big) - (0.1 * np.log(64) + 1) ** 2
               / np.sqrt(192)) < 1e-12
    model = _build()
    spec = model.serving_spec()
    assert spec.prefill_tail is None and len(spec.layers) == 3
    assert all(st == LatentKVLayerState(32, 8, 4) and st.kind == "latent"
               and st.width == 40 for st in spec.layers)
    assert type(model.model.layers[0].mlp) is X.Xing4MLP
    assert type(model.model.layers[1].mlp) is X.Xing4MoE
    # the streams: what embed hands the layers and final folds away
    ids = paddle.to_tensor(np.zeros((2, 5), np.int32))
    x = model.serving_embed(ids, 0)
    # (four streams of 64 side by side: ops/hyper_connection.py's layout)
    assert x.shape == [2, 5, 4 * 64] and x._data.dtype == jnp.float32
    assert model.serving_final(x).shape == [2, 5, 64]


@pytest.mark.parametrize("absorbed", [False, True],
                         ids=["expanded", "absorbed"])
def test_model_forward_matches_reference(weights, absorbed):
    """(b) the absorbed form (queries carried into the latent space, the
    cached row as key and value) gives what expanded keys and values give:
    both the reference's logits."""
    ids = _prompt(np.random.default_rng(0), 60)
    got = _build()(paddle.to_tensor(ids[None]), absorbed=absorbed)._data[0]
    want = ref.logits(weights, CFG, ids)
    assert got.shape == want.shape == (60, CFG["vocab_size"])
    assert float(jnp.max(jnp.abs(got - want))) < TOL


def test_multi_token_prediction_matches_reference(weights):
    ids = _prompt(np.random.default_rng(3), 40)
    got = _build(with_mtp=True).mtp_logits(paddle.to_tensor(ids[None]))
    want = ref.mtp_logits(weights, CFG, ids)
    assert got._data[0].shape == want.shape == (39, CFG["vocab_size"])
    assert float(jnp.max(jnp.abs(got._data[0] - want))) < TOL


def test_teacher_forced_pass_reads_the_same_rows(weights):
    ids = [int(t) for t in _prompt(np.random.default_rng(1), 90)]
    full = ref.logits(weights, CFG, ids)
    rows = ref.teacher_forced_logits(SEED, CFG, "float32", ids[:60],
                                     ids[60:], pad_to=32, cap=64)
    assert float(jnp.max(jnp.abs(rows - full[59:89]))) < TOL


@pytest.mark.parametrize("wrong", [
    "bfloat16_for_float32", "columns_then_rows", "rotary_at_position_0",
    "no_selection_bias", "unnormalized_topk", "one_stream"])
def test_tolerance_fails_what_is_wrong(weights, wrong, monkeypatch):
    """Each of these must move the logits by far more than ``TOL``: the
    program in bfloat16 where float32 is stated, Sinkhorn's columns before
    its rows, every token rotated as position 0, routing without the
    selection bias, chosen scores not normalized, the sublayer read off
    stream 0 alone. (Sinkhorn and the read-out are swapped where the layers
    run them: inside ``ops/hyper_connection.py``'s kernel.)"""
    ids = _prompt(np.random.default_rng(2), 60)
    cfg, w, dtype = dict(CFG), weights, "float32"
    if wrong == "bfloat16_for_float32":
        dtype = "bfloat16"
    elif wrong == "columns_then_rows":
        # (in the served path's own Sinkhorn, the kernel's: ``cols[j]`` is
        # column ``j`` of each position's matrix, positions in the lanes)
        def swapped(cols, iters, eps):
            for _ in range(2):  # two rounds: far from converged
                cols = [c / (jnp.sum(c, axis=0, keepdims=True) + eps)
                        for c in cols]
                rows = sum(cols[1:], cols[0])
                cols = [c / (rows + eps) for c in cols]
            return cols
        monkeypatch.setattr(HC, "_sinkhorn", swapped)
    elif wrong == "rotary_at_position_0":
        monkeypatch.setattr(X, "_positions",
                            lambda start, b, s: jnp.zeros((b, s), jnp.int32))
    elif wrong == "no_selection_bias":
        w = dict(weights, layers=[
            {k: 3.0 * v if k == "e_bias" else v for k, v in p.items()}
            for p in weights["layers"]])
    elif wrong == "unnormalized_topk":
        cfg["norm_topk_prob"] = False
    elif wrong == "one_stream":
        monkeypatch.setattr(HC, "_read_out",
                            lambda pre, streams: streams[0])
    model = _build(dtype)
    keep = paddle.get_flags(["eager_jit_ops"])
    paddle.set_flags({"eager_jit_ops": False})
    HC._call.clear_cache()  # the kernel's launch is jitted: trace it anew
    try:
        got = model(paddle.to_tensor(ids[None]))._data[0]
    finally:
        paddle.set_flags(keep)
        HC._call.clear_cache()  # and let no later test meet the swap
    want = ref.logits(w, cfg, ids)
    assert float(jnp.max(jnp.abs(got.astype(jnp.float32) - want))) > 10 * TOL


# ------------------------------------------------- hyper-connections (e)


def test_h_res_is_doubly_stochastic_and_the_update_is_the_references(
        weights):
    model = _build()
    layer, p = model.model.layers[1], ref._f32(weights["layers"][1])
    rng = np.random.default_rng(5)
    Xs = jnp.asarray(rng.normal(size=(2, 7, 4, 64)), jnp.float32)
    y = jnp.asarray(rng.normal(size=(2, 7, 64)), jnp.float32)
    pre, post, res = layer.hc_mlp.mixers(Xs)
    assert float(jnp.max(jnp.abs(jnp.sum(res, -1) - 1))) < 1e-4   # rows
    assert float(jnp.max(jnp.abs(jnp.sum(res, -2) - 1))) < 1e-4   # columns
    assert float(jnp.min(res)) > 0 and float(jnp.max(post)) < 2
    u, mix = layer.hc_mlp.pre(Xs)
    new = layer.hc_mlp.post(Xs, y, mix)
    for b in range(2):
        rpre, rpost, rres = ref.hc_mixers(Xs[b], p, "hcm", CFG)
        assert float(jnp.max(jnp.abs(rres - res[b]))) < 1e-5
        want_u = jnp.sum(rpre[:, :, None] * Xs[b], axis=1)
        want = jnp.einsum("sij,sjh->sih", rres, Xs[b]) \
            + rpost[:, :, None] * y[b][:, None, :]
        assert float(jnp.max(jnp.abs(u[b] - want_u))) < 1e-5
        assert float(jnp.max(jnp.abs(new[b] - want))) < 1e-5


# ------------------------------------------------------ experts (c), (d)


def _per_token(x, idx, w, up, down, lo=0, hi=None):
    """Each token's chosen experts applied one by one (numpy float64)."""
    x, up, down = (np.asarray(a, np.float64) for a in (x, up, down))
    hi = up.shape[0] if hi is None else hi
    out = np.zeros_like(x)
    for t in range(x.shape[0]):
        for e, we in zip(np.asarray(idx[t]), np.asarray(w[t], np.float64)):
            if lo <= e < hi:
                g, u = np.split(x[t] @ up[e], 2)
                out[t] += we * ((g / (1 + np.exp(-g)) * u) @ down[e])
    return out


def _expert_case(skew: bool, tokens=64, experts=8, k=2):
    rng = np.random.default_rng(7)
    x = jnp.asarray(rng.normal(size=(tokens, 64)), jnp.float32)
    up = jnp.asarray(rng.normal(size=(experts, 64, 64)), jnp.float32) / 8
    down = jnp.asarray(rng.normal(size=(experts, 32, 64)), jnp.float32) / 6
    idx = np.stack([rng.permutation(experts)[:k] for _ in range(tokens)])
    if skew:  # expert 0 is given half of ALL assignments
        idx[:, 0] = 0
        idx[:, 1] = rng.integers(1, experts, tokens)
    w = jnp.asarray(rng.uniform(0.2, 1.0, (tokens, k)), jnp.float32)
    return x, jnp.asarray(idx, jnp.int32), w, up, down


@pytest.mark.parametrize("interpret", [False, True],
                         ids=["ragged_dot", "megablox_interpreted"])
@pytest.mark.parametrize("skew", [False, True], ids=["uniform", "skewed"])
def test_expert_layer_drops_no_token(skew, interpret):
    """(c) sort, grouped matmul, unsort against each token's experts
    applied one by one: at uniform load and with one expert given half of
    all assignments (a capacity of 1.25 would drop three in five of its
    tokens), every assignment is in the result."""
    x, idx, w, up, down = _expert_case(skew)
    got = gm.expert_ffn(x, idx, w, up, down, 8, interpret=interpret)
    want = _per_token(x, idx, w, up, down)
    assert float(np.max(np.abs(np.asarray(got) - want))) < 1e-4
    counts = gm.load_counters(idx, 8)
    assert int(counts["moe.assignments"]) == 128
    assert int(counts["moe.max_expert_assignments"]) == (64 if skew else int(
        np.bincount(np.asarray(idx).ravel(), minlength=8).max()))


@pytest.mark.parametrize("interpret", [False, True],
                         ids=["ragged_dot", "megablox_interpreted"])
def test_two_shares_of_the_experts_add_up_to_the_layer(interpret):
    """(d) at the op: the part computed by experts 0..3 plus the part by
    experts 4..7 is the whole sum; each is its own experts' part."""
    x, idx, w, up, down = _expert_case(skew=True)
    whole = gm.expert_ffn(x, idx, w, up, down, 8, interpret=interpret)
    lo = gm.expert_ffn(x, idx, w, up[:4], down[:4], 8, first=0,
                       interpret=interpret)
    hi = gm.expert_ffn(x, idx, w, up[4:], down[4:], 8, first=4,
                       interpret=interpret)
    assert float(jnp.max(jnp.abs(lo + hi - whole))) < 1e-4
    assert float(np.max(np.abs(np.asarray(hi) - _per_token(
        x, idx, w, up, down, 4, 8)))) < 1e-4


def test_expert_layer_and_its_two_shares_match_the_reference(weights):
    """(c), (d) at the layer, on the seeded weights: the whole layer is the
    reference's, and two shares of 4 experts, the shared expert counted in
    one of them, add up to it."""
    p = ref._f32(weights["layers"][1])
    x = jnp.asarray(np.random.default_rng(9).normal(size=(1, 50, 64)),
                    jnp.float32)
    want = ref.experts(x[0], p, CFG)
    parts = []
    for first, shared in ((None, None), (0, True), (4, False)):
        share = {} if first is None else dict(
            expert_first=first, expert_count=4, shared_expert_here=shared)
        cfg = dict(CFG, **share)
        model = hook.build_model(cfg, SEED, "float32", train=False)
        parts.append(model.model.layers[1].mlp(paddle.to_tensor(x))._data[0])
        if first is not None:
            assert model.model.layers[1].mlp.e_up.shape[0] == 4
            assert float(jnp.max(jnp.abs(parts[-1] - ref.experts(
                x[0], p, CFG, first, 4, shared)))) < TOL
    assert float(jnp.max(jnp.abs(parts[0] - want))) < TOL
    assert float(jnp.max(jnp.abs(parts[1] + parts[2] - want))) < TOL


def test_routing_follows_the_selection_bias_and_weighs_by_the_score():
    rng = np.random.default_rng(4)
    x = jnp.asarray(rng.normal(size=(30, 64)), jnp.float32)
    router = jnp.asarray(rng.normal(size=(64, 8)), jnp.float32) / 8
    bias = jnp.zeros((8,)).at[5].set(10.0)
    idx, w = gm.route_sigmoid_topk(x, router, bias, 2, 2.0)
    assert bool(jnp.all(jnp.any(idx == 5, axis=1)))   # the bias chooses
    assert float(jnp.max(jnp.abs(jnp.sum(w, -1) - 2.0))) < 1e-6
    s = jax.nn.sigmoid(x @ router)                     # the score weighs
    want = jnp.take_along_axis(s, idx, 1)
    assert float(jnp.max(jnp.abs(
        w - 2.0 * want / jnp.sum(want, -1, keepdims=True)))) < 1e-6


def test_the_selection_bias_is_fit_to_an_even_load(weights):
    """The seeded weights' selection bias is noaux_tc's fixed point on the
    model's own hidden states (``weights/xing4.py:selection_biases``): over
    the calibration tokens no expert stands more than a fifth over its
    share where the drawn bias leaves one at twice it or more, the fit does
    not touch the scores, and the program's layers and the reference's get
    the same numbers."""
    rng = np.random.default_rng(21)
    scores = jax.nn.sigmoid(jnp.asarray(rng.normal(size=(512, 8)),
                                        jnp.float32))
    drawn = jnp.asarray(rng.normal(size=(8,)) * 0.1, jnp.float32)

    def worst(bias):
        _, top = jax.lax.top_k(scores + bias, 2)
        load = np.bincount(np.asarray(top).reshape(-1), minlength=8)
        return load.max() * 8 / load.sum() - 1

    assert worst(drawn) > 0.5
    assert worst(W.fit_selection_bias(scores, 2)) < 0.05
    fitted = W.selection_biases(SEED, CFG, "float32")
    assert sorted(fitted) == [1, 2]          # the expert layers
    model = _build()
    for i, bias in fitted.items():
        assert np.array_equal(np.asarray(weights["layers"][i]["e_bias"]),
                              np.asarray(bias))
        assert np.array_equal(
            np.asarray(model.model.layers[i].mlp.e_bias._data),
            np.asarray(bias))
        assert 0 < float(jnp.max(jnp.abs(bias))) < 0.2


def test_the_fit_evens_the_load_on_tokens_it_did_not_see(weights):
    """24 fresh sequences of 32 tokens through the reference's layers:
    with the fitted bias the busiest expert of a layer stands under half
    over its share (0.34 and 0.19 here), and no further than no bias at
    all would leave it (0.46 and 0.23)."""
    ids = np.random.default_rng(5).integers(0, CFG["vocab_size"], (24, 32))
    Xs = jax.vmap(lambda row: ref._streams(
        weights["embed"]["embed"], row, 4))(jnp.asarray(ids))
    pos = jnp.arange(32)

    def worst(v):
        load = np.bincount(np.asarray(jax.lax.top_k(v, 2)[1]).reshape(-1),
                           minlength=8)
        return load.max() * 8 / load.sum() - 1

    for i, p in enumerate(weights["layers"]):
        p = ref._f32(p)
        Xs = jax.vmap(lambda x: ref.attention_sublayer(x, p, CFG, pos))(Xs)
        if W.kind_of(CFG, i) == W.EXPERT:
            s, biased = jax.vmap(lambda x: ref.biased_scores(
                ref.router_input(x, p, CFG), p))(Xs)
            assert worst(biased) < 0.5
            assert worst(biased) <= worst(s) + 0.05
        Xs = jax.vmap(lambda x: ref.ffn_sublayer(
            x, p, W.kind_of(CFG, i), CFG))(Xs)


def test_the_check_leaves_out_tokens_whose_routing_rounding_can_flip(
        capsys):
    """``served_token_gaps(route_margin=)``: a token is held to the
    reference only where its last chosen expert stands at least that far
    over its first unchosen one in every expert layer; the ladder line
    says what each margin keeps."""
    import json

    rng = np.random.default_rng(8)
    prompt, served = _prompt(rng, 40).tolist(), _prompt(rng, 24).tolist()
    kw = dict(pad_to=64, cap=32)
    every = ref.served_token_gaps(SEED, CFG, "float32", prompt, served, **kw)
    _, margin = ref.teacher_forced(SEED, CFG, "float32", prompt, served,
                                   **kw)
    margin = np.asarray(margin)
    assert len(every) == 24 and margin.shape == (24,) and margin.min() >= 0
    cut = float(np.median(margin))
    some = ref.served_token_gaps(SEED, CFG, "float32", prompt, served,
                                 route_margin=cut, **kw)
    assert some == [g for g, m in zip(every, margin) if m >= cut]
    assert 0 < len(some) < 24
    assert ref.served_token_gaps(SEED, CFG, "float32", prompt, served,
                                 route_margin=1.0, **kw) == []
    line = json.loads(capsys.readouterr().out.splitlines()[0])
    rows = line["route_margin_ladder"]
    assert rows[0][:2] == [0.0, 24] and rows[-1][1] <= rows[0][1]
    # the margin IS the distance to another choice: moving one expert's
    # score by just over it changes that token's experts
    p = ref._f32(W.layer(SEED, 1, CFG, "float32"))
    x = jnp.asarray(rng.normal(size=(6, 64)), jnp.float32)
    m = ref.route_margin(x, p, CFG)
    _, biased = ref.biased_scores(x, p)
    third = jnp.argsort(-biased, axis=-1)[:, 2]
    up = jax.nn.one_hot(third, 8) * (m[:, None] * 1.01 + 1e-6)
    before = jax.lax.top_k(biased, 2)[1]
    assert not bool(jnp.any(jnp.all(
        jnp.sort(jax.lax.top_k(biased + up, 2)[1], -1)
        == jnp.sort(before, -1), axis=-1)))
    assert bool(jnp.all(jnp.sort(jax.lax.top_k(
        biased + up * 0.9, 2)[1], -1) == jnp.sort(before, -1)))


def test_the_control_rounds_the_experts_to_the_int8_grid():
    """``expert_weights: int8_grid`` (the cell's control): every (expert,
    output channel) column of the stacked experts keeps at most 255
    levels, the values moved by under half a level; the linears are the
    engine's to quantize. Any other word is refused."""
    plain = _build()
    grid = hook.build_model(dict(CFG, expert_weights="int8_grid"), SEED,
                            "float32", train=False)
    for name in ("e_up", "e_down"):
        a = np.asarray(getattr(plain.model.layers[1].mlp, name)._data)
        b = np.asarray(getattr(grid.model.layers[1].mlp, name)._data)
        step = np.abs(a).max(axis=1, keepdims=True) / 127
        assert 0 < np.abs(a - b).max() and np.all(
            np.abs(a - b) <= step / 2 * 1.001)
        assert len(np.unique(np.round(b[3, :, 5] / step[3, 0, 5]))) <= 255
    assert np.array_equal(
        np.asarray(plain.model.layers[1].mlp.shared.up.weight._data),
        np.asarray(grid.model.layers[1].mlp.shared.up.weight._data))
    with pytest.raises(ValueError, match="expert_weights"):
        hook.build_model(dict(CFG, expert_weights="int4"), SEED, "float32",
                         train=False)


# ------------------------------------------------------------ the engine


@pytest.mark.parametrize("kernel", [None, True], ids=["xla", "kernels"])
def test_engine_prefill_then_decode_matches_reference(weights, kernel):
    """(a), (f) three requests of unequal lengths (5, 23 and 40 tokens: the
    decode step applies rotary at three different positions, one a lane)
    admitted and decoded together: the logits of every token served are the
    reference's full forward pass's. ``kernels``: the latent decode kernel
    and the prefill flash kernel, interpreted."""
    spy = Spy()
    engine = ServingEngine(spy.model, config=ServingConfig(
        **ENGINE, paged_kernel=kernel))
    assert engine.decode_kernel is bool(kernel)
    rng = np.random.default_rng(0)
    prompts = [_prompt(rng, n) for n in (5, 23, 40)]
    before = serving_metrics.stats().get("moe.layer_steps", 0)
    lanes = _serve(spy, engine, prompts, steps=6)
    assert _worst(weights, prompts, lanes) < TOL
    # the expert layers' load counters came back with the tokens
    stats = serving_metrics.stats()
    assert stats["moe.layer_steps"] - before == 6 * 2
    assert stats["moe.assignments"] >= 6 * 2 * 3 * 2
    assert engine.decode_traces == 1
    # one row of 40 values a token a layer, and nothing else
    a = engine.arena
    assert [tuple(e[0].shape) for e in a.pools] == [
        (a.num_blocks, 4, 80)] * 3 and all(len(e) == 1 for e in a.pools)
    assert a.bytes_total() == 3 * a.num_blocks * 8 * 40 * 4
    a.check_invariants()


def test_a_lane_that_restarts_reads_none_of_its_last_tenant(weights):
    """Retire and admit again into the same lanes (the restart path): the
    new requests' logits are the reference's, whatever rows the blocks
    held before."""
    spy = Spy()
    engine = ServingEngine(spy.model, config=ServingConfig(**ENGINE))
    rng = np.random.default_rng(1)
    first = [_prompt(rng, n) for n in (30, 9)]
    lanes = _serve(spy, engine, first, steps=3)
    for slot, _, _ in lanes:
        engine.retire(slot)
    again = [_prompt(rng, n) for n in (17, 33, 6)]
    lanes = _serve(spy, engine, again, steps=4)
    assert _worst(weights, again, lanes) < TOL
    assert engine.decode_traces == 1


def test_served_through_the_gateways_normal_path(weights):
    """``ServingAPI`` (scheduler, pump with a step in flight, engine,
    arena): greedy tokens are the reference's first choices."""
    from paddle_tpu.serving import RequestState, ServingAPI

    api = ServingAPI(_build(), config=ServingConfig(**ENGINE))
    try:
        rng = np.random.default_rng(6)
        prompts = [_prompt(rng, n) for n in (12, 31)]
        reqs = [api.submit(p, max_new_tokens=7) for p in prompts]
        api.run_until_idle()
        for p, r in zip(prompts, reqs):
            assert r.state == RequestState.FINISHED and len(r.tokens) == 7
            full = ref.logits(weights, CFG, list(p) + list(r.tokens)[:-1])
            gap = jnp.max(full[len(p) - 1:], -1) - jnp.take_along_axis(
                full[len(p) - 1:], jnp.asarray(r.tokens)[:, None], 1)[:, 0]
            assert float(jnp.max(gap)) < TOL
    finally:
        api.close()


# ------------------------------------------------------------ refusals (h)


@pytest.mark.parametrize("option, kw", [
    ("prefix_cache", dict(prefix_cache=True)),
    ("kv_tiering", dict(kv_tiering=True)),
    ("spec_k", dict(spec_k=2)),
    ("chunked_prefill", dict(chunked_prefill=8)),
    ("quant_kv", dict(quant_kv=True)),
])
def test_options_the_latent_pool_cannot_honour_are_refused_by_name(option,
                                                                   kw):
    with pytest.raises(ValueError, match=option):
        ServingEngine(_build(), config=ServingConfig(**ENGINE, **kw))


def test_a_model_mesh_is_refused_by_name():
    from paddle_tpu.distributed.mesh import serving_mesh

    with pytest.raises(ValueError, match="mesh"):
        ServingEngine(_build(), config=ServingConfig(
            **ENGINE, mesh=serving_mesh(2, 1)))


def test_disaggregated_handoff_is_refused():
    from paddle_tpu.serving.disagg import DisaggReplicaPool

    with pytest.raises(ValueError, match="disaggregated.*latent"):
        DisaggReplicaPool(_build(), prefill_replicas=1, decode_replicas=1)


def test_latent_layers_do_not_mix_with_kv_layers():
    import dataclasses

    from paddle_tpu.models.serving_seam import KVLayerState

    model = _build()
    declared = model.serving_spec()
    model.serving_spec = lambda: dataclasses.replace(
        declared, layers=(KVLayerState(4, 16),) + declared.layers[1:])
    with pytest.raises(ValueError, match="one shape of row"):
        ServingEngine(model, config=ServingConfig(**ENGINE))


def test_weight_quantization_is_carried(weights):
    """``quant_weights`` (the cell's control) runs: the attention's and the
    dense and shared MLPs' matrices int8, the absorbed form reading the
    dequantized up-projection; its logits are near the reference's (the
    largest difference 0.72, under the logits' standard deviation of 1.0)
    and not within ``TOL`` of them."""
    spy = Spy()
    engine = ServingEngine(spy.model, config=ServingConfig(
        **ENGINE, quant_weights=True))
    assert str(spy.model.model.layers[0].attn.kv_b.weight._data.dtype) \
        == "int8"
    prompts = [_prompt(np.random.default_rng(8), 20)]
    worst = _worst(weights, prompts, _serve(spy, engine, prompts, steps=3))
    assert 10 * TOL < worst < 1.0
