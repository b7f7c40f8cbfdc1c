"""Trinity (gated grouped-query attention over a window in three layers of
four, rotary there and no positions in the fourth, four norms a layer,
sigmoid-routed experts of which a share is held) on the normal serving
path, at the tiny preset: the model and the engine against the plain
reference (``benchmark/reference/trinity.py``), LOGITS and not tokens; the
banded flash prefill kernel (its body in the Pallas interpreter) against
the XLA form of both prefill views.

Tolerances. Program and reference both compute in float32 here (conftest
pins full matmul precision), so they differ by summation order alone: the
largest difference seen is 8e-6 on logits whose standard deviation is 1.0.
``TOL`` = 1e-4 leaves 12 times that and is far under what each breakage of
``test_tolerance_fails_what_is_wrong`` moves the logits by (each is held to
more than ten times ``TOL``), the bfloat16-for-float32 variant among them.
Routing is discrete: were a token's second and third expert scores to lie
within rounding of each other, program and reference could choose
differently and part by far more than ``TOL``; on these seeds none does.
The kernel's online softmax associates differently from the XLA form's
whole-row softmax: ``KTOL`` = 2e-5 on outputs of order 1 (seen: 1e-6)."""
import json
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.models import trinity as T
from paddle_tpu.models.serving_seam import KVLayerState, WindowLayerState
from paddle_tpu.ops import grouped_matmul as gm
from paddle_tpu.ops import paged_attention as pa
from paddle_tpu.serving import ServingConfig, ServingEngine
from paddle_tpu.serving import cache_views as E
from paddle_tpu.serving import metrics as serving_metrics

from benchmark.hooks import trinity as hook
from benchmark.reference import trinity as ref
from benchmark.weights import trinity as W

SEED = 7
TYPES = ["sliding_attention"] * 3 + ["full_attention", "sliding_attention"]
CFG = {
    "vocab_size": 512, "hidden_size": 64, "intermediate_size": 128,
    "moe_intermediate_size": 32, "num_hidden_layers": 5,
    "num_attention_heads": 6, "num_key_value_heads": 2, "head_dim": 16,
    "num_dense_layers": 1, "num_experts": 8, "num_experts_per_tok": 2,
    "num_shared_experts": 1, "score_func": "sigmoid", "route_norm": True,
    "route_scale": 2.448, "sliding_window": 8,
    "global_attn_every_n_layers": 4, "layer_types": TYPES,
    "rope_theta": 10000, "rms_norm_eps": 1e-5, "mup_enabled": True,
    "max_position_embeddings": 256,
}
ENGINE = dict(num_slots=3, kv_block_size=8, max_model_len=256)
TOL, KTOL = 1e-4, 2e-5


def _share(first, held, shared_here):
    """The configuration of one share of ``CFG``'s 8 routed experts, as a
    configuration file states it."""
    return dict(CFG, num_experts=held, expert_first=first,
                published={"num_experts": 8}, shared_expert_here=shared_here)


def _build(dtype="float32", cfg=CFG):
    return hook.build_model(cfg, SEED, dtype, train=False)


@pytest.fixture(scope="module")
def weights():
    return W.all_weights(SEED, CFG, "float32")


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


def _worst(weights, prompts, lanes, cfg=CFG):
    worst = 0.0
    for p, (_, toks, logits) in zip(prompts, lanes):
        full = ref.logits(weights, cfg, list(p) + toks[:-1])
        worst = max(worst, float(np.max(np.abs(
            np.stack(logits) - np.asarray(full[len(p) - 1:])))))
    return worst


def _moved(before, prefix):
    return {k: v - before.get(k, 0)
            for k, v in serving_metrics.stats().items()
            if k.startswith(prefix)}


# ------------------------------------------------------------- the model


def test_what_the_model_declares():
    big = T.TrinityConfig()
    assert big.expert_count == 256 and len(big.layer_types) == 60
    assert [big.is_sliding(i) for i in range(8)] == [True] * 3 + [False] \
        + [True] * 3 + [False]
    assert big.is_dense(5) and not big.is_dense(6)
    freq = T.rotary_frequencies(big)
    assert freq.shape == (64,) and freq[0] == 1.0
    assert abs(freq[-1] - 1e4 ** (-126 / 128)) < 1e-12
    model = _build()
    spec = model.serving_spec()
    assert spec.prefill_tail is None and len(spec.layers) == 5
    assert [st.kind for st in spec.layers] == ["window"] * 3 + ["kv",
                                                                "window"]
    assert spec.layers[0] == WindowLayerState(6, 16, 8, num_kv_heads=2)
    assert spec.layers[3] == KVLayerState(6, 16, num_kv_heads=2)
    assert spec.kernels == ("swa_prefill_flash",)
    layers = model.serving_layers()
    assert [la.dense for la in layers] == [True] + [False] * 4
    assert [la.attn.sliding for la in layers] == [True] * 3 + [False, True]
    assert layers[1].mlp.router.shape == [64, 8]
    ids = paddle.to_tensor(np.zeros((2, 5), np.int32))
    x = model.serving_embed(ids, 0)
    assert x.shape == [2, 5, 64] and x._data.dtype == jnp.float32
    # q, k, v, gate, o a layer; the dense MLP's two or the shared expert's
    names = [n for n, _ in model.serving_linears()]
    assert len(names) == 5 * 7 and "2.attn.gate_proj" in names
    assert "1.mlp.shared.up" in names and "0.mlp.down" in names


@pytest.mark.parametrize("bad, match", [
    (dict(score_func="softmax"), "sigmoid"),
    (dict(num_shared_experts=2), "one shared expert"),
    (dict(layer_types=["sliding_attention"] * 4), "layer_types"),
    (dict(layer_types=["sliding_attention"] * 4 + ["chunked"]),
     "layer_types"),
    (dict(num_key_value_heads=4), "multiple of the K/V"),
    (dict(expert_first=6, expert_count=4), "range of those routed"),
])
def test_what_the_layer_does_not_compute_is_refused(bad, match):
    with pytest.raises(ValueError, match=match):
        T.trinity_tiny(**bad)


def test_model_forward_matches_reference(weights):
    """``forward(ids)`` without a cache: the reference's logits over a
    sequence several windows long."""
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


@pytest.mark.parametrize("wrong", [
    "bfloat16_for_float32", "gate_left_out", "qk_norm_left_out",
    "sliding_layers_unrotated", "full_layer_rotated",
    "rotary_pairs_interleaved", "post_norms_left_out",
    "embedding_unscaled", "weights_not_normalized", "route_scale_left_out",
    "bias_in_the_weights", "window_one_row_short", "window_one_row_long",
    "shared_expert_left_out"])
def test_tolerance_fails_what_is_wrong(weights, wrong, monkeypatch):
    """Each of these must move the logits by far more than ``TOL``: the
    program in bfloat16 where float32 is stated; the attention's gate left
    out; queries and keys not normed; a sliding layer without rotary, the
    full layer with it, rotary over consecutive pairs; the two post-norms
    left out; the embedding without ``sqrt(hidden)``; the chosen weights
    not normalized, or without ``route_scale``; the selection bias carried
    into the weights; a window of 7 or of 9 rows; the shared expert
    dropped. (All but the first are made in the reference: the distance is
    the same.)"""
    ids = _prompt(np.random.default_rng(2), 60)
    cfg, dtype = dict(CFG), "float32"
    if wrong == "bfloat16_for_float32":
        dtype = "bfloat16"
    elif wrong == "gate_left_out":
        monkeypatch.setattr(ref, "gate", lambda x, p: 1.0)
    elif wrong == "qk_norm_left_out":
        norm = ref.rms_norm
        monkeypatch.setattr(ref, "rms_norm", lambda x, w, eps: (
            x if x.ndim == 3 else norm(x, w, eps)))
    elif wrong == "sliding_layers_unrotated":
        monkeypatch.setattr(ref, "rotary", lambda x, pos, freq: x)
    elif wrong == "full_layer_rotated":
        attention = ref.attention

        def rotated(x, p, c, pos, sliding):
            if sliding:
                return attention(x, p, c, pos, True)
            return attention(x, p, dict(c, sliding_window=10 ** 6), pos,
                             True)
        monkeypatch.setattr(ref, "attention", rotated)
    elif wrong == "rotary_pairs_interleaved":
        def pairs(x, pos, freq):
            ang = pos.astype(jnp.float32)[:, None, None] * freq
            x1, x2 = x[..., 0::2], x[..., 1::2]
            return jnp.stack([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                              x2 * jnp.cos(ang) + x1 * jnp.sin(ang)],
                             -1).reshape(x.shape)
        monkeypatch.setattr(ref, "rotary", pairs)
    elif wrong == "post_norms_left_out":
        norm = ref.rms_norm

        def f32(tree, skip=("e_up", "e_down")):
            out = {k: v if k in skip else v.astype(jnp.float32)
                   for k, v in tree.items()}
            for n in ("post_attn_norm", "post_mlp_norm"):
                if n in out:
                    out[n] = None
            return out
        monkeypatch.setattr(ref, "_f32", f32)
        monkeypatch.setattr(ref, "rms_norm", lambda x, w, eps: (
            x if w is None else norm(x, w, eps)))
    elif wrong == "embedding_unscaled":
        cfg["mup_enabled"] = False
    elif wrong == "weights_not_normalized":
        cfg["route_norm"] = False
    elif wrong == "route_scale_left_out":
        cfg["route_scale"] = 1.0
    elif wrong == "bias_in_the_weights":
        monkeypatch.setattr(ref, "biased_scores", lambda u, p: (
            lambda s: (s + p["e_bias"], s + p["e_bias"]))(
            jax.nn.sigmoid(ref._mm(u, p["router"]))))
    elif wrong == "window_one_row_short":
        cfg["sliding_window"] = 7
    elif wrong == "window_one_row_long":
        cfg["sliding_window"] = 9
    elif wrong == "shared_expert_left_out":
        cfg["shared_expert_here"] = False
    got = _build(dtype)(paddle.to_tensor(ids[None]))._data[0]
    want = ref.logits(weights, cfg, ids)
    assert float(jnp.max(jnp.abs(got.astype(jnp.float32) - want))) > 10 * TOL


# ------------------------------------------- the sublayers, one at a time


def test_rotary_is_rotate_half_at_the_tokens_position():
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.normal(size=(2, 5, 6, 16)), jnp.float32)
    pos = jnp.asarray([[3, 4, 5, 6, 7], [100, 101, 102, 103, 104]])
    freq = T.rotary_frequencies(T.trinity_tiny())
    got = T._rotary(x, pos, freq)
    for b in range(2):
        want = ref.rotary(x[b], pos[b], jnp.asarray(freq))
        assert float(jnp.max(jnp.abs(got[b] - want))) < 1e-5
    # a rotation: lengths kept, and relative (q . k depends on t - j)
    assert float(jnp.max(jnp.abs(jnp.sum(got * got, -1)
                                 - jnp.sum(x * x, -1)))) < 1e-4
    q, k = x[:1, :1], x[:1, 1:2]
    dot = lambda a, b: float(jnp.sum(
        T._rotary(q, jnp.asarray([[a]]), freq)
        * T._rotary(k, jnp.asarray([[b]]), freq)))
    assert abs(dot(9, 4) - dot(105, 100)) < 1e-4
    assert abs(dot(9, 4) - dot(9, 5)) > 1e-3


@pytest.mark.parametrize("index, sliding", [(0, True), (3, False)],
                         ids=["sliding", "full"])
def test_attention_sublayer_is_the_references(weights, index, sliding):
    """One attention sublayer over a whole sequence (no cache): norms a
    head, rotary in the sliding layer and none in the full one, the
    window's mask, the gate before ``Wo``."""
    layer = _build().serving_layers()[index]
    assert layer.attn.sliding is sliding
    p = ref._f32(weights["layers"][index])
    x = jnp.asarray(np.random.default_rng(4).normal(size=(1, 30, 64)),
                    jnp.float32)
    view = T._SequenceView(window=8 if sliding else None)
    got, _ = layer.attn(paddle.to_tensor(x), view, 0)
    want = ref.attention(x[0], p, CFG, jnp.arange(30), sliding)
    assert float(jnp.max(jnp.abs(got._data[0] - want))) < TOL
    # the other kind of layer's attention of the same weights differs
    other = ref.attention(x[0], p, CFG, jnp.arange(30), not sliding)
    assert float(jnp.max(jnp.abs(got._data[0] - other))) > 100 * TOL


@pytest.mark.parametrize("index", [0, 2], ids=["dense", "experts"])
def test_a_layer_has_four_norms_around_two_sublayers(weights, index):
    layer = _build().serving_layers()[index]
    p = ref._f32(weights["layers"][index])
    x = jnp.asarray(np.random.default_rng(5).normal(size=(1, 20, 64)),
                    jnp.float32)
    got, _ = layer(paddle.to_tensor(x), cache=T._SequenceView(window=8))
    want = ref.block(x[0], p, CFG, jnp.arange(20), index)
    assert float(jnp.max(jnp.abs(got._data[0] - want))) < TOL
    gains = [n for n, _ in layer.named_parameters() if n.endswith("_norm")]
    assert sorted(gains) == ["attn.k_norm", "attn.q_norm", "input_norm",
                             "post_attn_norm", "post_mlp_norm",
                             "pre_mlp_norm"]


def test_the_embedding_is_scaled_by_the_root_of_the_width(weights):
    model = _build()
    ids = np.arange(10, dtype=np.int32)[None]
    got = model.serving_embed(paddle.to_tensor(ids), 0)._data[0]
    table = weights["embed"]["embed"]
    assert float(jnp.max(jnp.abs(got - table[ids[0]] * 8.0))) < 1e-6
    assert abs(float(jnp.mean(got * got)) - 1.0) < 0.2  # unit mean square


def test_router_weights_sum_to_the_route_scale():
    rng = np.random.default_rng(6)
    x = jnp.asarray(rng.normal(size=(40, 64)), jnp.float32)
    router = jnp.asarray(rng.normal(size=(64, 8)), jnp.float32) / 8
    bias = jnp.zeros((8,)).at[5].set(10.0)
    idx, w = gm.route_sigmoid_topk(x, router, bias, 2, 2.448, True)
    assert bool(jnp.all(jnp.any(idx == 5, axis=1)))      # the bias chooses
    assert float(jnp.max(jnp.abs(jnp.sum(w, -1) - 2.448))) < 1e-5
    s = jax.nn.sigmoid(x @ router)                       # the score weighs
    chosen = jnp.take_along_axis(s, idx, 1)
    assert float(jnp.max(jnp.abs(
        w - 2.448 * chosen / jnp.sum(chosen, -1, keepdims=True)))) < 1e-5
    moe = _build().serving_layers()[1].mlp
    _, w = moe.route(x)
    assert float(jnp.max(jnp.abs(jnp.sum(w, -1) - 2.448))) < 1e-5
    assert w.shape == (40, 2) and moe.scaling == 2.448 and moe.normalize


# ------------------------------------------------------------- the shares


def test_the_shares_add_up_to_the_uncut_layer(weights):
    """What a chip of an 8-way split computes, for every one of the eight
    (1 routed expert each; the shared expert counted by the first alone),
    adds up to the uncut reference layer; each part is the reference's for
    that share; and a share holds the very expert the whole layer holds
    there. (Layer 1: its router's input does not depend on the experts
    before it, so every share's fitted bias is the whole layer's.)"""
    p = ref._f32(weights["layers"][1])
    u = jnp.asarray(np.random.default_rng(9).normal(size=(1, 50, 64)),
                    jnp.float32)
    want = ref.experts(u[0], p, CFG)
    whole = _build().serving_layers()[1].mlp
    assert float(jnp.max(jnp.abs(
        whole(paddle.to_tensor(u))._data[0] - want))) < TOL
    total = 0.0
    for i in range(8):
        cfg = _share(i, 1, shared_here=i == 0)
        if i in (0, 7):    # through the hook: the whole model of the share
            moe = _build(cfg=cfg).serving_layers()[1].mlp
        else:              # the layer alone, filled as the hook fills it
            moe = T.TrinityMoE(hook.model_config(cfg))
            leaves = W.layer(SEED, 1, cfg, "float32")
            for name, param in moe.named_parameters():
                param._data = leaves[hook.leaf_of(
                    "model.layers.1.mlp." + name)[2]]
        assert moe.e_up.shape[0] == 1 and moe.router.shape == [64, 8]
        assert (moe.shared is not None) is (i == 0)
        assert np.array_equal(np.asarray(moe.e_up._data),
                              np.asarray(whole.e_up._data[i:i + 1]))
        assert np.array_equal(np.asarray(moe.e_bias._data),
                              np.asarray(whole.e_bias._data))
        part = moe(paddle.to_tensor(u))._data[0]
        mine = ref.experts(u[0], ref._f32(W.layer(SEED, 1, cfg, "float32")),
                           cfg)
        assert float(jnp.max(jnp.abs(part - mine))) < TOL
        total = total + part
    assert float(jnp.max(jnp.abs(total - want))) < TOL
    assert float(jnp.max(jnp.abs(want))) > 100 * TOL


def test_the_selection_bias_is_fit_to_an_even_load(weights):
    fitted = W.selection_biases(SEED, CFG, "float32")
    assert sorted(fitted) == [1, 2, 3, 4]
    layers = _build().serving_layers()
    for i, bias in fitted.items():
        assert np.array_equal(np.asarray(weights["layers"][i]["e_bias"]),
                              np.asarray(bias))
        assert np.array_equal(np.asarray(layers[i].mlp.e_bias._data),
                              np.asarray(bias))
        assert 0 < float(jnp.max(jnp.abs(bias))) < 0.5
    # fresh sequences through the reference's layers: no expert stands at
    # twice its share
    ids = np.random.default_rng(5).integers(0, CFG["vocab_size"], (24, 32))
    X = ref.embedded(weights["embed"]["embed"], ids, CFG)
    pos = jnp.arange(32)
    for i, p in enumerate(weights["layers"]):
        p = ref._f32(p)
        X1, U = jax.vmap(lambda x: ref.router_input(x, p, CFG, pos, i))(X)
        if i:
            _, biased = jax.vmap(lambda u: ref.biased_scores(u, p))(U)
            top = np.asarray(jax.lax.top_k(biased, 2)[1]).reshape(-1)
            load = np.bincount(top, minlength=8)
            assert load.max() * 8 / load.sum() - 1 < 1.0
        X = jax.vmap(lambda x1, u: ref.rest_of_layer(x1, u, p, CFG))(X1, U)


def test_the_check_leaves_out_tokens_whose_routing_rounding_can_flip(
        capsys):
    """``served_token_gaps(route_margin=)``: a token is held to the
    reference only where no rounding under the margin changes what this
    share adds for it; the ladder line says what each margin keeps."""
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
    line = json.loads(capsys.readouterr().out.splitlines()[0])
    rows = line["route_margin_ladder"]
    assert rows[0][:2] == [0.0, 24] and rows[-1][1] <= rows[0][1]
    # the whole layer: the margin IS the edge of the choice
    p = ref._f32(W.layer(SEED, 2, CFG, "float32"))
    x = jnp.asarray(rng.normal(size=(60, 64)), jnp.float32)
    _, biased = ref.biased_scores(x, p)
    top = jax.lax.top_k(biased, 3)[0]
    edge = top[:, 1] - top[:, 2]
    assert float(jnp.max(jnp.abs(ref.route_margin(x, p, CFG) - edge))) < 1e-7
    # a share of 2: the edge where a held expert is chosen (the weights
    # are normalized over the chosen, so any change of them moves it);
    # else the distance to the best unchosen held expert, never less
    cfg = _share(2, 2, shared_here=False)
    m = ref.route_margin(x, p, cfg)
    chosen = jax.lax.top_k(biased, 2)[1]
    holds = jnp.any((chosen >= 2) & (chosen < 4), -1)
    assert 0 < int(jnp.sum(holds)) < 60
    assert float(jnp.max(jnp.abs(jnp.where(holds, m - edge, 0.0)))) < 1e-7
    assert bool(jnp.all(m >= edge - 1e-7))
    assert float(jnp.mean(jnp.where(holds, 0.0, m > edge + 1e-7))) > 0.2


def test_the_control_rounds_the_held_experts_to_the_int8_grid():
    plain = _build().serving_layers()[1].mlp
    grid = _build(cfg=dict(CFG, expert_weights="int8_grid")) \
        .serving_layers()[1].mlp
    for name in ("e_up", "e_down"):
        a = np.asarray(getattr(plain, name)._data)
        b = np.asarray(getattr(grid, name)._data)
        step = np.abs(a).max(axis=1, keepdims=True) / 127
        assert 0 < np.abs(a - b).max() and np.all(
            np.abs(a - b) <= step / 2 * 1.001)
    assert np.array_equal(np.asarray(plain.router._data),
                          np.asarray(grid.router._data))
    with pytest.raises(ValueError, match="expert_weights"):
        _build(cfg=dict(CFG, expert_weights="int4"))


# ------------------------------------------- the banded flash prefill kernel


def _plain_attention(q, k, v, window):
    s, h, d = q.shape
    g = h // k.shape[1]
    k, v = jnp.repeat(k, g, 1), jnp.repeat(v, g, 1)
    sc = jnp.einsum("qhd,khd->hqk", q, k) / math.sqrt(d)
    t = jnp.arange(s)
    mask = t[None, :] <= t[:, None]
    if window is not None:
        mask &= t[:, None] - t[None, :] < window
    pr = jax.nn.softmax(jnp.where(mask[None], sc, -jnp.inf), -1)
    return jnp.einsum("hqk,khd->qhd", pr, v)


def _qkv(s, heads, kv_heads, d=16, seed=0):
    ks = jax.random.split(jax.random.key(seed + s), 3)
    return (jax.random.normal(ks[0], (s, heads, d)),
            jax.random.normal(ks[1], (s, kv_heads, d)),
            jax.random.normal(ks[2], (s, kv_heads, d)))


@pytest.mark.parametrize("s, window, block", [
    (5, 8, 8),        # shorter than the window, than a tile
    (8, 8, 8),        # the window, one tile
    (24, 8, 8),       # three windows: a band of two tiles
    (37, 8, 8),       # a length no tile divides
    (64, 8, 16),      # tiles of two windows
    (33, 17, 8),      # a window no tile divides: a band of three
    (100, 1, 8),      # the query's own key alone
    (40, None, 8),    # causal only: every tile under the diagonal
    (16, 8, 512),     # the default tile, cut to the sequence
])
def test_banded_kernel_is_plain_attention_over_the_window(s, window, block):
    q, k, v = _qkv(s, 4, 2)
    got = pa.swa_prefill_attention(q, k, v, window, block=block)
    assert got.shape == q.shape
    assert float(jnp.max(jnp.abs(
        got - _plain_attention(q, k, v, window)))) < KTOL


def test_banded_kernel_visits_the_band_and_no_more():
    """The grid's key axis is the band's tiles (window / tile + 1), not
    the sequence's; with no window, the sequence's."""
    q, k, v = _qkv(64, 2, 1)
    for window, steps in ((8, 2), (16, 3), (17, 3), (18, 4), (None, 8)):
        jaxpr = str(jax.make_jaxpr(lambda a, b, c: pa.swa_prefill_attention(
            a, b, c, window, block=8))(q, k, v))
        assert f"grid=(2, 8, {steps})" in jaxpr


@pytest.fixture
def small_tiles(monkeypatch):
    """The views call the kernel with its default tile (512 rows): a tile
    of 8 makes a tiny prompt several tiles long."""
    monkeypatch.setattr(pa, "_SWA_BLOCK", 8)


@pytest.mark.parametrize("group", [1, 2, 6])
@pytest.mark.parametrize("s", [5, 8, 24, 37])
def test_window_prefill_view_by_kernel_is_the_view_by_xla(s, group,
                                                          small_tiles):
    """``WindowPrefillView(kernel=True)`` against ``kernel=False`` over
    lengths shorter than, equal to and several times the window and one no
    tile divides, at 1, 2 and 6 query heads a K/V head: the same attention
    and the SAME ring (the write is the view's own either way)."""
    q, k, v = (a[None] for a in _qkv(s, 2 * group, 2, seed=group))
    rings = tuple(jnp.full((3, 2, 8, 16), 7.0) for _ in range(2))
    out = {}
    for kernel in (False, True):
        view = E.WindowPrefillView(rings, jnp.int32(1), jnp.int32(s - 2), 8,
                                   kernel=kernel)
        o, nv = view.update_and_attend(q, k, v)
        assert isinstance(nv, E.WindowPrefillView) and nv.kernel is kernel
        out[kernel] = (o, nv.entry)
    assert float(jnp.max(jnp.abs(out[True][0] - out[False][0]))) < KTOL
    for a, b in zip(out[True][1], out[False][1]):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    assert float(jnp.max(jnp.abs(
        out[True][0][0] - _plain_attention(q[0], k[0], v[0], 8)))) < KTOL


@pytest.mark.parametrize("group", [1, 2, 6])
@pytest.mark.parametrize("s", [5, 8, 24, 37])
def test_capture_prefill_view_by_kernel_is_the_view_by_xla(s, group,
                                                           small_tiles):
    """``CapturePrefillView(kernel=True)``: the same kernel with no
    window, against the XLA form; the captured K/V are the layer's own."""
    q, k, v = (a[None] for a in _qkv(s, 2 * group, 2, seed=10 + group))
    o_x, cap_x = E.CapturePrefillView(8).update_and_attend(q, k, v)
    o_k, cap_k = E.CapturePrefillView(8, kernel=True).update_and_attend(
        q, k, v)
    assert float(jnp.max(jnp.abs(o_k - o_x))) < KTOL
    assert cap_k.k is k and cap_x.v is v
    # one query row alone (a prefill tail's last row) keeps the XLA form
    last = jnp.int32(s - 1)
    one, _ = E.CapturePrefillView(8, kernel=True, last=last) \
        .update_and_attend(q[:, s - 1:], k, v)
    assert float(jnp.max(jnp.abs(one[0, 0] - o_x[0, s - 1]))) < KTOL


# ------------------------------------------------------------ the engine


@pytest.mark.parametrize("kernel", [None, True], ids=["xla", "kernels"])
def test_engine_prefill_then_decode_matches_reference(weights, kernel,
                                                      small_tiles):
    """Three requests of unequal lengths (5, 23 and 40 tokens: one starts
    inside the window of 8 and grows past it, two start past it; the
    decode step applies rotary at three different positions, one a lane)
    admitted and decoded together through four rings a lane and one paged
    pool: the logits of every token served are the reference's full
    forward pass's. The rings hold ROTATED keys, each at ``position %
    window``: their order is free because a row carries its position in
    its values. ``kernels``: the banded flash prefill kernel (tiles of 8:
    a 40-token prompt is five) and the paged decode kernel at 3 query
    heads a K/V head, interpreted."""
    spy = Spy()
    engine = ServingEngine(spy.model, config=ServingConfig(
        **ENGINE, paged_kernel=kernel))
    assert engine.decode_kernel is bool(kernel)
    assert engine.paged_kernel is bool(kernel)
    rng = np.random.default_rng(0)
    prompts = [_prompt(rng, n) for n in (5, 23, 40)]
    before = dict(serving_metrics.stats())
    lanes = _serve(spy, engine, prompts, steps=12)
    assert _worst(weights, prompts, lanes) < TOL
    # the expert layers' and the sliding layers' counters came back with
    # the tokens
    moved = _moved(before, "moe.")
    assert moved["moe.layer_steps"] == 12 * 4
    assert moved["moe.assignments"] == 12 * 4 * 3 * 2
    assert moved["moe.local_assignments"] == moved["moe.assignments"]
    moved = _moved(before, "window.")
    assert moved["window.rows_read"] == 12 * 4 * 3 * 8
    # lane 0 writes positions 5..16: 6, 7, then 8 rows live; the others 8
    assert moved["window.rows_live"] == 4 * (6 + 7 + 10 * 8 + 2 * 12 * 8)
    assert engine.decode_traces == 1
    assert serving_metrics.gauges()["kernel.swa_prefill_flash"] == 0
    # four rings of [kv heads, window, head] K and V a lane; one pool
    a = engine.arena
    assert len(a.pools) == 1 and len(a.pools[0]) == 2
    a.check_invariants()


def test_a_share_is_served_and_matches_the_references_share():
    """The engine over a chip's share (experts 2..3 of 8, the shared
    expert here): prefill then decode give the logits of the reference
    given the same share, and the counters tell local from absent."""
    cfg = _share(2, 2, shared_here=True)
    weights = W.all_weights(SEED, cfg, "float32")
    spy = Spy(cfg=cfg)
    engine = ServingEngine(spy.model, config=ServingConfig(**ENGINE))
    rng = np.random.default_rng(3)
    prompts = [_prompt(rng, n) for n in (11, 30)]
    before = dict(serving_metrics.stats())
    lanes = _serve(spy, engine, prompts, steps=5)
    assert _worst(weights, prompts, lanes, cfg) < TOL
    moved = _moved(before, "moe.")
    assert moved["moe.assignments"] == 5 * 4 * 2 * 2
    assert 0 < moved["moe.local_assignments"] < moved["moe.assignments"]
    assert moved["moe.experts_touched"] <= 5 * 4 * 2


def test_a_ring_taken_by_a_new_tenant_holds_none_of_the_last(weights):
    """Lanes retired and admitted to again: the prefill fills a lane's four
    rings anew (a 6-token prompt into a ring that held 8 rotated rows of a
    30-token context: the rows past the new context are masked until it
    writes them)."""
    spy = Spy()
    engine = ServingEngine(spy.model, config=ServingConfig(**ENGINE))
    rng = np.random.default_rng(1)
    first = [_prompt(rng, n) for n in (30, 9)]
    lanes = _serve(spy, engine, first, steps=3)
    for slot, _, _ in lanes:
        engine.retire(slot)
    again = [_prompt(rng, n) for n in (25, 31, 6)]
    lanes = _serve(spy, engine, again, steps=6)
    assert _worst(weights, again, lanes) < TOL
    assert engine.decode_traces == 1


def test_served_through_the_gateways_normal_path(weights):
    """``ServingAPI`` (scheduler, pump with a step in flight, engine,
    arena): greedy tokens are the reference's first choices."""
    from paddle_tpu.serving import RequestState, ServingAPI

    api = ServingAPI(_build(), config=ServingConfig(**ENGINE))
    try:
        rng = np.random.default_rng(6)
        prompts = [_prompt(rng, n) for n in (12, 14)]
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


def test_served_behind_gateway_serve(weights):
    """``gateway.serve`` (the benchmark's front door, ``POST /v1/stream``
    through the load generator's own client) takes the model as it takes
    the other five, on the kernel route the cell asks for."""
    import time

    from benchmark.harness.loadgen import Client
    from paddle_tpu.serving.gateway.gateway import serve

    gw = serve(_build(), replicas=1, port=0, guard=False,
               config=ServingConfig(**ENGINE, paged_kernel=True))
    try:
        prompt = _prompt(np.random.default_rng(2), 19).tolist()
        rec = Client(f"http://127.0.0.1:{gw.port}", time.monotonic()).stream(
            {"id": 0, "due_s": None, "max_new_tokens": 4},
            json.dumps({"prompt": prompt, "max_new_tokens": 4}).encode())
        assert rec["state"] == "FINISHED" and len(rec["tokens"]) == 4
        full = ref.logits(weights, CFG, prompt + rec["tokens"][:-1])
        assert [int(t) for t in jnp.argmax(full[18:], -1)] == rec["tokens"]
    finally:
        gw.close()


# ------------------------------------------------------------ refusals


@pytest.mark.parametrize("option, kw", [
    ("prefix_cache", dict(prefix_cache=True)),
    ("kv_tiering", dict(kv_tiering=True, prefix_cache=False)),
    ("spec_k", dict(spec_k=2)),
    ("chunked_prefill", dict(chunked_prefill=8)),
])
def test_options_a_window_model_cannot_honour_are_refused_by_name(option,
                                                                  kw):
    with pytest.raises(ValueError, match=option):
        ServingEngine(_build(), config=ServingConfig(**ENGINE, **kw))


def test_the_control_is_carried(weights):
    """``quant_weights`` and ``quant_kv`` (the cell's control) run: the
    attention's five matrices (the gate's among them), the dense MLP's
    and the shared experts' int8, the one paged pool int8 with its scale
    pools, the rings as they were; the logits are near the reference's
    and not within ``TOL`` of them."""
    spy = Spy()
    engine = ServingEngine(spy.model, config=ServingConfig(
        **ENGINE, quant_weights=True, quant_kv=True))
    layers = spy.model.serving_layers()
    assert str(layers[1].attn.gate_proj.weight._data.dtype) == "int8"
    assert str(layers[0].mlp.up.weight._data.dtype) == "int8"
    assert str(layers[2].mlp.shared.down.weight._data.dtype) == "int8"
    assert str(layers[2].mlp.e_up._data.dtype) == "float32"
    assert len(engine.arena.pools[0]) == 4
    prompts = [_prompt(np.random.default_rng(8), 20)]
    worst = _worst(weights, prompts, _serve(spy, engine, prompts, steps=3))
    assert 10 * TOL < worst < 1.5
