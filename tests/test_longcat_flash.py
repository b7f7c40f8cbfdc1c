"""LongCat-Flash (two latent-attention sublayers a layer over a
one-row-a-token paged cache each, a shortcut-connected expert layer across
them, softmax routing over routed and zero-compute columns, a share of the
experts held) on the normal serving path, at the tiny preset: the model and
the engine against the plain reference
(``benchmark/reference/longcat_flash.py``), LOGITS and not tokens.

Tolerances. Program and reference both compute in float32 here (conftest
pins full matmul precision), so they differ by summation order alone: the
largest difference seen is 8e-6 on logits whose standard deviation is 1.0.
``TOL`` = 1e-4 leaves 12 times that and is far under what each breakage of
``test_tolerance_fails_what_is_wrong`` moves the logits by (each is held to
more than ten times ``TOL``), the bfloat16-for-float32 variant among them.
Routing is discrete: were a token's third and fourth column scores to lie
within rounding of each other, program and reference could choose
differently and part by far more than ``TOL``; on these seeds none does."""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.models import longcat_flash as L
from paddle_tpu.models.serving_seam import LatentKVLayerState
from paddle_tpu.ops import grouped_matmul as gm
from paddle_tpu.serving import ServingConfig, ServingEngine
from paddle_tpu.serving import metrics as serving_metrics

from benchmark.hooks import longcat_flash as hook
from benchmark.reference import longcat_flash as ref
from benchmark.weights import longcat_flash as W

SEED = 11
CFG = {
    "vocab_size": 512, "hidden_size": 64, "ffn_hidden_size": 128,
    "expert_ffn_hidden_size": 32, "num_layers": 2, "num_attention_heads": 4,
    "q_lora_rank": 32, "kv_lora_rank": 32, "qk_nope_head_dim": 16,
    "qk_rope_head_dim": 8, "v_head_dim": 16, "n_routed_experts": 8,
    "zero_expert_num": 4, "zero_expert_type": "identity", "moe_topk": 3,
    "routed_scaling_factor": 6, "rms_norm_eps": 1e-5, "rope_theta": 1e7,
    "mla_scale_q_lora": True, "mla_scale_kv_lora": True,
    "attention_method": "MLA", "attention_bias": False,
    "max_position_embeddings": 256,
}
ENGINE = dict(num_slots=3, kv_block_size=8, max_model_len=256)
TOL = 1e-4


def _share(first, held, zero_here):
    """The configuration of one share of ``CFG``'s 8 routed experts, as a
    configuration file states it."""
    return dict(CFG, n_routed_experts=held, expert_first=first,
                published={"n_routed_experts": 8},
                zero_experts_here=zero_here)


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


# ------------------------------------------------------------- the model


def test_what_the_model_declares():
    big = L.LongcatFlashConfig()
    assert big.row_width == 576 and big.expert_count == 512
    assert big.router_columns == 768
    attn = L.LongcatAttention(L.longcat_flash_tiny(hidden_size=128))
    assert attn.q_scale == 2.0 and abs(attn.kv_scale - 2.0) < 1e-12
    assert abs(L.softmax_scale(big) - 192 ** -0.5) < 1e-15
    assert L.rotary_frequencies(big)[0] == 1.0
    assert abs(L.rotary_frequencies(big)[-1] - 1e7 ** (-62 / 64)) < 1e-12
    model = _build()
    spec = model.serving_spec()
    # two cache entries a layer: one serving layer each
    assert spec.prefill_tail is None and len(spec.layers) == 4
    assert len(model.serving_layers()) == 4
    assert all(st == LatentKVLayerState(32, 8, 4) and st.kind == "latent"
               and st.width == 40 for st in spec.layers)
    assert spec.kernels == ("grouped_matmul",)
    halves = model.serving_layers()
    assert [h.expert for h in halves] == [True, False, True, False]
    assert halves[0].moe.router.shape == [64, 12]
    assert halves[1].moe is None
    ids = paddle.to_tensor(np.zeros((2, 5), np.int32))
    x = model.serving_embed(ids, 0)
    assert x.shape == [2, 5, 64] and x._data.dtype == jnp.float32
    assert len(model.serving_linears()) == 2 * 2 * 7


@pytest.mark.parametrize("bad, match", [
    (dict(zero_expert_type="constant"), "identity"),
    (dict(attention_method="MHA"), "latent attention"),
    (dict(expert_first=6, expert_count=4), "range of those routed"),
])
def test_what_the_layer_does_not_compute_is_refused(bad, match):
    with pytest.raises(ValueError, match=match):
        L.longcat_flash_tiny(**bad)


@pytest.mark.parametrize("absorbed", [False, True],
                         ids=["expanded", "absorbed"])
def test_model_forward_matches_reference(weights, absorbed):
    """The absorbed form (queries carried into the latent space with both
    scale factors folded, the cached row as key and value) gives what
    expanded keys and values give: both the reference's logits."""
    ids = _prompt(np.random.default_rng(0), 60)
    got = _build()(paddle.to_tensor(ids[None]), absorbed=absorbed)._data[0]
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
    "bfloat16_for_float32", "q_scale_left_out", "kv_scale_left_out",
    "weights_renormalised", "shortcut_joined_after_sublayer_a",
    "bias_in_the_weights", "zero_experts_left_out"])
def test_tolerance_fails_what_is_wrong(weights, wrong, monkeypatch):
    """Each of these must move the logits by far more than ``TOL``: the
    program in bfloat16 where float32 is stated; either latent scale
    factor left out; the chosen weights renormalised to sum 1; the expert
    layer's output added before sublayer ``b`` instead of at the layer's
    end (sublayer ``b`` then reads it); the selection bias carried into
    the mixing weights; the zero-compute experts' part dropped. (All but
    the first are made in the reference: the distance is the same.)"""
    ids = _prompt(np.random.default_rng(2), 60)
    cfg, dtype = dict(CFG), "float32"
    if wrong == "bfloat16_for_float32":
        dtype = "bfloat16"
    elif wrong == "q_scale_left_out":
        cfg["mla_scale_q_lora"] = False
    elif wrong == "kv_scale_left_out":
        cfg["mla_scale_kv_lora"] = False
    elif wrong == "weights_renormalised":
        route = ref.route
        monkeypatch.setattr(ref, "route", lambda u, p, c: (
            lambda w: w / jnp.sum(w, -1, keepdims=True)
            * float(c["routed_scaling_factor"]))(route(u, p, c)))
    elif wrong == "shortcut_joined_after_sublayer_a":
        def early(x1, u, p, c, pos):
            eps = float(c["rms_norm_eps"])
            x2 = x1 + ref.swiglu(u, p["a_up"], p["a_down"]) \
                + ref.experts(u, p, c)
            x3 = x2 + ref.attention(ref.rms_norm(x2, p["b_attn_norm"], eps),
                                    p, "b", c, pos)
            return x3 + ref.swiglu(ref.rms_norm(x3, p["b_mlp_norm"], eps),
                                   p["b_up"], p["b_down"])
        monkeypatch.setattr(ref, "rest_of_layer", early)
    elif wrong == "bias_in_the_weights":
        monkeypatch.setattr(ref, "biased_scores", lambda u, p: (
            lambda prob: (prob + p["e_bias"], prob + p["e_bias"]))(
            jax.nn.softmax(ref._mm(u, p["router"]), -1)))
    elif wrong == "zero_experts_left_out":
        cfg["zero_experts_here"] = False
    got = _build(dtype)(paddle.to_tensor(ids[None]))._data[0]
    want = ref.logits(weights, cfg, ids)
    assert float(jnp.max(jnp.abs(got.astype(jnp.float32) - want))) > 10 * TOL


# ---------------------------------------------- routing and the zero experts


def test_routing_is_softmax_over_all_columns_biased_for_the_choice_only():
    rng = np.random.default_rng(4)
    x = jnp.asarray(rng.normal(size=(30, 64)), jnp.float32)
    router = jnp.asarray(rng.normal(size=(64, 12)), jnp.float32) / 4
    bias = jnp.zeros((12,)).at[9].set(10.0)
    idx, w = gm.route_softmax_topk(x, router, bias, 3, 6.0)
    assert bool(jnp.all(jnp.any(idx == 9, axis=1)))     # the bias chooses
    p = jax.nn.softmax(x @ router, -1)                   # the score weighs
    assert float(jnp.max(jnp.abs(
        w - 6.0 * jnp.take_along_axis(p, idx, 1)))) < 1e-6
    # not renormalised: the chosen weights sum to 6 x their mass, under 6
    assert float(jnp.max(jnp.sum(w, -1))) < 6.0
    free, _ = gm.route_softmax_topk(x, router, jnp.zeros((12,)), 3, 6.0)
    assert bool(jnp.all(jnp.sort(free, -1) == jnp.sort(
        jax.lax.top_k(p, 3)[1], -1)))
    # the zero-compute columns' weight
    zw = gm.zero_expert_weight(idx, w, 8)
    assert float(jnp.max(jnp.abs(zw - jnp.sum(
        jnp.where(idx >= 8, w, 0.0), -1)))) == 0.0 and float(zw.min()) > 0


def test_a_token_that_chose_only_zero_experts_comes_out_scaled():
    """Every pick a zero-compute expert (the selection bias sends all three
    there): the layer's output is ``6 sum(p chosen) u``, no expert read."""
    moe = _build().serving_layers()[0].moe
    moe.e_bias._data = jnp.zeros((12,)).at[8:].set(10.0)
    u = jnp.asarray(np.random.default_rng(3).normal(size=(1, 20, 64)),
                    jnp.float32)
    got = moe(u, u)[0]
    p = jax.nn.softmax(u[0] @ moe.router._data, -1)
    mass = jnp.sum(jax.lax.top_k(p[:, 8:], 3)[0], -1, keepdims=True)
    assert float(jnp.max(jnp.abs(got - 6.0 * mass * u[0]))) < 1e-5
    counts = gm.load_counters(moe.route(u[0])[0], 12, first=0, held=8,
                              routed=8)
    assert int(counts["moe.zero_assignments"]) == 60
    assert int(counts["moe.local_assignments"]) == 0
    assert int(counts["moe.rows_moved"]) == 0


# ------------------------------------------------------------- the shares


def test_the_shares_add_up_to_the_uncut_layer(weights):
    """What a chip of a 4-way split computes, for every one of the four
    (2 routed experts each; the zero-compute part counted by the first
    alone), adds up to the uncut reference layer; each part is the
    reference's for that share; and a share holds the very experts the
    whole layer holds there. (Layer 0: its router's input does not depend
    on the experts before it, so every share's fitted bias is the whole
    layer's.)"""
    p = ref._f32(weights["layers"][0])
    u = jnp.asarray(np.random.default_rng(9).normal(size=(1, 50, 64)),
                    jnp.float32)
    want = ref.experts(u[0], p, CFG)
    whole = _build().serving_layers()[0].moe
    assert float(jnp.max(jnp.abs(whole(u, u)[0] - want))) < TOL
    total = 0.0
    for i in range(4):
        cfg = _share(2 * i, 2, zero_here=i == 0)
        moe = _build(cfg=cfg).serving_layers()[0].moe
        assert moe.e_up.shape[0] == 2 and moe.router.shape == [64, 12]
        assert np.array_equal(np.asarray(moe.e_up._data),
                              np.asarray(whole.e_up._data[2 * i:2 * i + 2]))
        assert np.array_equal(np.asarray(moe.e_bias._data),
                              np.asarray(whole.e_bias._data))
        part = moe(u, u)[0]
        mine = ref.experts(u[0], ref._f32(W.layer(SEED, 0, cfg, "float32")),
                           cfg)
        assert float(jnp.max(jnp.abs(part - mine))) < TOL
        total = total + part
    assert float(jnp.max(jnp.abs(total - want))) < TOL
    assert float(jnp.max(jnp.abs(want))) > 100 * TOL


def _per_token(x, idx, w, up, down, first):
    """Each token's held experts applied one by one (numpy float64)."""
    x, up, down = (np.asarray(a, np.float64) for a in (x, up, down))
    out = np.zeros_like(x)
    for t in range(x.shape[0]):
        for e, we in zip(np.asarray(idx[t]), np.asarray(w[t], np.float64)):
            if first <= e < first + up.shape[0]:
                g, u = np.split(x[t] @ up[e - first], 2)
                out[t] += we * ((g / (1 + np.exp(-g)) * u) @ down[e - first])
    return out


_T, _K, _E, _HELD, _FIRST = 256, 4, 48, 2, 6


def _routing(case, rng):
    idx = np.stack([rng.permutation(_E)[:_K] for _ in range(_T)])
    if case == "onto_one_held_expert":
        idx[:] = _FIRST + 1
    elif case == "onto_none":
        idx = np.stack([rng.permutation(_FIRST)[:_K] for _ in range(_T)])
    elif case == "mixed":
        idx[:100, 0], idx[50:200, 1] = _FIRST, _FIRST + 1
    return idx


@pytest.mark.parametrize("interpret", [False, True],
                         ids=["ragged_dot", "megablox_interpreted"])
@pytest.mark.parametrize("case", ["even", "onto_one_held_expert",
                                  "onto_none", "mixed"])
def test_a_small_share_drops_no_token_at_any_load(case, interpret):
    """A share of 2 of 48 columns, 1,024 assignments: a pass of the
    grouped matmuls takes ``row_cap`` = 128 rows (twice the share's even
    load of 43), and as many passes run as the share's assignments need:
    one at an even routing, eight when every assignment is one held
    expert's (a capacity would drop seven in eight), none when the share
    gets nothing. Every assignment is in the result."""
    rng = np.random.default_rng(7)
    x = jnp.asarray(rng.normal(size=(_T, 64)), jnp.float32)
    up = jnp.asarray(rng.normal(size=(_HELD, 64, 64)), jnp.float32) / 8
    down = jnp.asarray(rng.normal(size=(_HELD, 32, 64)), jnp.float32) / 6
    idx = jnp.asarray(_routing(case, rng), jnp.int32)
    w = jnp.asarray(rng.uniform(0.2, 1.0, (_T, _K)), jnp.float32)
    assert gm.row_cap(_T * _K, _HELD, _E) == 128
    got = gm.expert_ffn(x, idx, w, up, down, _E, first=_FIRST,
                        interpret=interpret)
    want = _per_token(x, idx, w, up, down, _FIRST)
    assert float(np.max(np.abs(np.asarray(got) - want))) < 1e-4
    counts = {k: int(v) for k, v in gm.load_counters(
        idx, _E, first=_FIRST, held=_HELD, routed=40).items()}
    local = int(np.sum((np.asarray(idx) >= _FIRST)
                       & (np.asarray(idx) < _FIRST + _HELD)))
    assert counts["moe.assignments"] == _T * _K
    assert counts["moe.local_assignments"] == local
    assert counts["moe.rows_moved"] == -(-local // 128) * 128
    assert counts["moe.zero_assignments"] == int(np.sum(np.asarray(idx)
                                                        >= 40))
    if case == "onto_one_held_expert":
        assert counts["moe.rows_moved"] == _T * _K
        assert counts["moe.max_expert_assignments"] == _T * _K
        assert counts["moe.experts_touched"] == 1
    if case == "onto_none":
        assert float(np.max(np.abs(np.asarray(got)))) == 0.0


@pytest.mark.parametrize("assignments, held, columns, cap", [
    (1536, 16, 768, 64),        # a decode step of 128 lanes: 32 expected
    (49152, 16, 768, 2048),     # a 4,096-token prefill: 1,024 expected
    (3072, 16, 768, 128),       # a 256-token prefill
    (256, 64, 64, 256),         # a share that is the whole: all, one pass
    (128, 4, 8, 128),           # half the experts: all, one pass
    (24, 2, 48, 24),            # fewer assignments than a row tile
])
def test_row_cap_is_twice_an_even_load_in_whole_tiles(assignments, held,
                                                      columns, cap):
    assert gm.row_cap(assignments, held, columns) == cap


def test_idle_lanes_are_not_counted_but_their_rows_are_moved():
    idx = jnp.asarray([[0, 1, 9], [1, 2, 10], [0, 3, 11], [7, 8, 9]],
                      jnp.int32)
    rows = jnp.asarray([True, True, False, False])
    c = {k: int(v) for k, v in gm.load_counters(
        idx, 12, rows=rows, first=0, held=2, routed=8).items()}
    assert c == {"moe.assignments": 6, "moe.zero_assignments": 2,
                 "moe.local_assignments": 3, "moe.max_expert_assignments": 2,
                 "moe.experts_touched": 2, "moe.rows_moved": 12,
                 "moe.layer_steps": 1}


# ------------------------------------------------------ the selection bias


def test_the_selection_bias_is_fit_to_an_even_choice_of_columns(weights):
    """On synthetic probabilities a drawn column bias leaves one column at
    twice its share; the fit brings every column within a twentieth of it,
    so the 4 zero-compute columns of 12 get a third of the picks. The
    program's layers and the reference's get the same fitted numbers."""
    rng = np.random.default_rng(21)
    logits = jnp.asarray(3.0 * rng.normal(size=(2048, 12))
                         + rng.normal(size=(12,)), jnp.float32)
    prob = jax.nn.softmax(logits, -1)

    def loads(bias):
        _, top = jax.lax.top_k(prob + bias, 3)
        return np.bincount(np.asarray(top).reshape(-1), minlength=12)

    assert loads(0.0).max() * 12 / loads(0.0).sum() - 1 > 0.5
    fit = loads(W.fit_selection_bias(prob, 3))
    assert fit.max() * 12 / fit.sum() - 1 < 0.05
    assert abs(fit[8:].sum() / fit.sum() - 1 / 3) < 0.01
    fitted = W.selection_biases(SEED, CFG, "float32")
    assert sorted(fitted) == [0, 1]
    halves = _build().serving_layers()
    for i, bias in fitted.items():
        assert np.array_equal(np.asarray(weights["layers"][i]["e_bias"]),
                              np.asarray(bias))
        assert np.array_equal(np.asarray(halves[2 * i].moe.e_bias._data),
                              np.asarray(bias))
        assert 0 < float(jnp.max(jnp.abs(bias))) < 0.2


def test_the_fit_gives_the_zero_experts_a_third_on_tokens_it_did_not_see(
        weights):
    """24 fresh sequences of 32 tokens through the reference's layers: the
    zero-compute columns get 0.33 +- 0.05 of the picks in every layer,
    and the busiest column stands under half over its share."""
    ids = np.random.default_rng(5).integers(0, CFG["vocab_size"], (24, 32))
    X = weights["embed"]["embed"][jnp.asarray(ids)].astype(jnp.float32)
    pos = jnp.arange(32)
    for p in weights["layers"]:
        p = ref._f32(p)
        X1, U = jax.vmap(lambda x: ref.router_input(x, p, CFG, pos))(X)
        _, biased = jax.vmap(lambda u: ref.biased_scores(u, p))(U)
        top = np.asarray(jax.lax.top_k(biased, 3)[1]).reshape(-1)
        load = np.bincount(top, minlength=12)
        assert abs(load[8:].sum() / load.sum() - 1 / 3) < 0.05
        assert load.max() * 12 / load.sum() - 1 < 0.5
        X = jax.vmap(lambda x1, u: ref.rest_of_layer(x1, u, p, CFG, pos))(
            X1, U)


def test_the_check_leaves_out_tokens_whose_routing_rounding_can_flip(
        capsys):
    """``served_token_gaps(route_margin=)``: a token is held to the
    reference only where no rounding under the margin gives it another
    held or zero-compute expert; the ladder line says what each margin
    keeps. For a share, two ABSENT experts changing places move nothing:
    a token whose edge lies between two of them has an infinite margin."""
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
    # whole layer: the margin IS the edge of the choice
    p = ref._f32(W.layer(SEED, 1, CFG, "float32"))
    x = jnp.asarray(rng.normal(size=(40, 64)), jnp.float32)
    _, biased = ref.biased_scores(x, p)
    top = jax.lax.top_k(biased, 4)[0]
    assert float(jnp.max(jnp.abs(ref.route_margin(x, p, CFG)
                                 - (top[:, 2] - top[:, 3])))) < 1e-7
    # a share of 2 experts without the zero part: wider, infinite where
    # neither a held expert is chosen nor one stands unchosen ... (all do
    # stand somewhere, so finite, but never under the whole layer's)
    cfg = _share(2, 2, zero_here=False)
    m = ref.route_margin(x, p, cfg)
    assert bool(jnp.all(m >= top[:, 2] - top[:, 3] - 1e-7))
    assert float(jnp.mean(m > top[:, 2] - top[:, 3] + 1e-7)) > 0.5
    # moving a held column by just under the margin changes nothing of
    # this share's choice; by just over it, the choice of some token
    held = jnp.arange(12)[None, :] == 2

    def held_chosen(b):
        return jnp.any(jax.lax.top_k(b, 3)[1] == 2, axis=-1)

    before = held_chosen(biased)
    sign = jnp.where(before, -1.0, 1.0)[:, None]   # towards the edge
    near = biased + held * sign * (m[:, None] * 0.99)
    assert bool(jnp.all(held_chosen(near) == before))


def test_the_control_rounds_the_held_experts_to_the_int8_grid():
    plain = _build().serving_layers()[0].moe
    grid = _build(cfg=dict(CFG, expert_weights="int8_grid")) \
        .serving_layers()[0].moe
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


# ------------------------------------------------------------ the engine


@pytest.mark.parametrize("kernel", [None, True], ids=["xla", "kernels"])
def test_engine_prefill_then_decode_matches_reference(weights, kernel):
    """Three requests of unequal lengths (5, 23 and 40 tokens: the decode
    step applies rotary at three different positions, one a lane) admitted
    and decoded together through FOUR latent pool entries for two layers:
    the logits of every token served are the reference's full forward
    pass's. ``kernels``: the latent decode kernel and the prefill flash
    kernel, interpreted."""
    spy = Spy()
    engine = ServingEngine(spy.model, config=ServingConfig(
        **ENGINE, paged_kernel=kernel))
    assert engine.decode_kernel is bool(kernel)
    rng = np.random.default_rng(0)
    prompts = [_prompt(rng, n) for n in (5, 23, 40)]
    before = dict(serving_metrics.stats())
    lanes = _serve(spy, engine, prompts, steps=6)
    assert _worst(weights, prompts, lanes) < TOL
    # the expert layers' counters came back with the tokens
    moved = {k: v - before.get(k, 0)
             for k, v in serving_metrics.stats().items()
             if k.startswith("moe.")}
    assert moved["moe.layer_steps"] == 6 * 2
    assert moved["moe.assignments"] == 6 * 2 * 3 * 3
    assert moved["moe.local_assignments"] + moved["moe.zero_assignments"] \
        == moved["moe.assignments"]            # the whole layer is here
    assert 0 < moved["moe.zero_assignments"] < moved["moe.assignments"]
    assert moved["moe.rows_moved"] == 6 * 2 * 9   # one pass of every row
    assert engine.decode_traces == 1
    assert serving_metrics.gauges()["kernel.grouped_matmul"] == 0
    # one row of 40 values a token a HALF-layer, and nothing else
    a = engine.arena
    assert [tuple(e[0].shape) for e in a.pools] == [
        (a.num_blocks, 4, 80)] * 4 and all(len(e) == 1 for e in a.pools)
    assert a.bytes_total() == 4 * a.num_blocks * 8 * 40 * 4
    a.check_invariants()


def test_a_share_is_served_and_matches_the_references_share():
    """The engine over a chip's share (experts 2..3 of 8, the zero-compute
    part here): prefill then decode give the logits of the reference given
    the same share, and the counters tell local from absent."""
    cfg = _share(2, 2, zero_here=True)
    weights = W.all_weights(SEED, cfg, "float32")
    spy = Spy(cfg=cfg)
    engine = ServingEngine(spy.model, config=ServingConfig(**ENGINE))
    rng = np.random.default_rng(3)
    prompts = [_prompt(rng, n) for n in (11, 30)]
    before = dict(serving_metrics.stats())
    lanes = _serve(spy, engine, prompts, steps=5)
    assert _worst(weights, prompts, lanes, cfg) < TOL
    moved = {k: v - before.get(k, 0)
             for k, v in serving_metrics.stats().items()
             if k.startswith("moe.")}
    assert moved["moe.assignments"] == 5 * 2 * 2 * 3
    assert moved["moe.local_assignments"] + moved["moe.zero_assignments"] \
        < moved["moe.assignments"]             # some picks are absent


def test_a_lane_that_restarts_reads_none_of_its_last_tenant(weights):
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


def test_served_behind_gateway_serve(weights):
    """``gateway.serve`` (the benchmark's front door, ``POST /v1/stream``
    through the load generator's own client) takes the model as it takes
    the other four."""
    import time

    from benchmark.harness.loadgen import Client
    from paddle_tpu.serving.gateway.gateway import serve

    gw = serve(_build(), replicas=1, port=0, guard=False,
               config=ServingConfig(**ENGINE))
    try:
        prompt = _prompt(np.random.default_rng(2), 9).tolist()
        rec = Client(f"http://127.0.0.1:{gw.port}", time.monotonic()).stream(
            {"id": 0, "due_s": None, "max_new_tokens": 4},
            json.dumps({"prompt": prompt, "max_new_tokens": 4}).encode())
        assert rec["state"] == "FINISHED" and len(rec["tokens"]) == 4
        full = ref.logits(weights, CFG, prompt + rec["tokens"][:-1])
        assert [int(t) for t in jnp.argmax(full[8:], -1)] == rec["tokens"]
    finally:
        gw.close()


# ------------------------------------------------------------ refusals


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


def test_weight_quantization_is_carried(weights):
    """``quant_weights`` (the cell's control) runs: both half-layers'
    attention and dense MLP matrices int8, the absorbed form reading the
    dequantized up-projection; its logits are near the reference's and not
    within ``TOL`` of them."""
    spy = Spy()
    engine = ServingEngine(spy.model, config=ServingConfig(
        **ENGINE, quant_weights=True))
    halves = spy.model.serving_layers()
    assert str(halves[1].attn.kv_b.weight._data.dtype) == "int8"
    assert str(halves[0].mlp.up.weight._data.dtype) == "int8"
    prompts = [_prompt(np.random.default_rng(8), 20)]
    worst = _worst(weights, prompts, _serve(spy, engine, prompts, steps=3))
    assert 10 * TOL < worst < 1.5
