"""Keye-VL-2.0's language model (grouped-query attention that reads only
the ``topk`` tokens a learned indexer picks, over K/V pools with an index
key pool beside them; softmax-routed experts of which a share is held) on
the normal serving path, at the tiny preset: the model and the engine
against the plain reference (``benchmark/reference/keye.py``), LOGITS and
not tokens, and the kept SETS against the reference's; the three kernels
of ``ops/sparse_attention.py`` (their bodies in the Pallas interpreter)
against the ``jax.numpy`` forms.

Tolerances. Program and reference both compute in float32 here (conftest
pins full matmul precision), so they differ by summation order alone: the
largest difference seen is 4e-6 on logits whose standard deviation is 1.0.
``TOL`` = 1e-4 leaves 25 times that and is far under what each breakage of
``test_tolerance_fails_what_is_wrong`` moves the logits by (each is held to
more than ten times ``TOL``), the bfloat16-for-float32 variant among them.
Both discrete choices could part program and reference by far more than
``TOL`` were two scores within rounding of each other at an edge (a
token's 8th and 9th index score, its 2nd and 3rd expert); on these seeds
none is: ``test_kept_sets_are_the_references`` holds every query's set of
every layer to the reference's, exactly."""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.models import keye as K
from paddle_tpu.models.serving_seam import SparseKVLayerState
from paddle_tpu.ops import sparse_attention as sa
from paddle_tpu.serving import ServingConfig, ServingEngine
from paddle_tpu.serving import cache_views as E
from paddle_tpu.serving import metrics as serving_metrics

from benchmark.hooks import keye as hook
from benchmark.reference import keye as ref
from benchmark.weights import keye as W

SEED = 7
CFG = {
    "vocab_size": 512, "hidden_size": 64, "moe_intermediate_size": 32,
    "num_hidden_layers": 3, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "num_experts": 8,
    "num_experts_per_tok": 2, "norm_topk_prob": True, "rms_norm_eps": 1e-6,
    "rope_theta": 1e7, "max_position_embeddings": 256,
    "sa_config": {"indexer_head_dim": 8, "indexer_num_heads": 8,
                  "indexer_num_kv_heads": 1, "kv_chunk_size": 512,
                  "q_chunk_size": 512, "topk": 8},
}
ENGINE = dict(num_slots=3, kv_block_size=8, max_model_len=256)
TOL, KTOL = 1e-4, 2e-5


def _share(first, held):
    """The configuration of one share of ``CFG``'s 8 routed experts, as a
    configuration file states it."""
    return dict(CFG, num_experts=held, expert_first=first,
                published={"num_experts": 8})


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
    model = K.KeyeForCausalLM(K.keye_tiny())
    spec = model.serving_spec()
    assert spec.layers == (SparseKVLayerState(4, 16, 2, 8, 8, 8),) * 3
    assert spec.layers[0].kind == "sparse" and spec.prefill_tail is None
    assert E.pool_row(spec.layers) == (2, 16, 0, 8)
    sites = [s for s, _ in model.serving_linears()]
    assert sites[:6] == [f"0.attn.{n}" for n in (
        "q_proj", "k_proj", "v_proj", "o_proj", "iq_proj", "ik_proj")]
    assert len(sites) == 3 * 6
    full = K.KeyeConfig()
    assert (full.index_heads, full.index_dim, full.topk) == (16, 64, 2048)
    with pytest.raises(ValueError, match="one index key head"):
        K.keye_tiny(sa_config={"indexer_num_kv_heads": 2})
    with pytest.raises(ValueError, match="a range of those routed"):
        K.keye_tiny(expert_first=6, expert_count=4)


@pytest.mark.parametrize("n", [5, 40], ids=["under_topk", "past_topk"])
def test_model_forward_matches_reference(weights, n):
    """``forward(ids)`` without a cache: the reference's logits, over a
    sequence in which every token is kept (5 under a ``topk`` of 8) and
    over one five times ``topk``."""
    ids = _prompt(np.random.default_rng(0), n)
    got = _build()(paddle.to_tensor(ids[None]))._data[0]
    want = ref.logits(weights, CFG, ids)
    assert got.shape == want.shape == (n, CFG["vocab_size"])
    assert float(jnp.max(jnp.abs(got - want))) < TOL


def test_teacher_forced_pass_reads_the_same_rows(weights):
    ids = [int(t) for t in _prompt(np.random.default_rng(1), 90)]
    full = ref.logits(weights, CFG, ids)
    rows, margin = ref.teacher_forced(SEED, CFG, "float32", ids[:60],
                                      ids[60:], pad_to=32, cap=64)
    assert float(jnp.max(jnp.abs(rows - full[59:89]))) < TOL
    assert margin.shape == (30,) and float(jnp.min(margin)) >= 0.0


@pytest.mark.parametrize("wrong", [
    "bfloat16_for_float32", "softmax_scale_of_the_index_head",
    "relu_left_out",
    "selection_shared_across_layers", "every_token_kept",
    "index_key_not_normed", "index_unrotated", "qk_norm_left_out",
    "weights_not_normalized", "topk_one_short"])
def test_tolerance_fails_what_is_wrong(weights, wrong, monkeypatch):
    """Each of these must move the logits by far more than ``TOL``: the
    program in bfloat16 where float32 is stated; a wrong scale (a positive
    factor on the index scores moves no set, so the wrong one is the
    softmax's: the index head's width taken for the head's); the index
    scores without their ``relu``; the
    first layer's selection used by every layer; no selection at all; the
    index key without its LayerNorm, the indexer without rotary; queries
    and keys not normed; the chosen experts' probabilities not
    renormalized; 7 tokens kept for 8. (All but the first are made in the
    reference: the distance is the same.)"""
    ids = _prompt(np.random.default_rng(2), 60)
    cfg, dtype = dict(CFG), "float32"
    if wrong == "bfloat16_for_float32":
        dtype = "bfloat16"
    elif wrong == "softmax_scale_of_the_index_head":
        from types import SimpleNamespace
        monkeypatch.setattr(ref, "math", SimpleNamespace(
            sqrt=lambda d: 8.0 ** 0.5))
    elif wrong == "relu_left_out":
        monkeypatch.setattr(ref.jax.nn, "relu", lambda x: x)
    elif wrong == "selection_shared_across_layers":
        first, inputs = [], ref.index_inputs

        def shared(x, p, c, pos):
            if not first:
                first.append(inputs(x, p, c, pos))
            return first[0]
        monkeypatch.setattr(ref, "index_inputs", shared)
    elif wrong == "every_token_kept":
        cfg["sa_config"] = dict(CFG["sa_config"], topk=1000)
    elif wrong == "index_key_not_normed":
        monkeypatch.setattr(ref, "layer_norm", lambda x, w, b, eps: x)
    elif wrong == "index_unrotated":
        rotary = ref.rotary
        monkeypatch.setattr(ref, "rotary", lambda x, pos, freq: (
            x if x.shape[-1] == 8 else rotary(x, pos, freq)))
    elif wrong == "qk_norm_left_out":
        norm = ref.rms_norm
        monkeypatch.setattr(ref, "rms_norm", lambda x, w, eps: (
            x if x.ndim == 3 else norm(x, w, eps)))
    elif wrong == "weights_not_normalized":
        cfg["norm_topk_prob"] = False
    elif wrong == "topk_one_short":
        cfg["sa_config"] = dict(CFG["sa_config"], topk=7)
    got = _build(dtype)(paddle.to_tensor(ids[None]))._data[0]
    want = ref.logits(weights, cfg, ids)
    assert float(jnp.max(jnp.abs(got.astype(jnp.float32) - want))) > 10 * TOL


# --------------------------------------------------- the selection itself


def _layer_inputs(weights, ids, index):
    """The normed input of layer ``index`` and its positions, by the
    reference."""
    with jax.default_matmul_precision("highest"):
        x = ref.embedded(weights["embed"]["embed"], ids)
        pos = jnp.arange(x.shape[0])
        for p in weights["layers"][:index]:
            x = ref.block(x, ref._f32(p), CFG, pos)
        p = ref._f32(weights["layers"][index])
        return ref.rms_norm(x, p["input_norm"], 1e-6), p, pos


@pytest.mark.parametrize("kernel", [False, True], ids=["xla", "kernels"])
def test_kept_sets_are_the_references(weights, kernel):
    """Every query's kept set, in every layer, at float32: what the
    program's prefill keeps (a threshold a row and ``score >= threshold``)
    and what its decode step keeps (``select_topk``'s positions) are the
    reference's ``S_t``; a context shorter than ``topk`` keeps every
    token."""
    ids = _prompt(np.random.default_rng(4), 40)
    model = _build()
    for index, layer in enumerate(model.serving_layers()):
        h, p, pos = _layer_inputs(weights, ids, index)
        qi_r, ki_r, w_r = ref.index_inputs(h, p, CFG, pos)
        want = np.asarray(ref.kept(ref.index_scores(qi_r, ki_r, w_r, CFG),
                                   pos[:, None], 8))
        # (two heads' relu leaves exact zeros: a row whose edge is a zero
        # keeps the zeros that tie with it, here and in the reference)
        assert all(n >= min(t + 1, 8) for t, n in enumerate(want.sum(1)))
        assert (want.sum(1) == 8).sum() > 20 and want[:8].sum() == 36
        qi, ki, w = layer.attn.index_inputs(paddle.to_tensor(h[None]),
                                            h[None], pos[None])
        qi, ki, w = qi[0], ki[0], w[0]
        tau = sa.index_thresholds(qi, ki, w, 8, kernel=kernel, block_q=8,
                                  block_k=16, chunk=16)
        # (the mask is made here by another program than the threshold
        # was: a rounding's room under the edge keeps the edge itself in)
        got = np.asarray(sa.keep_mask(qi, ki, w, tau - 1e-5))
        assert (got == want).all()
        # the decode step's: the last query over a paged index pool
        pool = jnp.zeros((7, 4, 16), jnp.float32).at[1:6].set(
            ki.reshape(5, 4, 16))
        table = jnp.asarray([[1, 2, 3, 4, 5, 0]], jnp.int32)
        scores = sa.paged_index_scores(qi[-1:], w[-1:], pool, table,
                                       jnp.asarray([39]), kernel=kernel,
                                       pages=2)
        idx, live = sa.select_topk(scores, 8)
        assert bool(live.all())
        picked = set(np.asarray(idx[0]).tolist())
        assert len(picked) == 8
        assert picked <= set(np.nonzero(want[-1])[0].tolist())


@pytest.mark.parametrize("k", [1, 8, 64, 300])
def test_the_threshold_is_the_sorts_kth_largest_exactly(k):
    """``kth_largest`` (eight rounds of four bits over the ordered-integer
    view of the scores, no sort) against ``jnp.sort``: exact, with ties
    (every fifth score an exact zero), rows that are all or partly
    ``NEG_INF`` and infinities; and ``select_topk``'s positions are a
    top-``k`` set of ``lax.top_k``'s values."""
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((7, 300)), jnp.float32)
    x = x.at[:, ::5].set(0.0).at[2, 100:].set(-1e30).at[3].set(-1e30)
    x = x.at[4, :5].set(jnp.inf)
    want = jnp.sort(x, -1)[:, -k]
    assert np.array_equal(np.asarray(sa.kth_largest(x, k)), np.asarray(want))
    idx, live = sa.select_topk(x, k)
    top = np.asarray(jax.lax.top_k(x, k)[0])
    for r in range(7):
        got = sorted(np.asarray(x[r])[np.asarray(idx[r])[
            np.asarray(live[r])]].tolist(), reverse=True)
        assert got == [t for t in top[r].tolist() if t > -5e29]
    assert int(live[3].sum()) == 0 and int(live[2].sum()) == min(k, 100)


def _toy(p, heads=4, kv=2, d=16, hi=2, di=8, seed=0):
    rng = np.random.default_rng(seed)
    f = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)
    return (f(p, heads, d), f(p, kv, d), f(p, kv, d), f(p, hi, di), f(p, di),
            f(p, hi))


@pytest.mark.parametrize("p, bq, bk, chunk", [
    (40, 8, 16, 16), (37, 8, 8, 24), (64, 16, 32, 64), (6, None, None, None)])
def test_prefill_kernels_are_the_plain_forms(p, bq, bk, chunk):
    """``index_scores`` + ``sparse_prefill_flash`` (interpreted) against the
    ``jax.numpy`` route: the same thresholds bit for bit where both make
    them, the same attention to ``KTOL``; prompts that are no whole number
    of tiles, tiles wider than query tiles, a prompt under ``topk``."""
    q, k, v, qi, ki, w = _toy(p)
    kw = dict(block_q=bq, block_k=bk, chunk=chunk)
    tau_x = sa.index_thresholds(qi, ki, w, 8, chunk=chunk)
    tau_k = sa.index_thresholds(qi, ki, w, 8, kernel=True, **kw)
    assert np.allclose(tau_x, tau_k, rtol=1e-6, atol=1e-6)
    want = sa.sparse_prefill_attention(q, k, v, qi, ki, w, tau_x,
                                       chunk=chunk)
    got = sa.sparse_prefill_attention(q, k, v, qi, ki, w, tau_k,
                                      kernel=True, **kw)
    assert got.shape == want.shape == (p, 4, 16)
    assert float(jnp.max(jnp.abs(got - want))) < KTOL
    # and the plain form is attention over the reference's kept sets
    keep = np.asarray(sa.keep_mask(qi, ki, w, tau_x - 1e-5))
    assert all(n >= min(t + 1, 8) for t, n in enumerate(keep.sum(1)))
    assert not np.triu(keep, 1).any()


def test_paged_scores_kernel_reads_the_live_pages_alone():
    """Three lanes of unequal lengths, one not active, tables that share
    no block: the kernel's scores are the gather's, ``NEG_INF`` past each
    lane's position; the blocks of no lane's table are poisoned with NaN
    and nothing of it comes out."""
    rng = np.random.default_rng(3)
    pool = jnp.asarray(rng.standard_normal((12, 4, 16)), jnp.float32)
    tables = jnp.asarray([[1, 2, 3, 0], [4, 5, 0, 0], [6, 7, 8, 9]],
                         jnp.int32)
    pos = jnp.asarray([17, 9, 30], jnp.int32)
    active = jnp.asarray([True, True, False])
    qi = jnp.asarray(rng.standard_normal((3, 2, 8)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((3, 2)), jnp.float32)
    want = sa.paged_index_scores(qi, w, pool, tables, pos, active)
    poisoned = pool.at[jnp.asarray([0, 10, 11])].set(jnp.nan)
    got = sa.paged_index_scores(qi, w, poisoned, tables, pos, active,
                                kernel=True, pages=2)
    assert got.shape == want.shape == (3, 32)
    assert np.allclose(got, want, rtol=1e-5, atol=1e-5)
    assert (np.asarray(got[0, 18:]) < -1e29).all()
    assert (np.asarray(got[2]) < -1e29).all()


# ------------------------------------------------- through the engine


@pytest.mark.parametrize("kernel", [None, True], ids=["xla", "kernels"])
def test_engine_prefill_then_decode_matches_reference(weights, kernel):
    """Three requests of unequal lengths (5, 23 and 40 tokens: one starts
    under ``topk`` = 8 and grows past it, two start past it; the decode
    step applies rotary, scores and selects at three different positions,
    one a lane) admitted and decoded together through three pools a layer
    under one block table: the logits of every token served are the
    reference's full forward pass's, which makes its own selection.
    ``kernels``: the two prefill kernels and the paged index-score
    kernel, interpreted."""
    spy = Spy()
    engine = ServingEngine(spy.model, config=ServingConfig(
        **ENGINE, paged_kernel=kernel))
    assert engine.decode_kernel is bool(kernel)
    rng = np.random.default_rng(0)
    prompts = [_prompt(rng, n) for n in (5, 23, 40)]
    before = dict(serving_metrics.stats())
    lanes = _serve(spy, engine, prompts, steps=12)
    assert _worst(weights, prompts, lanes) < TOL
    moved = _moved(before, "moe.")
    assert moved["moe.layer_steps"] == 12 * 3
    assert moved["moe.assignments"] == 12 * 3 * 3 * 2
    moved = _moved(before, "sparse.")
    assert moved["sparse.layer_steps"] == 12 * 3
    # the view counts the rows it hands the attention: min(live, topk) a
    # lane, so the request that starts at 5 tokens reads 6, 7, then 8
    assert moved["sparse.rows_read"] == 3 * sum(
        min(n + i + 1, 8) for n in (5, 23, 40) for i in range(12))
    # step i writes position len + i: that many + 1 rows are live
    live = sum(n + i + 1 for n in (5, 23, 40) for i in range(12))
    assert moved["sparse.rows_live"] == 3 * live
    assert moved["sparse.index_rows_scored"] == 3 * live
    assert _moved(before, "prefill.")["prefill.block_writes"] == 3 * 3 * 3
    assert engine.decode_traces == 1
    g = serving_metrics.gauges()
    assert g["kernel.paged_index"] == int(bool(kernel))
    # K, V and index keys a layer: [blocks, 8, 2, 16] twice, [blocks, 4, 16]
    a = engine.arena
    assert len(a.pools) == 3 and len(a.pools[0]) == 3
    assert a.pools[0][2].shape[1:] == (4, 16)
    assert g["arena.index_bytes"] == 3 * a.pools[0][2].size * 4
    a.check_invariants()


def test_the_shares_add_up_to_the_uncut_layer(weights):
    """The expert layer's result from each of 8 chips' shares (one expert
    each), all given the SAME input, adds up to the uncut layer's: by the
    reference and by the program's layer."""
    rng = np.random.default_rng(5)
    u = jnp.asarray(rng.standard_normal((24, 64)), jnp.float32)
    p = ref._f32(weights["layers"][1])
    with jax.default_matmul_precision("highest"):
        whole = ref.experts(u, p, CFG)
        parts, got = 0.0, 0.0
        for first in range(8):
            cfg = _share(first, 1)
            share = W.layer(SEED, 1, cfg, "float32")
            assert jnp.array_equal(share["e_up"][0], p["e_up"][first])
            parts = parts + ref.experts(u, ref._f32(share), cfg)
            moe = _build(cfg=cfg).serving_layers()[1].mlp
            got = got + moe(paddle.to_tensor(u[None]), None, u[None])[0]
    assert float(jnp.max(jnp.abs(parts - whole))) < TOL
    assert float(jnp.max(jnp.abs(got - whole))) < TOL
    assert float(jnp.max(jnp.abs(whole))) > 0.1


def test_a_share_is_served_and_matches_the_references_share():
    """The engine over a chip's share (experts 2..3 of 8): prefill then
    decode give the logits of the reference given the same share, and the
    counters tell local from absent."""
    cfg = _share(2, 2)
    weights = W.all_weights(SEED, cfg, "float32")
    spy = Spy(cfg=cfg)
    engine = ServingEngine(spy.model, config=ServingConfig(**ENGINE))
    rng = np.random.default_rng(3)
    prompts = [_prompt(rng, n) for n in (11, 30)]
    before = dict(serving_metrics.stats())
    lanes = _serve(spy, engine, prompts, steps=5)
    assert _worst(weights, prompts, lanes, cfg) < TOL
    moved = _moved(before, "moe.")
    assert moved["moe.assignments"] == 5 * 3 * 2 * 2
    assert 0 < moved["moe.local_assignments"] < moved["moe.assignments"]


def test_a_lane_taken_by_a_new_tenant_reads_none_of_the_last(weights):
    """Lanes retired and admitted to again: a request's index keys, like
    its K and V, lie in blocks of its own table, and a shorter tenant's
    scores never reach what the last one wrote."""
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


def test_route_margin_tells_a_held_edge_from_an_absent_one(weights):
    """The check's ``route_margin``: a token with a held expert among its
    two, the second probability over the third; a token with none, the
    second over the best held one it did not choose."""
    rng = np.random.default_rng(9)
    u = jnp.asarray(rng.standard_normal((64, 64)), jnp.float32)
    p = ref._f32(weights["layers"][0])
    cfg = _share(2, 2)
    with jax.default_matmul_precision("highest"):
        prob = np.asarray(ref.probabilities(u, p))
        got = np.asarray(ref.route_margin(u, p, cfg))
    order = np.argsort(-prob, -1)
    for t in range(64):
        top = order[t, :2]
        if any(2 <= e < 4 for e in top):
            want = prob[t, order[t, 1]] - prob[t, order[t, 2]]
        else:
            want = prob[t, order[t, 1]] - prob[t, 2:4].max()
        assert abs(got[t] - want) < 1e-6
    assert np.all(got >= 0)


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
    the other six, on the kernel route the cell asks for."""
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
    ("quant_kv", dict(quant_kv=True)),
])
def test_options_a_sparse_model_cannot_honour_are_refused_by_name(option,
                                                                  kw):
    with pytest.raises(ValueError, match=option) as err:
        ServingEngine(_build(), config=ServingConfig(**ENGINE, **kw))
    assert "sparse-attention layers" in str(err.value)


def test_a_mesh_and_the_handoff_are_refused_by_name():
    kind = E.KINDS["sparse"]
    assert "mesh (more than one chip)" in kind.refuses
    assert E.HANDOFF in kind.refuses
    with pytest.raises(ValueError, match="mesh"):
        E.refuse_options(K.KeyeForCausalLM(K.keye_tiny()).serving_spec()
                         .layers, {"mesh (more than one chip)": True})


def test_the_control_is_carried(weights):
    """``quant_weights`` and the experts on the int8 grid (the cell's
    control; the kind has no int8 K/V) run: the attention's four matrices
    and the indexer's two int8, the head weights, the router and the
    stacked experts as they were; the logits are near the reference's and
    not within ``TOL`` of them."""
    cfg = dict(CFG, expert_weights="int8_grid")
    spy = Spy(cfg=cfg)
    engine = ServingEngine(spy.model, config=ServingConfig(
        **ENGINE, quant_weights=True))
    layers = spy.model.serving_layers()
    assert str(layers[1].attn.iq_proj.weight._data.dtype) == "int8"
    assert str(layers[0].attn.o_proj.weight._data.dtype) == "int8"
    assert str(layers[2].attn.iw._data.dtype) == "float32"
    assert str(layers[2].mlp.e_up._data.dtype) == "float32"
    drawn = W.layer(SEED, 2, CFG, "float32")["e_up"]
    assert not jnp.array_equal(layers[2].mlp.e_up._data, drawn)
    assert len(engine.arena.pools[0]) == 3
    prompts = [_prompt(np.random.default_rng(8), 20)]
    worst = _worst(weights, prompts, _serve(spy, engine, prompts, steps=3))
    assert 10 * TOL < worst < 1.5
