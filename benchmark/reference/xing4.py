"""Plain Xing4.0 reference: the forward pass in ``jax.numpy``, float32,
matmuls at ``jax.default_matmul_precision("highest")``. No kernel, no cache,
no absorbed attention, no sort: keys and values of every token are expanded
from the latent row, attention is plain masked softmax in blocks of query
rows (``lax.map``, so that a 10k-token request fits the chip), and every
token's experts are applied through a dense 0/1 mask, one expert at a time
(``lax.scan`` over the stacked experts, so one expert's float32 copy exists
at a time). It imports nothing of ``paddle_tpu`` and takes nothing the
program made: its weights come from ``benchmark/weights/xing4.py`` and the
seed, the same values the program was filled with, upcast.

From the model's public ``config.json`` (``model_type`` ``xing4_0``): hidden
3584, 32 heads of latent attention (``q_lora_rank`` 768, ``kv_lora_rank``
512, ``qk_nope_head_dim`` 128, ``qk_rope_head_dim`` 64, ``v_head_dim`` 128),
YaRN rotary (factor 64 over 4096, ``beta_fast`` 32, ``beta_slow`` 1,
``mscale`` = ``mscale_all_dim`` = 1, ``rope_theta`` 10000), dense layers of
width 9216 while the index is under ``first_k_dense_replace``, then 64 routed
experts of width 1024, 4 a token, sigmoid scores with a selection bias
(``noaux_tc``, ``n_group`` = ``topk_group`` = 1: no group limit),
``norm_topk_prob``, ``routed_scaling_factor`` 2, one shared expert; ``hc_mult``
4 residual streams mixed by matrices that ``hc_sinkhorn_iters`` 20 rounds make
doubly stochastic (``hc_eps`` 1e-6, clamp -30..30); RMSNorm 1e-6; an untied
head; one multi-token-prediction module.

**The equations**, one layer, streams ``X`` ``[n, h]`` a token (``n`` = 4):

* Hyper-connection around each sublayer ``F`` (attention, then MLP or
  experts): ``xf = RMSNorm(flatten(X))`` over the ``n h`` values (learned
  gain); ``z = xf W`` (``2n + n^2`` columns: pre, post, res row-major);
  ``H_pre = sigmoid(a_pre z_pre + b_pre)``, ``H_post = 2 sigmoid(a_post
  z_post + b_post)``, ``H_res = Sinkhorn(exp(clip(a_res z_res + b_res, -30,
  30)))``: 20 rounds of every row divided by (its sum + ``hc_eps``), then
  every column likewise; ``u = sum_j H_pre[j] X[j]``, ``y = F(RMSNorm(u))``,
  ``X'[i] = sum_j H_res[i, j] X[j] + H_post[i] y``. The embedding is repeated
  into the ``n`` streams; the streams are summed before the final norm.
* Latent attention: ``c_q = RMSNorm(x W_qa)``; ``[q_nope | q_rope] = c_q
  W_qb`` per head (128 + 64); ``[c_kv | k_rope] = x W_kva`` (512 + 64);
  ``c_kv = RMSNorm(c_kv)``; rotary on ``q_rope`` and on the ONE ``k_rope`` at
  the token's position; ``[k_nope | v] = c_kv W_kvb`` per head (128 + 128);
  scores ``(q_nope . k_nope + q_rope . k_rope) * mscale^2 / sqrt(192)``,
  ``mscale = 0.1 mscale_all_dim ln(factor) + 1``; causal softmax; ``W_o``.
* Experts: ``s = sigmoid(x W_g)``; the 4 largest of ``s + bias`` are chosen;
  weights ``s_i / sum(chosen s) * routed_scaling_factor``; ``y = sum_i w_i
  SwiGLU_i(x) + SwiGLU_shared(x)``, ``SwiGLU(x) = W_down(SiLU(g) * u)``, ``[g
  | u] = W_up x``. A share ``(first, count)`` of the experts gives the part
  of the sum that its experts give; the shared expert belongs to one share.
* Multi-token prediction (:func:`mtp_logits`): ``z = W_proj [RMSNorm(h) ;
  RMSNorm(Emb(t_{i+1}))]`` with ``h`` the summed streams after the last
  layer at position ``i``; ``z`` repeated into the streams, one expert
  decoder block as above at position ``i``, the streams summed, the shared
  final norm and head: the logits of token ``i + 2``.

**Assumed** (not keys of that config; the configuration file lists each):
the Sinkhorn order rows-then-columns and ``hc_eps`` added to each sum; the
norm over the flattened streams with a learned gain; the learned scalars
``a_*``; streams begin as copies of the embedding and end summed; RMSNorm
with a gain before each sublayer (on ``u``); rotary pairs are the
consecutive values ``(2i, 2i+1)`` (the family's public code de-interleaves
them first, which is the same rotation); the rotary's own amplitude factor
is ``mscale(mscale) / mscale(mscale_all_dim)`` = 1; the MTP block's position.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from benchmark.weights import xing4 as W

HI = jax.lax.Precision.HIGHEST
ROWS = 512  # query rows of one attention block


def _mm(a, b):
    return jnp.matmul(a, b, precision=HI)


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def silu(x):
    return x * jax.nn.sigmoid(x)


def swiglu(x, up, down):
    gu = _mm(x, up)
    g, u = jnp.split(gu, 2, axis=-1)
    return _mm(silu(g) * u, down)


# ------------------------------------------------------------------ rotary


def yarn_mscale(factor: float, m: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * m * math.log(factor) + 1.0


def yarn_inv_freq(cfg: dict):
    """The YaRN frequencies ``[rope / 2]`` (float32) from ``rope_scaling``
    and ``rope_theta``, and the rotary's amplitude factor."""
    import numpy as np

    rs, dim = cfg["rope_scaling"], int(cfg["qk_rope_head_dim"])
    base, factor = float(cfg["rope_theta"]), float(rs["factor"])
    orig = float(rs["original_max_position_embeddings"])
    pos_freq = base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    extra, inter = 1.0 / pos_freq, 1.0 / (factor * pos_freq)

    def corr(rot):
        return dim * math.log(orig / (rot * 2 * math.pi)) \
            / (2 * math.log(base))

    low = max(math.floor(corr(float(rs["beta_fast"]))), 0)
    high = min(math.ceil(corr(float(rs["beta_slow"]))), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0, 1)
    inv = inter * ramp + extra * (1 - ramp)
    amp = yarn_mscale(factor, float(rs["mscale"])) \
        / yarn_mscale(factor, float(rs["mscale_all_dim"]))
    return jnp.asarray(inv, jnp.float32), amp


def softmax_scale(cfg: dict) -> float:
    rs = cfg["rope_scaling"]
    m = yarn_mscale(float(rs["factor"]), float(rs["mscale_all_dim"]))
    return m * m / math.sqrt(int(cfg["qk_nope_head_dim"])
                             + int(cfg["qk_rope_head_dim"]))


def rotary(x, positions, inv_freq, amp):
    """``x`` ``[s, ..., rope]`` at ``positions`` ``[s]``: each consecutive
    pair ``(x[2i], x[2i+1])`` turned by ``positions * inv_freq[i]``; the
    result lies de-interleaved (first halves, then second halves), queries
    and keys alike."""
    ang = positions.astype(jnp.float32)[:, None] * inv_freq[None, :]
    shape = (x.shape[0],) + (1,) * (x.ndim - 2) + (inv_freq.shape[0],)
    cos, sin = (jnp.cos(ang) * amp).reshape(shape), \
        (jnp.sin(ang) * amp).reshape(shape)
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


# ------------------------------------------------------- hyper-connections


def sinkhorn(m, iters: int, eps: float):
    for _ in range(iters):
        m = m / (jnp.sum(m, -1, keepdims=True) + eps)
        m = m / (jnp.sum(m, -2, keepdims=True) + eps)
    return m


def hc_mixers(X, p, prefix: str, cfg: dict):
    """``X`` ``[s, n, h]`` -> ``(H_pre [s, n], H_post [s, n], H_res [s, n,
    n])``."""
    s, n, h = X.shape
    xf = rms_norm(X.reshape(s, n * h), p[prefix + "_norm"],
                  float(cfg["rms_norm_eps"]))
    z = _mm(xf, p[prefix + "_w"])
    a, b = p[prefix + "_a"], p[prefix + "_b"]
    pre = jax.nn.sigmoid(a[0] * z[:, :n] + b[:n])
    post = 2.0 * jax.nn.sigmoid(a[1] * z[:, n:2 * n] + b[n:2 * n])
    res = (a[2] * z[:, 2 * n:] + b[2 * n:]).reshape(s, n, n)
    res = jnp.exp(jnp.clip(res, float(cfg["mhc_h_res_clamp_min"]),
                           float(cfg["mhc_h_res_clamp_max"])))
    return pre, post, sinkhorn(res, int(cfg["hc_sinkhorn_iters"]),
                               float(cfg["hc_eps"]))


def hc_sublayer(X, p, prefix: str, norm: str, cfg: dict, fn):
    pre, post, res = hc_mixers(X, p, prefix, cfg)
    u = jnp.sum(pre[:, :, None] * X, axis=1)
    y = fn(rms_norm(u, p[norm], float(cfg["rms_norm_eps"])))
    return jnp.einsum("sij,sjh->sih", res, X, precision=HI) \
        + post[:, :, None] * y[:, None, :]


# --------------------------------------------------------------- attention


def attention(x, p, cfg: dict, positions):
    """``x`` ``[s, h]`` at ``positions`` ``[s]`` -> ``[s, h]``, causal in
    the order of the rows."""
    sz = W.sizes(cfg)
    s, heads = x.shape[0], sz["num_attention_heads"]
    nope, rope, vd, kvr = (sz["qk_nope_head_dim"], sz["qk_rope_head_dim"],
                           sz["v_head_dim"], sz["kv_lora_rank"])
    eps = float(cfg["rms_norm_eps"])
    inv_freq, amp = yarn_inv_freq(cfg)
    q = _mm(rms_norm(_mm(x, p["q_a"]), p["q_a_norm"], eps), p["q_b"])
    q = q.reshape(s, heads, nope + rope)
    q_rope = rotary(q[..., nope:], positions, inv_freq, amp)
    row = _mm(x, p["kv_a"])
    c_kv = rms_norm(row[:, :kvr], p["kv_a_norm"], eps)
    k_rope = rotary(row[:, kvr:], positions, inv_freq, amp)      # [s, rope]
    kv = _mm(c_kv, p["kv_b"]).reshape(s, heads, nope + vd)
    k_nope, v = kv[..., :nope], kv[..., nope:]
    scale = softmax_scale(cfg)
    rows_n = min(ROWS, s)
    blocks = -(-s // rows_n)
    pad = ((0, blocks * rows_n - s), (0, 0), (0, 0))
    qn = jnp.pad(q[..., :nope], pad).reshape(blocks, rows_n, heads, nope)
    qr = jnp.pad(q_rope, pad).reshape(blocks, rows_n, heads, rope)
    cols = jnp.arange(s)

    def one(xs):
        qn_b, qr_b, r0 = xs
        sc = (jnp.einsum("rhd,thd->hrt", qn_b, k_nope, precision=HI)
              + jnp.einsum("rhd,td->hrt", qr_b, k_rope, precision=HI)) * scale
        mask = cols[None, :] <= (r0 + jnp.arange(rows_n))[:, None]
        sc = jnp.where(mask[None], sc, -jnp.inf)
        return jnp.einsum("hrt,thd->rhd", jax.nn.softmax(sc, -1), v,
                          precision=HI).reshape(rows_n, heads * vd)

    out = jax.lax.map(one, (qn, qr, jnp.arange(blocks) * rows_n))
    return _mm(out.reshape(blocks * rows_n, heads * vd)[:s], p["o"])


# ------------------------------------------------------------------ experts


def biased_scores(x, p):
    """``(s, s + bias)``: the sigmoid scores ``[s, E]`` and what the choice
    is made by."""
    s = jax.nn.sigmoid(_mm(x, p["router"]))
    return s, s + p["e_bias"]


def route(x, p, cfg: dict):
    """``x`` ``[s, h]`` -> the dense weights ``[s, E]`` (0 where an expert
    was not chosen)."""
    k, e = int(cfg["num_experts_per_tok"]), int(cfg["n_routed_experts"])
    s, biased = biased_scores(x, p)
    _, top = jax.lax.top_k(biased, k)
    chosen = jnp.sum(jax.nn.one_hot(top, e, dtype=s.dtype), axis=-2)
    w = s * chosen
    if cfg.get("norm_topk_prob", True):
        w = w / jnp.sum(w, -1, keepdims=True)
    return w * float(cfg["routed_scaling_factor"])


def route_margin(x, p, cfg: dict):
    """``[s]``: how far each token's last chosen expert stands over its
    first unchosen one, in biased-score units. A program whose scores
    differ from these by more than that gives the token another expert."""
    k = int(cfg["num_experts_per_tok"])
    top, _ = jax.lax.top_k(biased_scores(x, p)[1], k + 1)
    return top[:, k - 1] - top[:, k]


def experts(x, p, cfg: dict, first: int = 0, count=None, shared=True):
    """The expert layer's result ``[s, h]``, or the part of it that the
    experts ``first .. first + count`` (and, with ``shared``, the shared
    expert) give."""
    w = route(x, p, cfg)
    count = w.shape[1] - first if count is None else count
    held = slice(first, first + count)

    def one(y, xs):
        up, down, we = xs
        return y + we[:, None] * swiglu(x, up.astype(jnp.float32),
                                        down.astype(jnp.float32)), None

    y, _ = jax.lax.scan(one, jnp.zeros_like(x),
                        (p["e_up"][held], p["e_down"][held], w.T[held]))
    if shared:
        y = y + swiglu(x, p["s_up"].astype(jnp.float32),
                       p["s_down"].astype(jnp.float32))
    return y


# -------------------------------------------------------------------- model


def _f32(tree, skip=("e_up", "e_down")):
    """Upcast every leaf but the stacked experts, which :func:`experts`
    upcasts one at a time."""
    return {k: v if k in skip else v.astype(jnp.float32)
            for k, v in tree.items()}


def attention_sublayer(X, p, cfg: dict, positions):
    return hc_sublayer(X, p, "hca", "attn_norm", cfg,
                       lambda x: attention(x, p, cfg, positions))


def router_input(X, p, cfg: dict):
    """What the second sublayer hands its MLP or experts, and so what the
    router scores: ``RMSNorm(H_pre X)`` ``[s, h]``."""
    pre, _, _ = hc_mixers(X, p, "hcm", cfg)
    return rms_norm(jnp.sum(pre[:, :, None] * X, axis=1), p["mlp_norm"],
                    float(cfg["rms_norm_eps"]))


def ffn_sublayer(X, p, kind: str, cfg: dict):
    if kind == W.DENSE:
        ffn = lambda x: swiglu(x, p["up"], p["down"])
    else:
        ffn = lambda x: experts(x, p, cfg)
    return hc_sublayer(X, p, "hcm", "mlp_norm", cfg, ffn)


def block(X, p, kind: str, cfg: dict, positions):
    """One decoder layer over one sequence's streams ``[s, n, h]``."""
    return ffn_sublayer(attention_sublayer(X, p, cfg, positions), p, kind,
                        cfg)


def _streams(table, ids, n: int):
    x = table[jnp.asarray(ids)].astype(jnp.float32)
    return jnp.repeat(x[:, None, :], n, axis=1)


def _head(X, fin, eps: float):
    return _mm(rms_norm(jnp.sum(X, axis=1), fin["norm"], eps), fin["head"])


def final_streams(weights: dict, cfg: dict, ids):
    with jax.default_matmul_precision("highest"):
        X = _streams(weights["embed"]["embed"], ids, int(cfg["hc_mult"]))
        pos = jnp.arange(X.shape[0])
        for i, p in enumerate(weights["layers"]):
            X = block(X, _f32(p), W.kind_of(cfg, i), cfg, pos)
        return X


def logits(weights: dict, cfg: dict, ids):
    """Float32 logits [s, vocab] of one sequence of token ids [s] (small
    sizes: everything is held at once)."""
    with jax.default_matmul_precision("highest"):
        return _head(final_streams(weights, cfg, ids), _f32(weights["final"]),
                     float(cfg["rms_norm_eps"]))


def mtp_logits(weights: dict, cfg: dict, ids):
    """The multi-token-prediction module's logits ``[s - 1, vocab]``: row
    ``i`` predicts token ``i + 2`` from the model's summed streams at
    position ``i`` and the embedding of token ``i + 1``."""
    eps = float(cfg["rms_norm_eps"])
    with jax.default_matmul_precision("highest"):
        p = _f32(weights["mtp"])
        h = jnp.sum(final_streams(weights, cfg, ids), axis=1)[:-1]
        e = weights["embed"]["embed"][jnp.asarray(ids)[1:]]
        z = _mm(jnp.concatenate([rms_norm(h, p["hnorm"], eps),
                                 rms_norm(e.astype(jnp.float32), p["enorm"],
                                          eps)], -1), p["proj"])
        X = jnp.repeat(z[:, None, :], int(cfg["hc_mult"]), axis=1)
        X = block(X, p, W.EXPERT, cfg, jnp.arange(X.shape[0]))
        return _head(X, _f32(weights["final"]), eps)


# ------------------------------------------------------------------ serving


def _frozen(cfg: dict):
    """The configuration's keys the equations read, hashable."""
    keys = list(W.sizes(cfg)) + [
        "rms_norm_eps", "rope_theta", "routed_scaling_factor",
        "norm_topk_prob", "hc_sinkhorn_iters", "hc_eps",
        "mhc_h_res_clamp_min", "mhc_h_res_clamp_max"]
    return tuple(sorted((k, cfg[k]) for k in keys)) + (
        ("rope_scaling", tuple(sorted(
            (k, v) for k, v in cfg["rope_scaling"].items()
            if not isinstance(v, str)))),)


def _thawed(frozen):
    cfg = dict(frozen)
    cfg["rope_scaling"] = dict(cfg["rope_scaling"])
    return cfg


@functools.partial(jax.jit, static_argnames=("kind", "frozen"))
def _block_jit(X, p, kind, frozen):
    """The layer's result and each token's :func:`route_margin` in it
    (infinite in a dense layer, which chooses nothing)."""
    cfg = _thawed(frozen)
    with jax.default_matmul_precision("highest"):
        p = _f32(p)
        X = attention_sublayer(X, p, cfg, jnp.arange(X.shape[0]))
        margin = jnp.full((X.shape[0],), jnp.inf, jnp.float32) \
            if kind == W.DENSE else route_margin(router_input(X, p, cfg), p,
                                                 cfg)
        return ffn_sublayer(X, p, kind, cfg), margin


@functools.partial(jax.jit, static_argnames=("kind", "frozen"))
def calibration_layer(X, p, kind, frozen):
    """``benchmark/weights/xing4.py`` fits an expert layer's selection
    bias on the scores its router gives the streams that enter it: several
    sequences' streams ``X`` ``[b, s, n, h]`` through one layer, the bias
    fit (and used) on the way. -> ``(X after the layer, the bias [E] or
    None in a dense layer)``."""
    cfg = _thawed(frozen)
    with jax.default_matmul_precision("highest"):
        p, bias = _f32(p), None
        pos = jnp.arange(X.shape[1])
        X = jax.vmap(lambda x: attention_sublayer(x, p, cfg, pos))(X)
        if kind != W.DENSE:
            scores = jax.vmap(lambda x: biased_scores(
                router_input(x, p, cfg), p)[0])(X)
            bias = W.fit_selection_bias(
                scores.reshape(-1, scores.shape[-1]),
                int(cfg["num_experts_per_tok"]))
            p = dict(p, e_bias=bias)
        return jax.vmap(lambda x: ffn_sublayer(x, p, kind, cfg))(X), bias


@functools.partial(jax.jit, static_argnames=("eps", "cap"))
def _rows_logits(X, fin, start, eps, cap):
    rows = jax.lax.dynamic_slice_in_dim(X, start, cap, axis=0)
    with jax.default_matmul_precision("highest"):
        return _head(rows, _f32(fin), eps)


def teacher_forced(seed, cfg, dtype, prompt, served, pad_to=256, cap=512):
    """One pass over ``prompt + served`` (token lists): the float32 logits
    ``[len(served), vocab]`` of the positions that predict each served
    token, and ``[len(served)]`` the least :func:`route_margin` of that
    position over the expert layers, on the device. Layer by layer, so
    only one layer's weights exist at a time; the sequence is padded at
    its END to a multiple of ``pad_to`` (every layer is causal, so the
    padding reaches no row that is read) and at most ``cap`` rows are read,
    so few programs are compiled."""
    import numpy as np

    plen, n = len(prompt), len(served)
    if not 0 < n <= cap:
        raise ValueError(f"{n} served tokens; the check holds 1..{cap}")
    padded = -(-max(plen + n, cap + 1) // pad_to) * pad_to
    ids = np.zeros((padded,), np.int32)
    ids[:plen] = prompt
    ids[plen:plen + n] = served
    frozen = _frozen(cfg)
    X = _streams(W.embed(seed, cfg, dtype)["embed"], ids, int(cfg["hc_mult"]))
    margin = jnp.full((padded,), jnp.inf, jnp.float32)
    for i in range(int(cfg["num_hidden_layers"])):
        X, m = _block_jit(X, W.layer(seed, i, cfg, dtype), W.kind_of(cfg, i),
                          frozen)
        margin = jnp.minimum(margin, m)
    start = min(plen - 1, padded - cap)
    off = plen - 1 - start
    out = _rows_logits(X, W.final(seed, cfg, dtype), start,
                       float(cfg["rms_norm_eps"]), cap)
    return out[off:off + n], margin[plen - 1:plen - 1 + n]


def teacher_forced_logits(seed, cfg, dtype, prompt, served, **kw):
    return teacher_forced(seed, cfg, dtype, prompt, served, **kw)[0]


def _gap_of(ref_logits, tokens):
    picked = jnp.take_along_axis(ref_logits, tokens[:, None], -1)[:, 0]
    return jnp.max(ref_logits, -1) - picked


#: the margins :func:`served_token_gaps` prints its numbers at, so that a
#: run shows where the cell's ``route_margin`` stands
LADDER = (0.0, 2.5e-4, 5e-4, 1e-3, 2e-3, 4e-3, 8e-3, 1.6e-2)


def served_token_gaps(seed, cfg, dtype, prompt, served, route_margin=0.0,
                      **kw):
    """For every served token that the check can hold the program to: the
    reference's best logit at its position minus the reference's logit of
    the token that was served, in logit units (0 where the served token is
    the reference's first choice).

    Routing is discrete: where a token's last chosen expert stands over its
    first unchosen one by less than the program's rounding moves a score,
    a sound bf16 program gives the token another expert than this float32
    pass does, and its logits part by far more than rounding. Which tokens
    stand so close is known HERE, from the float32 scores alone, before the
    program's output is looked at: a token whose :func:`route_margin` is
    under ``route_margin`` (the cell's, in biased-score units) in any
    expert layer is left out, and the rest, whose experts no sound program
    can change, are held as closely as a dense model's. One JSON line says
    what the numbers would be at each margin of :data:`LADDER`."""
    import json

    import numpy as np

    ref, margin = teacher_forced(seed, cfg, dtype, prompt, served, **kw)
    gaps = np.asarray(_gap_of(ref, jnp.asarray(served, jnp.int32)))
    margin = np.asarray(margin)
    rows = []
    for m in LADDER:
        kept = gaps[margin >= m]
        rows.append([m, int(kept.size),
                     float(kept.mean()) if kept.size else None,
                     float(kept.max()) if kept.size else None,
                     int((kept > 0).sum())])
    print(json.dumps({"route_margin_ladder": rows, "columns": [
        "margin", "tokens_kept", "gap_mean", "gap_max", "not_first"]}),
        flush=True)
    return gaps[margin >= route_margin].tolist()
