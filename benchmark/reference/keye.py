"""Plain reference of Keye-VL-2.0's language model (``model_type``
``KeyeVL2``): the forward pass in ``jax.numpy``, float32, matmuls at
``jax.default_matmul_precision("highest")``. No kernel, no cache, no
gather: attention is plain masked softmax in blocks of query rows
(``lax.map``, so that 30,720 positions fit the chip), the selection a mask
made from each row's own index scores, and the held experts are applied
through a dense 0/1 mask, one expert at a time (``lax.scan``). It imports
nothing of ``paddle_tpu`` and takes nothing the program made (never its
selection, never its routing): its weights come from
``benchmark/weights/keye.py`` and the seed, the same values the program was
filled with, upcast.

From the public ``config.json`` of ``Kwai-Keye/Keye-VL-2.0-30B-A3B``: hidden
2048, 48 layers all alike, 32 query heads over 4 K/V heads of ``head_dim``
128, ``rope_theta`` 1e7 with ``mrope_section`` [16, 24, 24], ``sa_config``:
an indexer of 16 heads of 64 over 1 key head, ``topk`` 2048; 128 routed
experts of width 768, 8 a token, ``norm_topk_prob``, no shared expert;
RMSNorm 1e-6; no biases; an untied head.

**The equations**, one layer (``N`` RMSNorm with a gain; ``x`` the residual
stream ``[T, hidden]``; ``t`` a query's position, ``s <= t`` a key's)::

    h   = N_in(x)
    q   = rot_t(N_128(h Wq))  [32, 128]    k = rot_s(N_128(h Wk))  [4, 128]    v = h Wv  [4, 128]
    qI  = rot_t(h WqI)        [16, 64]     kI = rot_s(LN(h WkI))   [64]        w = h Ww  [16]
    I[t, s] = (16 * 64)^-1/2 * sum_j w[t, j] * relu(qI[t, j] . kI[s])           for s <= t
    S_t     = the 2,048 positions s <= t of largest I[t, s]  (all of them while t < 2,048)
    a[t, n] = sum_{s in S_t} softmax_{s in S_t}(q[t, n] . k[s, n // 8] / sqrt(128)) v[s, n // 8]
    x1  = x + concat_n(a[t, n]) Wo
    u   = N_post(x1);  p = softmax(u Wr) over 128;  E_t = the 8 largest;  g = p[E_t] / sum p[E_t]
    y   = x1 + sum_{e in E_t, e held here} g_e Wdown_e (silu(u Wgate_e) * (u Wup_e))
    logits = N_final(x_L) W_head

One selection ``S_t`` serves all 32 query heads and all 4 K/V heads of the
layer; every layer has its own indexer. A share ``(first, held)`` of the
routed experts gives the part of the sum that its experts give (the
weights still normalized over all eight chosen); what the absent experts
would add is left out, and that partial result is what goes on.

**Departures, and what is assumed** (not keys of that config; the
configuration file lists each with its provenance): ``S_t`` is made as
``{s <= t : I[t, s] >= the 2,048th largest of row t}``, which is the same
set unless two float32 scores tie exactly at the edge; the indexer's
queries, key and head weights are linear in the layer's normed input (no
query latent); LayerNorm with gain and bias (eps as the RMSNorms') on the
index key; rotate-half rotary at theta 1e7 over all 64 values of each
index query and of the index key; the scale ``(16 * 64)^-1/2``;
``q_chunk_size`` / ``kv_chunk_size`` tile the score computation and change
no value; ``N_128`` RMSNorm with a gain over a head's 128 values, before
rotary, on q and k; rotate-half pairing; one position id a token, so the
three ``mrope_section`` axes coincide and the rotary is plain; softmax over
all 128 columns before the choice; no selection bias; RMSNorm computed in
float32 with the gain applied after.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from benchmark.weights import keye as W

HI = jax.lax.Precision.HIGHEST
ROWS = 128  # query rows of one attention block


def _mm(a, b):
    return jnp.matmul(a, b, precision=HI)


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def layer_norm(x, w, b, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * w + b


def swiglu(x, up, down):
    g, v = jnp.split(_mm(x, up), 2, axis=-1)
    return _mm(g * jax.nn.sigmoid(g) * v, down)


def inv_freq(theta: float, dim: int):
    import numpy as np

    return jnp.asarray(float(theta) ** (
        -np.arange(0, dim, 2, dtype=np.float64) / dim), jnp.float32)


def rotary(x, positions, freq):
    """``x`` ``[s, heads, dim]`` at ``positions`` ``[s]``: the pair
    ``(x[i], x[i + dim / 2])`` turned by ``positions * freq[i]``."""
    ang = positions.astype(jnp.float32)[:, None, None] * freq
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


# --------------------------------------------------------------- attention


def index_scale(cfg: dict) -> float:
    sz = W.sizes(cfg)
    return float(sz["index_heads"] * sz["index_dim"]) ** -0.5


def index_inputs(x, p, cfg: dict, positions):
    """``(qI [s, 16, 64], kI [s, 64], w [s, 16])`` of the normed input
    ``x`` ``[s, h]``: what the indexer brings a token (``w`` without the
    scale)."""
    sz = W.sizes(cfg)
    s, hi, di = x.shape[0], sz["index_heads"], sz["index_dim"]
    freq = inv_freq(cfg["rope_theta"], di)
    qi = rotary(_mm(x, p["iq_proj"]).reshape(s, hi, di), positions, freq)
    ki = layer_norm(_mm(x, p["ik_proj"]), p["ik_norm"], p["ik_bias"],
                    float(cfg["rms_norm_eps"]))
    ki = rotary(ki[:, None], positions, freq)[:, 0]
    return qi, ki, _mm(x, p["iw"])


def index_scores(qi, ki, w, cfg: dict):
    """``I`` ``[rows, s]`` of index queries ``qi`` ``[rows, 16, 64]`` with
    head weights ``w`` ``[rows, 16]`` against the keys ``ki`` ``[s, 64]``
    (no mask)."""
    sc = jnp.einsum("rjd,td->rjt", qi, ki, precision=HI)
    return index_scale(cfg) * jnp.sum(w[:, :, None] * jax.nn.relu(sc), 1)


def kept(I, rows, topk: int):
    """``[rows, s]`` bool: ``S_t`` of each row of ``I`` at positions
    ``rows`` ``[r, 1]``: under the diagonal, the ``topk`` largest (every
    one while there are no more)."""
    cols = jnp.arange(I.shape[1])[None, :]
    causal = cols <= rows
    if I.shape[1] <= topk:
        return causal
    I = jnp.where(causal, I, -jnp.inf)
    edge = jax.lax.top_k(I, topk)[0][:, -1:]
    return causal & (I >= edge)


def attention(x, p, cfg: dict, positions):
    """``x`` ``[s, h]`` (already normed) at ``positions`` ``[s]`` ->
    ``[s, h]``: every query over the keys its own index scores keep."""
    sz = W.sizes(cfg)
    s, heads, kvh, d = (x.shape[0], sz["num_attention_heads"],
                        sz["num_key_value_heads"], sz["head_dim"])
    group, eps = heads // kvh, float(cfg["rms_norm_eps"])
    freq = inv_freq(cfg["rope_theta"], d)
    q = rms_norm(_mm(x, p["q_proj"]).reshape(s, heads, d), p["q_norm"], eps)
    k = rms_norm(_mm(x, p["k_proj"]).reshape(s, kvh, d), p["k_norm"], eps)
    q, k = rotary(q, positions, freq), rotary(k, positions, freq)
    v = _mm(x, p["v_proj"]).reshape(s, kvh, d)
    qi, ki, w = index_inputs(x, p, cfg, positions)
    rows_n = min(ROWS, s)
    blocks = -(-s // rows_n)
    pad = lambda a: jnp.pad(a, ((0, blocks * rows_n - s),)
                            + ((0, 0),) * (a.ndim - 1))
    qb = pad(q).reshape(blocks, rows_n, kvh, group, d)
    qib = pad(qi).reshape((blocks, rows_n) + qi.shape[1:])
    wb = pad(w).reshape(blocks, rows_n, -1)

    def one(xs):
        q_b, qi_b, w_b, r0 = xs
        rows = (r0 + jnp.arange(rows_n))[:, None]
        keep = kept(index_scores(qi_b, ki, w_b, cfg), rows, sz["topk"])
        sc = jnp.einsum("rkgd,tkd->kgrt", q_b, k, precision=HI) \
            / math.sqrt(d)
        sc = jnp.where(keep[None, None], sc, -jnp.inf)
        return jnp.einsum("kgrt,tkd->rkgd", jax.nn.softmax(sc, -1), v,
                          precision=HI).reshape(rows_n, heads * d)

    a = jax.lax.map(one, (qb, qib, wb, jnp.arange(blocks) * rows_n))
    return _mm(a.reshape(blocks * rows_n, heads * d)[:s], p["o_proj"])


# ------------------------------------------------------------------ experts


def probabilities(u, p):
    """The router's softmax ``[T, routed]``: what the choice is made by."""
    return jax.nn.softmax(_mm(u, p["router"]), -1)


def route(u, p, cfg: dict):
    """``u`` ``[T, h]`` -> the dense weights ``[T, routed]`` (0 where an
    expert was not chosen): the chosen probabilities, normalized to sum 1
    where ``norm_topk_prob``."""
    prob = probabilities(u, p)
    _, top = jax.lax.top_k(prob, int(cfg["num_experts_per_tok"]))
    chosen = jnp.sum(jax.nn.one_hot(top, prob.shape[-1], dtype=prob.dtype),
                     axis=-2)
    w = prob * chosen
    if cfg.get("norm_topk_prob", True):
        w = w / jnp.sum(w, -1, keepdims=True)
    return w


def route_margin(u, p, cfg: dict):
    """``[T]``: how far each token's choice stands from one that changes
    THIS share's output, in probability units (``reference/trinity.py``
    says why two cases): a token with a held expert among its chosen, the
    last chosen over the first unchosen; a token with none, the last
    chosen over the best unchosen HELD expert."""
    sz = W.sizes(cfg)
    k = int(cfg["num_experts_per_tok"])
    prob = probabilities(u, p)
    top, idx = jax.lax.top_k(prob, k + 1)
    col = jnp.arange(prob.shape[-1])
    held = (col >= sz["first"]) & (col < sz["first"] + sz["held"])
    any_held = jnp.any(held[idx[:, :k]], -1)
    chosen = jnp.sum(jax.nn.one_hot(idx[:, :k], prob.shape[-1],
                                    dtype=jnp.float32), axis=-2) > 0
    high_out = jnp.max(jnp.where(~chosen & held, prob, -jnp.inf), -1)
    return jnp.where(any_held, top[:, k - 1] - top[:, k],
                     top[:, k - 1] - high_out)


def experts(u, p, cfg: dict):
    """The expert layer's result ``[T, h]`` for this configuration's
    share: the held experts' part."""
    sz = W.sizes(cfg)
    w = route(u, p, cfg)
    held = slice(sz["first"], sz["first"] + sz["held"])

    def one(y, xs):
        up, down, we = xs
        return y + we[:, None] * swiglu(u, up.astype(jnp.float32),
                                        down.astype(jnp.float32)), None

    y, _ = jax.lax.scan(one, jnp.zeros_like(u),
                        (p["e_up"], p["e_down"], w.T[held]))
    return y


# -------------------------------------------------------------------- model


def _f32(tree, skip=("e_up", "e_down")):
    """Upcast every leaf but the stacked experts, which :func:`experts`
    upcasts one at a time."""
    return {k: v if k in skip else v.astype(jnp.float32)
            for k, v in tree.items()}


def embedded(table, ids):
    return table[jnp.asarray(ids)].astype(jnp.float32)


def router_input(x, p, cfg: dict, positions):
    """``(x1, u)``: the stream after the attention sublayer and what the
    experts (and the router) read, ``N_post(x1)``."""
    eps = float(cfg["rms_norm_eps"])
    x1 = x + attention(rms_norm(x, p["input_norm"], eps), p, cfg, positions)
    return x1, rms_norm(x1, p["post_attn_norm"], eps)


def block(x, p, cfg: dict, positions):
    """One decoder layer over one sequence's stream ``[s, h]``."""
    x1, u = router_input(x, p, cfg, positions)
    return x1 + experts(u, p, cfg)


def _head(x, fin, eps: float):
    return _mm(rms_norm(x, fin["norm"], eps), fin["head"])


def logits(weights: dict, cfg: dict, ids):
    """Float32 logits [s, vocab] of one sequence of token ids [s] (small
    sizes: everything is held at once)."""
    with jax.default_matmul_precision("highest"):
        x = embedded(weights["embed"]["embed"], ids)
        pos = jnp.arange(x.shape[0])
        for p in weights["layers"]:
            x = block(x, _f32(p), cfg, pos)
        return _head(x, _f32(weights["final"]), float(cfg["rms_norm_eps"]))


# ------------------------------------------------------------------ serving


def _frozen(cfg: dict):
    """The configuration's keys the equations read, hashable."""
    return tuple(sorted(W.sizes(cfg).items())) + (
        ("rms_norm_eps", float(cfg["rms_norm_eps"])),
        ("rope_theta", float(cfg["rope_theta"])),
        ("norm_topk_prob", bool(cfg.get("norm_topk_prob", True))))


def _thawed(frozen):
    """A configuration the functions above read as they read the file's:
    ``num_experts`` the experts held, the routed count under
    ``published``."""
    cfg = dict(frozen)
    cfg["published"] = {"num_experts": cfg.pop("routed")}
    cfg["num_experts"] = cfg.pop("held")
    cfg["expert_first"] = cfg.pop("first")
    cfg["sa_config"] = {"indexer_head_dim": cfg.pop("index_dim"),
                        "indexer_num_heads": cfg.pop("index_heads"),
                        "topk": cfg.pop("topk")}
    return cfg


@functools.partial(jax.jit, static_argnames=("frozen",))
def _block_jit(x, p, frozen):
    """The layer's result and each token's :func:`route_margin` in it."""
    cfg = _thawed(frozen)
    with jax.default_matmul_precision("highest"):
        p = _f32(p)
        x1, u = router_input(x, p, cfg, jnp.arange(x.shape[0]))
        return x1 + experts(u, p, cfg), route_margin(u, p, cfg)


@functools.partial(jax.jit, static_argnames=("eps", "cap"))
def _rows_logits(x, fin, start, eps, cap):
    rows = jax.lax.dynamic_slice_in_dim(x, start, cap, axis=0)
    with jax.default_matmul_precision("highest"):
        return _head(rows, _f32(fin), eps)


def teacher_forced(seed, cfg, dtype, prompt, served, pad_to=256, cap=512):
    """One pass over ``prompt + served`` (token lists): the float32 logits
    ``[len(served), vocab]`` of the positions that predict each served
    token, and ``[len(served)]`` the least :func:`route_margin` of that
    position over the layers, on the device. Layer by layer, so only one
    layer's weights exist at a time; the sequence is padded at its END to a
    multiple of ``pad_to`` (every layer is causal, so the padding reaches
    no row that is read) and at most ``cap`` rows are read, so few programs
    are compiled."""
    import numpy as np

    plen, n = len(prompt), len(served)
    if not 0 < n <= cap:
        raise ValueError(f"{n} served tokens; the check holds 1..{cap}")
    padded = -(-max(plen + n, cap + 1) // pad_to) * pad_to
    ids = np.zeros((padded,), np.int32)
    ids[:plen] = prompt
    ids[plen:plen + n] = served
    frozen = _frozen(cfg)
    x = embedded(W.embed(seed, cfg, dtype)["embed"], ids)
    margin = jnp.full((padded,), jnp.inf, jnp.float32)
    for i in range(int(cfg["num_hidden_layers"])):
        x, m = _block_jit(x, W.layer(seed, i, cfg, dtype), frozen)
        margin = jnp.minimum(margin, m)
    start = min(plen - 1, padded - cap)
    off = plen - 1 - start
    out = _rows_logits(x, W.final(seed, cfg, dtype), start,
                       float(cfg["rms_norm_eps"]), cap)
    return out[off:off + n], margin[plen - 1:plen - 1 + n]


def teacher_forced_logits(seed, cfg, dtype, prompt, served, **kw):
    return teacher_forced(seed, cfg, dtype, prompt, served, **kw)[0]


def _gap_of(ref_logits, tokens):
    picked = jnp.take_along_axis(ref_logits, tokens[:, None], -1)[:, 0]
    return jnp.max(ref_logits, -1) - picked


#: the margins :func:`served_token_gaps` prints its numbers at (probability
#: units: the 8 chosen of 128 hold most of the mass, a chosen weight some
#: 0.1), so that a run shows where the cell's ``route_margin`` stands
LADDER = (0.0, 0.0001, 0.0003, 0.001, 0.002, 0.004, 0.008, 0.016)


def served_token_gaps(seed, cfg, dtype, prompt, served, route_margin=0.0,
                      **kw):
    """For every served token that the check can hold the program to: the
    reference's best logit at its position minus the reference's logit of
    the token that was served, in logit units (0 where the served token is
    the reference's first choice): the logits of what the timed path
    produced (prefill, then decode through the three pools) against this
    full forward pass, which makes its own selection and its own routing.

    Routing is discrete (``reference/trinity.py`` says what that does to a
    sound bf16 program): a token whose :func:`route_margin` is under
    ``route_margin`` (the cell's) in any layer is left out, known HERE from
    the float32 probabilities alone. The selection is discrete too, and has
    no such margin to leave tokens out by: among thousands of candidates
    the 2,048th and 2,049th scores always lie close, a sound bf16 program
    keeps a few other tokens than this pass does at EVERY position, and
    what that moves is inside the cell's limits (the cell's file says how
    it was measured). One JSON line says what the numbers would be at each
    margin of :data:`LADDER`."""
    import json

    import numpy as np

    ref, margin = teacher_forced(seed, cfg, dtype, prompt, served, **kw)
    gaps = np.asarray(_gap_of(ref, jnp.asarray(served, jnp.int32)))
    margin = np.asarray(margin)
    rows = []
    for m in LADDER:
        kept_ = gaps[margin >= m]
        rows.append([m, int(kept_.size),
                     float(kept_.mean()) if kept_.size else None,
                     float(kept_.max()) if kept_.size else None,
                     int((kept_ > 0).sum())])
    print(json.dumps({"route_margin_ladder": rows, "columns": [
        "margin", "tokens_kept", "gap_mean", "gap_max", "not_first"]}),
        flush=True)
    return gaps[margin >= route_margin].tolist()
