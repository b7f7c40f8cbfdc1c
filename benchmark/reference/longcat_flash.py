"""Plain LongCat-Flash reference (the language model of LongCat-Flash-Omni):
the forward pass in ``jax.numpy``, float32, matmuls at
``jax.default_matmul_precision("highest")``. No kernel, no cache, no
absorbed attention, no sort: keys and values of every token are expanded
from the latent row, attention is plain masked softmax in blocks of query
rows (``lax.map``, so that a 7k-token request fits the chip), and the held
experts are applied through a dense 0/1 mask, one expert at a time
(``lax.scan`` over the stacked experts, so one expert's float32 copy exists
at a time). It imports nothing of ``paddle_tpu`` and takes nothing the
program made: its weights come from ``benchmark/weights/longcat_flash.py``
and the seed, the same values the program was filled with, upcast.

From the public ``config.json`` of ``meituan-longcat/LongCat-Flash-Omni``
(the language model's keys): hidden 6144, 28 layers, 64 heads of latent
attention (``q_lora_rank`` 1536, ``kv_lora_rank`` 512, ``qk_nope_head_dim``
128, ``qk_rope_head_dim`` 64, ``v_head_dim`` 128, ``mla_scale_q_lora`` and
``mla_scale_kv_lora`` true), plain rotary (``rope_theta`` 1e7), two dense
MLPs a layer of width ``ffn_hidden_size`` 12288, 512 routed experts of
width ``expert_ffn_hidden_size`` 2048 and ``zero_expert_num`` 256
zero-compute experts of ``zero_expert_type`` ``identity``, ``moe_topk`` 12,
``routed_scaling_factor`` 6, RMSNorm 1e-5, no biases, an untied head.

**The equations**, one layer (``N`` RMSNorm with a gain; sublayers ``a``,
``b``; ``x`` the residual stream)::

    x1 = x  + MLA_a(N1a(x))
    u  = N2a(x1)
    s  = MoE(u)                  # the shortcut: joins at the layer's end
    x2 = x1 + MLP_a(u)           # SwiGLU: W_down(SiLU(g) * v), [g | v] = W_up u
    x3 = x2 + MLA_b(N1b(x2))
    x4 = x3 + MLP_b(N2b(x3))
    out = x4 + s

* ``MLA``: ``c_q = N(h W_qa)``; with ``mla_scale_q_lora`` ``c_q`` is
  multiplied by ``sqrt(hidden / q_lora_rank)`` (2); ``[q_nope | q_rope] =
  c_q W_qb`` per head (128 + 64); ``[c | k_rope] = h W_kva`` (512 + 64); ``c
  = N(c)``: the row a cache would hold is ``[c | rotary(k_rope)]``; with
  ``mla_scale_kv_lora`` ``c`` is multiplied by ``sqrt(hidden /
  kv_lora_rank)`` (3.464) before ``W_kvb``; ``[k_nope | v] = c W_kvb`` per
  head (128 + 128); rotary on ``q_rope`` and on the ONE ``k_rope`` at the
  token's position; scores ``(q_nope . k_nope + q_rope . k_rope) /
  sqrt(192)``; causal softmax; ``W_o`` over the 64 values of 128.
* ``MoE``: logits ``u W_r`` in float32 over ``routed + zero`` columns (768),
  ``p = softmax(logits)``; the 12 largest of ``p + e_score_correction_bias``
  are chosen; the weights are the chosen ``p`` times
  ``routed_scaling_factor``, NOT renormalized; ``s = sum_i w_i E_i(u)`` with
  ``E_i`` a SwiGLU of width 2048 for a routed expert and ``E_i(u) = u`` for a
  zero-compute one. A share ``(first, held)`` of the routed experts gives
  the part of the sum that its experts give; the zero-compute part belongs
  to one share (it is computed where the token lives). What the absent
  experts would add is left out, and that partial ``s`` is what goes on.

**Assumed** (not keys of that config; the configuration file lists each):
``hidden_act`` silu; no bias on the router's logits (the selection bias is
added to the probabilities, for the choice only); no renormalization of the
chosen weights; rotary pairs are the consecutive values ``(2i, 2i+1)``, the
result de-interleaved (the family's public code, as DeepSeek-V3's); the
softmax scale ``1 / sqrt(192)`` with no further factor; RMSNorm computed in
float32 with the gain applied after; an untied head.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from benchmark.weights import longcat_flash as W

HI = jax.lax.Precision.HIGHEST
ROWS = 512  # query rows of one attention block


def _mm(a, b):
    return jnp.matmul(a, b, precision=HI)


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def swiglu(x, up, down):
    g, v = jnp.split(_mm(x, up), 2, axis=-1)
    return _mm(g * jax.nn.sigmoid(g) * v, down)


def rotary(x, positions, inv_freq):
    """``x`` ``[s, ..., rope]`` at ``positions`` ``[s]``: each consecutive
    pair ``(x[2i], x[2i+1])`` turned by ``positions * inv_freq[i]``; the
    result lies de-interleaved, queries and keys alike."""
    ang = positions.astype(jnp.float32)[:, None] * inv_freq[None, :]
    shape = (x.shape[0],) + (1,) * (x.ndim - 2) + (inv_freq.shape[0],)
    cos, sin = jnp.cos(ang).reshape(shape), jnp.sin(ang).reshape(shape)
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def inv_freq(cfg: dict):
    import numpy as np

    dim = int(cfg["qk_rope_head_dim"])
    return jnp.asarray(float(cfg["rope_theta"]) ** (
        -np.arange(0, dim, 2, dtype=np.float64) / dim), jnp.float32)


# --------------------------------------------------------------- attention


def attention(x, p, half: str, cfg: dict, positions):
    """``x`` ``[s, h]`` (already normed) at ``positions`` ``[s]`` ->
    ``[s, h]``, causal in the order of the rows. ``p`` holds the layer's
    leaves, ``half`` is ``"a"`` or ``"b"``."""
    sz = W.sizes(cfg)
    g = lambda name: p[f"{half}_{name}"]
    s, heads, hidden = x.shape[0], sz["num_attention_heads"], sz["hidden_size"]
    nope, rope, vd, kvr = (sz["qk_nope_head_dim"], sz["qk_rope_head_dim"],
                           sz["v_head_dim"], sz["kv_lora_rank"])
    eps = float(cfg["rms_norm_eps"])
    freq = inv_freq(cfg)
    c_q = rms_norm(_mm(x, g("q_a")), g("q_a_norm"), eps)
    if cfg.get("mla_scale_q_lora", True):
        c_q = c_q * math.sqrt(hidden / sz["q_lora_rank"])
    q = _mm(c_q, g("q_b")).reshape(s, heads, nope + rope)
    q_rope = rotary(q[..., nope:], positions, freq)
    row = _mm(x, g("kv_a"))
    c = rms_norm(row[:, :kvr], g("kv_a_norm"), eps)
    k_rope = rotary(row[:, kvr:], positions, freq)               # [s, rope]
    if cfg.get("mla_scale_kv_lora", True):
        c = c * math.sqrt(hidden / kvr)
    kv = _mm(c, g("kv_b")).reshape(s, heads, nope + vd)
    k_nope, v = kv[..., :nope], kv[..., nope:]
    scale = 1.0 / math.sqrt(nope + rope)
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
    return _mm(out.reshape(blocks * rows_n, heads * vd)[:s], g("o"))


# ------------------------------------------------------------------ experts


def biased_scores(u, p):
    """``(p, p + bias)``: the softmax probabilities ``[s, columns]`` and
    what the choice is made by."""
    prob = jax.nn.softmax(_mm(u, p["router"]), -1)
    return prob, prob + p["e_bias"]


def route(u, p, cfg: dict):
    """``u`` ``[s, h]`` -> the dense weights ``[s, columns]`` (0 where a
    column was not chosen): the chosen probabilities times the scaling
    factor, not renormalized."""
    prob, biased = biased_scores(u, p)
    _, top = jax.lax.top_k(biased, int(cfg["moe_topk"]))
    chosen = jnp.sum(jax.nn.one_hot(top, prob.shape[-1], dtype=prob.dtype),
                     axis=-2)
    return prob * chosen * float(cfg["routed_scaling_factor"])


def _relevant(cfg: dict):
    """``[columns]`` bool: the columns whose choice changes THIS share's
    output (its held experts and, where counted here, the zero-compute
    ones). Two absent experts changing places move nothing here."""
    sz = W.sizes(cfg)
    col = jnp.arange(sz["routed"] + sz["zero_expert_num"])
    held = (col >= sz["first"]) & (col < sz["first"] + sz["held"])
    if cfg.get("zero_experts_here", True):
        held = held | (col >= sz["routed"])
    return held


def route_margin(u, p, cfg: dict):
    """``[s]``: how far each token's choice stands from one that changes
    this share's output, in biased-probability units: the least of (a
    chosen relevant column over the first unchosen one) and (the last
    chosen column over an unchosen relevant one); infinite where neither
    exists. A program whose scores differ from these by less cannot give
    the token another held or zero-compute expert."""
    k = int(cfg["moe_topk"])
    _, biased = biased_scores(u, p)
    top, idx = jax.lax.top_k(biased, k + 1)
    rel = _relevant(cfg)
    chosen = jnp.sum(jax.nn.one_hot(idx[:, :k], biased.shape[-1],
                                    dtype=jnp.float32), axis=-2) > 0
    inf = jnp.inf
    low_in = jnp.min(jnp.where(chosen & rel, biased, inf), -1)
    high_out = jnp.max(jnp.where(~chosen & rel, biased, -inf), -1)
    return jnp.minimum(low_in - top[:, k], top[:, k - 1] - high_out)


def experts(u, p, cfg: dict):
    """The expert layer's result ``[s, h]`` for this configuration's share:
    the held experts' part and, with ``zero_experts_here``, the
    zero-compute experts' (``w u`` each)."""
    sz = W.sizes(cfg)
    w = route(u, p, cfg)
    held = slice(sz["first"], sz["first"] + sz["held"])

    def one(y, xs):
        up, down, we = xs
        return y + we[:, None] * swiglu(u, up.astype(jnp.float32),
                                        down.astype(jnp.float32)), None

    y, _ = jax.lax.scan(one, jnp.zeros_like(u),
                        (p["e_up"], p["e_down"], w.T[held]))
    if cfg.get("zero_experts_here", True):
        y = y + jnp.sum(w[:, sz["routed"]:], -1, keepdims=True) * u
    return y


# -------------------------------------------------------------------- model


def _f32(tree, skip=("e_up", "e_down")):
    """Upcast every leaf but the stacked experts, which :func:`experts`
    upcasts one at a time."""
    return {k: v if k in skip else v.astype(jnp.float32)
            for k, v in tree.items()}


def router_input(x, p, cfg: dict, positions):
    """``(x1, u)``: the stream after sublayer ``a``'s attention and what
    the router scores, ``N2a(x1)``."""
    eps = float(cfg["rms_norm_eps"])
    x1 = x + attention(rms_norm(x, p["a_attn_norm"], eps), p, "a", cfg,
                       positions)
    return x1, rms_norm(x1, p["a_mlp_norm"], eps)


def rest_of_layer(x1, u, p, cfg: dict, positions):
    eps = float(cfg["rms_norm_eps"])
    s = experts(u, p, cfg)
    x2 = x1 + swiglu(u, p["a_up"], p["a_down"])
    x3 = x2 + attention(rms_norm(x2, p["b_attn_norm"], eps), p, "b", cfg,
                        positions)
    x4 = x3 + swiglu(rms_norm(x3, p["b_mlp_norm"], eps), p["b_up"],
                     p["b_down"])
    return x4 + s


def block(x, p, cfg: dict, positions):
    """One decoder layer over one sequence's stream ``[s, h]``."""
    x1, u = router_input(x, p, cfg, positions)
    return rest_of_layer(x1, u, p, cfg, positions)


def _head(x, fin, eps: float):
    return _mm(rms_norm(x, fin["norm"], eps), fin["head"])


def logits(weights: dict, cfg: dict, ids):
    """Float32 logits [s, vocab] of one sequence of token ids [s] (small
    sizes: everything is held at once)."""
    with jax.default_matmul_precision("highest"):
        x = weights["embed"]["embed"][jnp.asarray(ids)].astype(jnp.float32)
        pos = jnp.arange(x.shape[0])
        for p in weights["layers"]:
            x = block(x, _f32(p), cfg, pos)
        return _head(x, _f32(weights["final"]), float(cfg["rms_norm_eps"]))


# ------------------------------------------------------------------ serving


def _frozen(cfg: dict):
    """The configuration's keys the equations read, hashable."""
    sz = W.sizes(cfg)
    keys = ["rms_norm_eps", "rope_theta", "routed_scaling_factor"]
    flags = ["mla_scale_q_lora", "mla_scale_kv_lora", "zero_experts_here"]
    return tuple(sorted(sz.items())) + tuple(
        (k, float(cfg[k])) for k in keys) + tuple(
        (k, bool(cfg.get(k, True))) for k in flags)


def _thawed(frozen):
    """A configuration the functions above read as they read the file's:
    ``n_routed_experts`` the experts held, the routed count under
    ``published``."""
    cfg = dict(frozen)
    cfg["published"] = {"n_routed_experts": cfg.pop("routed")}
    cfg["n_routed_experts"] = cfg.pop("held")
    cfg["expert_first"] = cfg.pop("first")
    return cfg


@functools.partial(jax.jit, static_argnames=("frozen",))
def _block_jit(x, p, frozen):
    """The layer's result and each token's :func:`route_margin` in it."""
    cfg = _thawed(frozen)
    with jax.default_matmul_precision("highest"):
        p = _f32(p)
        pos = jnp.arange(x.shape[0])
        x1, u = router_input(x, p, cfg, pos)
        return rest_of_layer(x1, u, p, cfg, pos), route_margin(u, p, cfg)


@functools.partial(jax.jit, static_argnames=("frozen",))
def calibration_layer(X, p, frozen):
    """``benchmark/weights/longcat_flash.py`` fits a layer's selection bias
    on the probabilities its router gives the stream that reaches it:
    several sequences' streams ``X`` ``[b, s, h]`` through one layer, the
    bias fit (and used) on the way. -> ``(X after the layer, the bias
    [columns])``."""
    cfg = _thawed(frozen)
    with jax.default_matmul_precision("highest"):
        p = _f32(p)
        pos = jnp.arange(X.shape[1])
        X1, U = jax.vmap(lambda x: router_input(x, p, cfg, pos))(X)
        prob = jax.vmap(lambda u: biased_scores(u, p)[0])(U)
        bias = W.fit_selection_bias(prob.reshape(-1, prob.shape[-1]),
                                    int(cfg["moe_topk"]))
        p = dict(p, e_bias=bias)
        return jax.vmap(lambda x1, u: rest_of_layer(x1, u, p, cfg, pos))(
            X1, U), bias


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
    layer's weights exist at a time; the sequence is padded at its END to
    a multiple of ``pad_to`` (every layer is causal, so the padding reaches
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
    x = W.embed(seed, cfg, dtype)["embed"][jnp.asarray(ids)].astype(
        jnp.float32)
    margin = jnp.full((padded,), jnp.inf, jnp.float32)
    for i in range(int(cfg["num_layers"])):
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


#: the margins :func:`served_token_gaps` prints its numbers at (biased
#: probability units: a twelfth chosen column holds some 0.01), so that a
#: run shows where the cell's ``route_margin`` stands
LADDER = (0.0, 1e-5, 3e-5, 1e-4, 3e-4, 1e-3, 3e-3, 1e-2)


def served_token_gaps(seed, cfg, dtype, prompt, served, route_margin=0.0,
                      **kw):
    """For every served token that the check can hold the program to: the
    reference's best logit at its position minus the reference's logit of
    the token that was served, in logit units (0 where the served token is
    the reference's first choice): the logits of what the timed path
    produced (prefill, then decode through the cache) against this full
    forward pass.

    Routing is discrete: where a token's choice stands within the program's
    rounding of one that gives it another held or zero-compute expert, a
    sound bf16 program parts from this float32 pass by far more than
    rounding. Which tokens stand so close is known HERE, from the float32
    scores alone, before the program's output is looked at: a token whose
    :func:`route_margin` is under ``route_margin`` (the cell's) in any
    layer is left out, and the rest are held as closely as a dense model's.
    One JSON line says what the numbers would be at each margin of
    :data:`LADDER`."""
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
