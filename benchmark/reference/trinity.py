"""Plain Trinity (``afmoe``) reference: the forward pass in ``jax.numpy``,
float32, matmuls at ``jax.default_matmul_precision("highest")``. No kernel,
no cache, no ring, no sort: attention is plain masked softmax in blocks of
query rows (``lax.map``, so that a 15k-token request fits the chip), the
window a mask, and the held experts are applied through a dense 0/1 mask,
one expert at a time (``lax.scan`` over the stacked experts, so one
expert's float32 copy exists at a time). It imports nothing of
``paddle_tpu`` and takes nothing the program made: its weights come from
``benchmark/weights/trinity.py`` and the seed, the same values the program
was filled with, upcast.

From the public ``config.json`` of ``arcee-ai/Trinity-Large-Preview``
(``model_type`` ``afmoe``): hidden 3072, 60 layers, 48 query heads over 8
K/V heads of ``head_dim`` 128, ``layer_types`` three ``sliding_attention``
then one ``full_attention`` (``sliding_window`` 4096), ``num_dense_layers``
6 leading SwiGLU layers of width ``intermediate_size`` 12288, then
``num_experts`` 256 routed experts of width ``moe_intermediate_size`` 3072,
``num_experts_per_tok`` 4, ``num_shared_experts`` 1, ``score_func``
sigmoid, ``route_norm`` true, ``route_scale`` 2.448, ``rope_theta`` 1e4
with no scaling, ``mup_enabled`` true, RMSNorm 1e-5, an untied head.

**The equations**, one layer (``N`` RMSNorm with a gain; ``x`` the residual
stream ``[T, hidden]``)::

    x0 = E[ids] * sqrt(hidden)                           (mup_enabled)
    h  = N_in(x)
    q  = N_q(h Wq as 48 x 128)   k = N_k(h Wk as 8 x 128)   v = h Wv as 8 x 128
    sliding layers only: q, k = rotary(q, k; position)
    a_t = sum_j softmax_j(q_t . k_j / sqrt(128)) v_j     over j <= t, and
          t - j < window on sliding layers; query head i reads K/V head i // 6
    a  = a * sigmoid(h Wg)                               Wg: hidden -> 48 x 128
    x1 = x + N_post_attn(a Wo)
    u  = N_pre_mlp(x1)
    m  = Wd(silu(Wg' u) * (Wu u))                        in the leading dense layers
    m  = SwiGLU_shared(u) + sum_{e in top4} w_e SwiGLU_e(u)   in the others, with
         s = sigmoid(u Wr) in float32, top4 = the 4 largest of s + b, w =
         s[top4] / sum(s[top4]) * route_scale
    y  = x1 + N_post_mlp(m)
    logits = N_final(x_L) W_head

A share ``(first, held)`` of the routed experts gives the part of the sum
that its experts give (the weights still normalized over all four chosen);
the shared expert belongs to one share (``shared_expert_here``). What the
absent experts would add is left out, and that partial ``m`` is what goes
on.

**Assumed** (not keys of that config; the configuration file lists each,
from ``described_as`` of the catalog and the family's public ``afmoe``
modelling code): the gate is ``sigmoid`` of a linear of the layer's normed
input, one gate a value of the attention's output, before ``Wo``; ``N_q``
and ``N_k`` are RMSNorms over the 128 values of a head, before rotary;
rotary in sliding layers ONLY, over all 128 values, value ``i`` paired with
value ``i + 64`` (rotate-half); full layers have no positions; the four
norms' places (sandwich: before and after each sublayer); ``sqrt(hidden)``
on the embedding; the selection bias is added for the choice only; no
group limit (``n_group`` = ``topk_group`` = 1); RMSNorm computed in float32
with the gain applied after.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from benchmark.weights import trinity as W

HI = jax.lax.Precision.HIGHEST
ROWS = 256  # query rows of one attention block


def _mm(a, b):
    return jnp.matmul(a, b, precision=HI)


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def swiglu(x, up, down):
    g, v = jnp.split(_mm(x, up), 2, axis=-1)
    return _mm(g * jax.nn.sigmoid(g) * v, down)


def inv_freq(cfg: dict):
    import numpy as np

    dim = int(cfg["head_dim"])
    return jnp.asarray(float(cfg["rope_theta"]) ** (
        -np.arange(0, dim, 2, dtype=np.float64) / dim), jnp.float32)


def rotary(x, positions, freq):
    """``x`` ``[s, heads, dim]`` at ``positions`` ``[s]``: the pair
    ``(x[i], x[i + dim / 2])`` turned by ``positions * freq[i]``."""
    ang = positions.astype(jnp.float32)[:, None, None] * freq
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def is_sliding(cfg: dict, index: int) -> bool:
    types = cfg.get("layer_types")
    if types is None:
        return (index + 1) % int(cfg["global_attn_every_n_layers"]) != 0
    return types[index] == "sliding_attention"


# --------------------------------------------------------------- attention


def gate(x, p):
    """``[s, heads * head_dim]``: one gate a value of the attention's
    output, from the layer's normed input."""
    return jax.nn.sigmoid(_mm(x, p["gate_proj"]))


def attention(x, p, cfg: dict, positions, sliding: bool):
    """``x`` ``[s, h]`` (already normed) at ``positions`` ``[s]`` ->
    ``[s, h]``: causal in the order of the rows, over the last
    ``sliding_window`` of them (the row's own counted) where ``sliding``."""
    sz = W.sizes(cfg)
    s, heads, kvh, d = (x.shape[0], sz["num_attention_heads"],
                        sz["num_key_value_heads"], sz["head_dim"])
    group, eps = heads // kvh, float(cfg["rms_norm_eps"])
    q = rms_norm(_mm(x, p["q_proj"]).reshape(s, heads, d), p["q_norm"], eps)
    k = rms_norm(_mm(x, p["k_proj"]).reshape(s, kvh, d), p["k_norm"], eps)
    v = _mm(x, p["v_proj"]).reshape(s, kvh, d)
    if sliding:
        freq = inv_freq(cfg)
        q, k = rotary(q, positions, freq), rotary(k, positions, freq)
    rows_n = min(ROWS, s)
    blocks = -(-s // rows_n)
    qb = jnp.pad(q, ((0, blocks * rows_n - s), (0, 0), (0, 0))).reshape(
        blocks, rows_n, kvh, group, d)
    cols = jnp.arange(s)

    def one(xs):
        q_b, r0 = xs
        rows = (r0 + jnp.arange(rows_n))[:, None]
        sc = jnp.einsum("rkgd,tkd->kgrt", q_b, k, precision=HI) \
            / math.sqrt(d)
        mask = cols[None, :] <= rows
        if sliding:
            mask &= rows - cols[None, :] < sz["sliding_window"]
        sc = jnp.where(mask[None, None], sc, -jnp.inf)
        return jnp.einsum("kgrt,tkd->rkgd", jax.nn.softmax(sc, -1), v,
                          precision=HI).reshape(rows_n, heads * d)

    a = jax.lax.map(one, (qb, jnp.arange(blocks) * rows_n))
    a = a.reshape(blocks * rows_n, heads * d)[:s]
    return _mm(a * gate(x, p), p["o_proj"])


# ------------------------------------------------------------------ experts


def biased_scores(u, p):
    """``(s, s + bias)``: the sigmoid scores ``[T, routed]`` and what the
    choice is made by."""
    score = jax.nn.sigmoid(_mm(u, p["router"]))
    return score, score + p["e_bias"]


def route(u, p, cfg: dict):
    """``u`` ``[T, h]`` -> the dense weights ``[T, routed]`` (0 where an
    expert was not chosen): the chosen scores, normalized to sum 1 where
    ``route_norm``, times ``route_scale``."""
    score, biased = biased_scores(u, p)
    _, top = jax.lax.top_k(biased, int(cfg["num_experts_per_tok"]))
    chosen = jnp.sum(jax.nn.one_hot(top, score.shape[-1], dtype=score.dtype),
                     axis=-2)
    w = score * chosen
    if cfg.get("route_norm", True):
        w = w / jnp.sum(w, -1, keepdims=True)
    return w * float(cfg["route_scale"])


def route_margin(u, p, cfg: dict):
    """``[T]``: how far each token's choice stands from one that changes
    THIS share's output, in biased-score units. A token with a held expert
    among its chosen: the last chosen over the first unchosen (any change
    of the chosen set moves its held expert's weight: the weights are
    normalized over the four). A token with none: the last chosen over the
    best unchosen HELD expert (two absent experts changing places move
    nothing here). A program whose scores differ from these by less cannot
    change what this share adds for the token."""
    sz = W.sizes(cfg)
    k = int(cfg["num_experts_per_tok"])
    _, biased = biased_scores(u, p)
    top, idx = jax.lax.top_k(biased, k + 1)
    col = jnp.arange(biased.shape[-1])
    held = (col >= sz["first"]) & (col < sz["first"] + sz["held"])
    any_held = jnp.any(held[idx[:, :k]], -1)
    chosen = jnp.sum(jax.nn.one_hot(idx[:, :k], biased.shape[-1],
                                    dtype=jnp.float32), axis=-2) > 0
    high_out = jnp.max(jnp.where(~chosen & held, biased, -jnp.inf), -1)
    return jnp.where(any_held, top[:, k - 1] - top[:, k],
                     top[:, k - 1] - high_out)


def experts(u, p, cfg: dict):
    """The expert layer's result ``[T, h]`` for this configuration's share:
    the held experts' part and, with ``shared_expert_here``, the shared
    expert's."""
    sz = W.sizes(cfg)
    w = route(u, p, cfg)
    held = slice(sz["first"], sz["first"] + sz["held"])

    def one(y, xs):
        up, down, we = xs
        return y + we[:, None] * swiglu(u, up.astype(jnp.float32),
                                        down.astype(jnp.float32)), None

    y, _ = jax.lax.scan(one, jnp.zeros_like(u),
                        (p["e_up"], p["e_down"], w.T[held]))
    if cfg.get("shared_expert_here", True):
        y = y + swiglu(u, p["s_up"], p["s_down"])
    return y


# -------------------------------------------------------------------- model


def _f32(tree, skip=("e_up", "e_down")):
    """Upcast every leaf but the stacked experts, which :func:`experts`
    upcasts one at a time."""
    return {k: v if k in skip else v.astype(jnp.float32)
            for k, v in tree.items()}


def embedded(table, ids, cfg: dict):
    """``x0``: the rows of the token table in float32, times
    ``sqrt(hidden)`` where ``mup_enabled``."""
    x = table[jnp.asarray(ids)].astype(jnp.float32)
    if cfg.get("mup_enabled", True):
        x = x * math.sqrt(int(cfg["hidden_size"]))
    return x


def router_input(x, p, cfg: dict, positions, index: int):
    """``(x1, u)``: the stream after the attention sublayer and what the
    MLP (and the router) reads, ``N_pre_mlp(x1)``."""
    eps = float(cfg["rms_norm_eps"])
    a = attention(rms_norm(x, p["input_norm"], eps), p, cfg, positions,
                  is_sliding(cfg, index))
    x1 = x + rms_norm(a, p["post_attn_norm"], eps)
    return x1, rms_norm(x1, p["pre_mlp_norm"], eps)


def rest_of_layer(x1, u, p, cfg: dict):
    m = swiglu(u, p["up"], p["down"]) if "up" in p else experts(u, p, cfg)
    return x1 + rms_norm(m, p["post_mlp_norm"], float(cfg["rms_norm_eps"]))


def block(x, p, cfg: dict, positions, index: int):
    """Decoder layer ``index`` over one sequence's stream ``[s, h]``."""
    x1, u = router_input(x, p, cfg, positions, index)
    return rest_of_layer(x1, u, p, cfg)


def _head(x, fin, eps: float):
    return _mm(rms_norm(x, fin["norm"], eps), fin["head"])


def logits(weights: dict, cfg: dict, ids):
    """Float32 logits [s, vocab] of one sequence of token ids [s] (small
    sizes: everything is held at once)."""
    with jax.default_matmul_precision("highest"):
        x = embedded(weights["embed"]["embed"], ids, cfg)
        pos = jnp.arange(x.shape[0])
        for i, p in enumerate(weights["layers"]):
            x = block(x, _f32(p), cfg, pos, i)
        return _head(x, _f32(weights["final"]), float(cfg["rms_norm_eps"]))


# ------------------------------------------------------------------ serving


def _frozen(cfg: dict):
    """The configuration's keys the equations read, hashable."""
    n = int(cfg["num_hidden_layers"])
    return tuple(sorted(W.sizes(cfg).items())) + (
        ("rms_norm_eps", float(cfg["rms_norm_eps"])),
        ("rope_theta", float(cfg["rope_theta"])),
        ("route_scale", float(cfg["route_scale"])),
        ("route_norm", bool(cfg.get("route_norm", True))),
        ("mup_enabled", bool(cfg.get("mup_enabled", True))),
        ("shared_expert_here", bool(cfg.get("shared_expert_here", True))),
        ("layer_types", tuple("sliding_attention" if is_sliding(cfg, i)
                              else "full_attention" for i in range(n))))


def _thawed(frozen):
    """A configuration the functions above read as they read the file's:
    ``num_experts`` the experts held, the routed count under
    ``published``."""
    cfg = dict(frozen)
    cfg["published"] = {"num_experts": cfg.pop("routed")}
    cfg["num_experts"] = cfg.pop("held")
    cfg["expert_first"] = cfg.pop("first")
    return cfg


@functools.partial(jax.jit, static_argnames=("index", "frozen"))
def _block_jit(x, p, index, frozen):
    """The layer's result and each token's :func:`route_margin` in it
    (infinite in a dense layer)."""
    cfg = _thawed(frozen)
    with jax.default_matmul_precision("highest"):
        p = _f32(p)
        x1, u = router_input(x, p, cfg, jnp.arange(x.shape[0]), index)
        margin = (route_margin(u, p, cfg) if "router" in p
                  else jnp.full((x.shape[0],), jnp.inf, jnp.float32))
        return rest_of_layer(x1, u, p, cfg), margin


@functools.partial(jax.jit, static_argnames=("index", "frozen"))
def calibration_layer(X, p, index, frozen):
    """``benchmark/weights/trinity.py`` fits an expert layer's selection
    bias on the scores its router gives the stream that reaches it: several
    sequences' streams ``X`` ``[b, s, h]`` through layer ``index``, the
    bias fit (and used) on the way. -> ``(X after the layer, the bias
    [routed], or None for a dense layer)``."""
    cfg = _thawed(frozen)
    with jax.default_matmul_precision("highest"):
        p = _f32(p)
        pos = jnp.arange(X.shape[1])
        X1, U = jax.vmap(lambda x: router_input(x, p, cfg, pos, index))(X)
        bias = None
        if "router" in p:
            score = jax.vmap(lambda u: biased_scores(u, p)[0])(U)
            bias = W.fit_selection_bias(
                score.reshape(-1, score.shape[-1]),
                int(cfg["num_experts_per_tok"]))
            p = dict(p, e_bias=bias)
        return jax.vmap(lambda x1, u: rest_of_layer(x1, u, p, cfg))(X1, U), \
            bias


@functools.partial(jax.jit, static_argnames=("eps", "cap"))
def _rows_logits(x, fin, start, eps, cap):
    rows = jax.lax.dynamic_slice_in_dim(x, start, cap, axis=0)
    with jax.default_matmul_precision("highest"):
        return _head(rows, _f32(fin), eps)


def teacher_forced(seed, cfg, dtype, prompt, served, pad_to=256, cap=512):
    """One pass over ``prompt + served`` (token lists): the float32 logits
    ``[len(served), vocab]`` of the positions that predict each served
    token, and ``[len(served)]`` the least :func:`route_margin` of that
    position over the expert layers, on the device. Layer by layer, so only
    one layer's weights exist at a time; the sequence is padded at its END
    to a multiple of ``pad_to`` (every layer is causal, so the padding
    reaches no row that is read) and at most ``cap`` rows are read, so few
    programs are compiled."""
    import numpy as np

    plen, n = len(prompt), len(served)
    if not 0 < n <= cap:
        raise ValueError(f"{n} served tokens; the check holds 1..{cap}")
    padded = -(-max(plen + n, cap + 1) // pad_to) * pad_to
    ids = np.zeros((padded,), np.int32)
    ids[:plen] = prompt
    ids[plen:plen + n] = served
    frozen = _frozen(cfg)
    x = embedded(W.embed(seed, cfg, dtype)["embed"], ids, cfg)
    margin = jnp.full((padded,), jnp.inf, jnp.float32)
    for i in range(int(cfg["num_hidden_layers"])):
        x, m = _block_jit(x, W.layer(seed, i, cfg, dtype), i, frozen)
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
#: score units: 256 sigmoid scores spread by 0.2), so that a run shows
#: where the cell's ``route_margin`` stands
LADDER = (0.0, 0.0005, 0.001, 0.002, 0.004, 0.008, 0.016, 0.032)


def served_token_gaps(seed, cfg, dtype, prompt, served, route_margin=0.0,
                      **kw):
    """For every served token that the check can hold the program to: the
    reference's best logit at its position minus the reference's logit of
    the token that was served, in logit units (0 where the served token is
    the reference's first choice): the logits of what the timed path
    produced (prefill, then decode through the rings and the pool) against
    this full forward pass.

    Routing is discrete: where a token's choice stands within the program's
    rounding of one that changes what this share adds for it, a sound bf16
    program parts from this float32 pass by far more than rounding. Which
    tokens stand so close is known HERE, from the float32 scores alone,
    before the program's output is looked at: a token whose
    :func:`route_margin` is under ``route_margin`` (the cell's) in any
    expert layer is left out, and the rest are held as closely as a dense
    model's. One JSON line says what the numbers would be at each margin of
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
