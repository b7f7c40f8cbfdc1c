"""Plain Olmo-Hybrid reference: the forward pass in ``jax.numpy``, float32,
matmuls at ``jax.default_matmul_precision("highest")``, the recurrence one
token at a time (``lax.scan`` over ``t``). No chunks, no cache, no kernel, no
batching trick. It imports nothing of ``paddle_tpu`` and takes nothing the
program made: its weights come from ``benchmark/weights/olmo_hybrid.py`` and
the seed, the same values the program was filled with, upcast. (The repo's
tier-1 tests keep their own copy of these equations,
``tests/refs/olmo_hybrid_ref.py``; a test holds the two equal.)

The equations. ``x_t`` is a block's input at position ``t``, ``sigma`` the
logistic function, ``SiLU(z) = z sigma(z)``, ``RMSNorm_n(z; w) = z /
sqrt(mean(z^2) + eps) * w`` over the last ``n`` entries.

Linear-attention mixer (Gated DeltaNet, arXiv:2412.06464; ``H`` heads of
key width ``dk``, value width ``dv``)::

    q~, k~, v~ = W_q x, W_k x, W_v x                  # H dk, H dk, H dv channels
    (q', k', v')_t = SiLU( sum_{j=0..K-1} c_j * (q~, k~, v~)_{t-(K-1)+j} )
                                                      # causal depthwise conv, zeros before t = 0
    per head:  q_t = q'_t / sqrt(|q'_t|^2 + 1e-6) * dk^(-1/2)
               k_t = k'_t / sqrt(|k'_t|^2 + 1e-6),    v_t = v'_t
    beta_t  = 2 sigma(w_b . x_t)                      # the 2 is linear_allow_neg_eigval
    alpha_t = exp( -exp(A_log) softplus(w_a . x_t + dt_bias) )
    S_t = alpha_t S_{t-1} - beta_t (alpha_t S_{t-1} k_t - v_t) k_t^T     # S in R^{dv x dk}, S_{-1} = 0
    o_t = S_t q_t
    y_t = W_o [ RMSNorm_dv(o_t; w_n) * SiLU(W_g x_t) ]

Full-attention mixer: ``q, k, v = W_q x, W_k x, W_v x``; ``q <-
RMSNorm_h(q)``, ``k <- RMSNorm_h(k)`` over the whole width; heads of
``h / heads``; causal ``softmax(q k^T / sqrt(d)) v``; ``W_o``. No rotary.

Block: ``h = x + RMSNorm(mixer(x))``, ``out = h + RMSNorm(MLP(h))``,
``MLP(h) = W_down( SiLU(W_gate h) * W_up h )``; a final RMSNorm; an untied
head.

Weights are as ``benchmark/weights/olmo_hybrid.py`` makes them: ``{"embed":
{"embed"}, "layers": [ {...} ], "final": {"norm", "head"}}``; a linear layer has ``q_w k_w v_w g_w a_w b_w o_w q_conv
k_conv v_conv A_log dt_bias o_norm``, a full layer ``q_w k_w v_w o_w q_norm
k_norm``, both ``post_attn_norm gate_w up_w down_w post_ffn_norm``; matrices
are ``[in, out]``, convolutions ``[K, channels]``.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from benchmark.weights import olmo_hybrid as W

HI = jax.lax.Precision.HIGHEST


def _mm(a, b):
    return jnp.matmul(a, b, precision=HI)


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def silu(x):
    return x * jax.nn.sigmoid(x)


def causal_conv(x, c):
    """``x`` [s, C], ``c`` [K, C]: ``y_t = sum_j c_j x_{t-(K-1)+j}``."""
    k, s = c.shape[0], x.shape[0]
    xp = jnp.concatenate([jnp.zeros((k - 1, x.shape[1]), x.dtype), x])
    return sum(c[j] * xp[j:j + s] for j in range(k))


def linear_mixer(x, p, heads: int, dk: int, dv: int, eps: float,
                 neg_eigval: bool = True):
    """One sequence ``x`` [s, h] through the gated-delta mixer, the
    recurrence run token by token."""
    s = x.shape[0]
    q = silu(causal_conv(_mm(x, p["q_w"]), p["q_conv"])).reshape(s, heads, dk)
    k = silu(causal_conv(_mm(x, p["k_w"]), p["k_conv"])).reshape(s, heads, dk)
    v = silu(causal_conv(_mm(x, p["v_w"]), p["v_conv"])).reshape(s, heads, dv)
    q = q * jax.lax.rsqrt(jnp.sum(q * q, -1, keepdims=True) + 1e-6) \
        * dk ** -0.5
    k = k * jax.lax.rsqrt(jnp.sum(k * k, -1, keepdims=True) + 1e-6)
    beta = (2.0 if neg_eigval else 1.0) * jax.nn.sigmoid(_mm(x, p["b_w"]))
    alpha = jnp.exp(-jnp.exp(p["A_log"]) * jax.nn.softplus(
        _mm(x, p["a_w"]) + p["dt_bias"]))                    # [s, H]

    def step(S, xs):                                         # S [H, dv, dk]
        q_t, k_t, v_t, a_t, b_t = xs
        S = a_t[:, None, None] * S
        Sk = jnp.einsum("hvk,hk->hv", S, k_t, precision=HI)
        S = S - b_t[:, None, None] * jnp.einsum(
            "hv,hk->hvk", Sk - v_t, k_t, precision=HI)
        return S, jnp.einsum("hvk,hk->hv", S, q_t, precision=HI)

    _, o = jax.lax.scan(step, jnp.zeros((heads, dv, dk), jnp.float32),
                        (q, k, v, alpha, beta))              # o [s, H, dv]
    o = rms_norm(o, p["o_norm"], eps).reshape(s, heads * dv)
    return _mm(o * silu(_mm(x, p["g_w"])), p["o_w"])


def full_mixer(x, p, heads: int, eps: float):
    s, h = x.shape
    d = h // heads
    q = rms_norm(_mm(x, p["q_w"]), p["q_norm"], eps)
    k = rms_norm(_mm(x, p["k_w"]), p["k_norm"], eps)
    v = _mm(x, p["v_w"])
    q, k, v = (t.reshape(s, heads, d).transpose(1, 0, 2) for t in (q, k, v))
    scores = _mm(q, k.transpose(0, 2, 1)) / math.sqrt(d)
    scores = jnp.where(jnp.tril(jnp.ones((s, s), bool)), scores, -jnp.inf)
    ctx = _mm(jax.nn.softmax(scores, -1), v).transpose(1, 0, 2).reshape(s, h)
    return _mm(ctx, p["o_w"])


def block(x, p, cfg: dict):
    """One decoder layer over one sequence [s, h]; the kind of layer is read
    off its leaves."""
    eps = float(cfg["rms_norm_eps"])
    if "A_log" in p:
        y = linear_mixer(x, p, int(cfg["linear_num_key_heads"]),
                         int(cfg["linear_key_head_dim"]),
                         int(cfg["linear_value_head_dim"]), eps,
                         bool(cfg.get("linear_allow_neg_eigval", True)))
    else:
        y = full_mixer(x, p, int(cfg["num_attention_heads"]), eps)
    x = x + rms_norm(y, p["post_attn_norm"], eps)
    m = _mm(silu(_mm(x, p["gate_w"])) * _mm(x, p["up_w"]), p["down_w"])
    return x + rms_norm(m, p["post_ffn_norm"], eps)


def _f32(tree):
    return jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), tree)


def _frozen(cfg: dict):
    """The keys :func:`block` reads, hashable (a jit's static argument)."""
    return tuple((k, cfg[k]) for k in (
        "rms_norm_eps", "num_attention_heads", "linear_num_key_heads",
        "linear_key_head_dim", "linear_value_head_dim",
        "linear_allow_neg_eigval"))


def logits(weights: dict, cfg: dict, ids):
    """Float32 logits [s, vocab] of one sequence of token ids [s] (small
    sizes: everything is held at once)."""
    with jax.default_matmul_precision("highest"):
        x = _f32(weights["embed"])["embed"][jnp.asarray(ids)]
        for p in weights["layers"]:
            x = block(x, _f32(p), cfg)
        fin = _f32(weights["final"])
        return _mm(rms_norm(x, fin["norm"], float(cfg["rms_norm_eps"])),
                   fin["head"])


# ------------------------------------------------------------------ serving


@functools.partial(jax.jit, static_argnames=("cfg",))
def _block_jit(x, p, cfg):
    with jax.default_matmul_precision("highest"):
        return block(x, _f32(p), dict(cfg))


@functools.partial(jax.jit, static_argnames=("eps", "cap"))
def _rows_logits(x, f, start, eps, cap):
    rows = jax.lax.dynamic_slice_in_dim(x, start, cap, axis=0)
    f = _f32(f)
    with jax.default_matmul_precision("highest"):
        return _mm(rms_norm(rows, f["norm"], eps), f["head"])


def teacher_forced_logits(seed, cfg, dtype, prompt, served, pad_to=256,
                          cap=512):
    """One pass over ``prompt + served`` (token lists): the float32 logits
    [len(served), vocab] of the positions that predict each served token,
    on the device. Layer by layer, so only one layer's float32 weights
    exist at a time; the sequence is padded at its END to a multiple of
    ``pad_to`` (every layer is causal, so the padding reaches no row that
    is read) and at most ``cap`` rows are read, so few programs are
    compiled."""
    import numpy as np

    plen, n = len(prompt), len(served)
    if not 0 < n <= cap:
        raise ValueError(f"{n} served tokens; the check holds 1..{cap}")
    padded = -(-max(plen + n, cap + 1) // pad_to) * pad_to
    ids = np.zeros((padded,), np.int32)
    ids[:plen] = prompt
    ids[plen:plen + n] = served
    x = W.embed(seed, cfg, dtype)["embed"][jnp.asarray(ids)].astype(
        jnp.float32)
    for i in range(int(cfg["num_hidden_layers"])):
        x = _block_jit(x, W.layer(seed, i, cfg, dtype), _frozen(cfg))
    start = min(plen - 1, padded - cap)
    off = plen - 1 - start
    out = _rows_logits(x, W.final(seed, cfg, dtype), start,
                       float(cfg["rms_norm_eps"]), cap)
    return out[off:off + n]


def _gap_of(ref_logits, tokens):
    picked = jnp.take_along_axis(ref_logits, tokens[:, None], -1)[:, 0]
    return jnp.max(ref_logits, -1) - picked


def served_token_gaps(seed, cfg, dtype, prompt, served, **kw):
    """For every served token: the reference's best logit at its position
    minus the reference's logit of the token that was served, in logit
    units (0 where the served token is the reference's first choice)."""
    import numpy as np

    ref = teacher_forced_logits(seed, cfg, dtype, prompt, served, **kw)
    return np.asarray(_gap_of(ref, jnp.asarray(served, jnp.int32))).tolist()
