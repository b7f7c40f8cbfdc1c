"""Plain Phi-4-mini-flash reference (the SambaY decoder-hybrid-decoder,
arXiv:2507.06607): the forward pass in ``jax.numpy``, float32, matmuls at
``jax.default_matmul_precision("highest")``, the state-space recurrence one
token at a time (``lax.scan`` over ``t``), attention as plain masked softmax
in blocks of query rows (``lax.map``, so that a 9k-token request fits the
chip). No kernel, no cache, no batching, no grouped-query trick, no tail split: every
layer runs on every token. It imports nothing of ``paddle_tpu`` and takes
nothing the program made: its weights come from
``benchmark/weights/phi4flash.py`` and the seed, the same values the program
was filled with, upcast.

From the model's public ``config.json``: hidden 2560, 32 layers, 40 heads, 20
K/V heads (head width 64), MLP width 10240 (SiLU-gated, no bias), LayerNorm
eps 1e-5, ``sliding_window`` 512, ``mb_per_layer`` 2, vocabulary 200,064, the
head tied to the token table, no head bias.

**Assumed** (not keys of that config; the configuration file lists each with
this provenance): the Mamba sizes, the family's defaults in the model's
public code (``d_state`` 16, ``d_conv`` 4, ``expand`` 2 so ``d_inner`` 5120,
``dt_rank`` ceil(2560/16) = 160, bias on the convolution and on ``dt_proj``,
none on ``in_proj``/``x_proj``/``out_proj``); no rotary or other positions
anywhere (arXiv:2507.06607: no explicit positional encoding); differential
attention in every attention layer (same paper: the released model is SambaY
with differential attention); LayerNorm with bias before mixer and MLP and at
the end; bias on the Q/K/V projection and on the attention output
projection; ``lam0`` from the layer's own index; a window of 512 keys that
counts the query's own position.

The equations. ``x`` is a block's input ``[s, h]``, ``LN`` LayerNorm with
gain and bias, ``SiLU(z) = z sigma(z)``.

Block, every layer: ``h = x + mixer(LN1(x))``, ``out = h + W_down(SiLU(g) *
u)`` with ``[g, u] = W_up LN2(h)``. Mixer by layer index ``i`` (0-based) of
``n`` layers, ``half = n / 2`` (16):

* ``i`` even, ``i <= half`` - **Mamba-1.** ``[u, z] = W_in x`` (``d_inner``
  each); ``c = SiLU(conv(u) + b_c)`` (causal, depthwise, width 4, zeros
  before ``t = 0``); ``[r, B, C] = W_x c`` (``dt_rank``, ``N``, ``N``);
  ``d = softplus(W_dt r + b_dt)``; ``A = -exp(A_log)`` ``[d_inner, N]``;
  ``S_t = exp(d_t * A) * S_{t-1} + (d_t * c_t) B_t^T``, ``S_{-1} = 0``;
  ``y_t = S_t C_t + D * c_t``; mixer output ``W_out (y * SiLU(z))``. Layer
  ``half`` is the same and also publishes **``m = y``** (before the gate) to
  the layers after it.
* ``i`` odd, ``i <= half + 1`` - **differential attention.** ``[q, k, v] =
  W_qkv x + b`` (``h | kv hd | kv hd``; the weights module keeps ``W_q`` and
  ``W_kv`` apart, the same numbers); ``q -> [heads/2, 2, hd]``
  (``q_{j,1}, q_{j,2}``), ``k -> [kv/2, 2, hd]``, ``v -> [kv/2, 2, hd]`` with
  ``V_g = [v_{g,1} | v_{g,2}]`` (``2 hd`` wide). For pair ``j``, ``g = j //
  (heads / kv)``: ``a_s = softmax(q_{j,s} K_{g,s}^T / sqrt(hd) + mask) V_g``
  for ``s`` = 1, 2; ``lam = exp(lq1 . lk1) - exp(lq2 . lk2) + lam0``,
  ``lam0 = 0.8 - 0.6 exp(-0.3 i)``; ``o_j = (1 - lam0) * RMSNorm_{2 hd}(a_1
  - lam * a_2)`` (learned gain, eps 1e-5); output ``W_o concat_j(o_j) +
  b_o``. Mask: ``i < half + 1``: query ``t`` sees keys ``t - window + 1 ..
  t``; ``i = half + 1``: causal over everything.
* ``i`` even, ``i > half`` - **gated memory unit.** ``W_2 (SiLU(W_1 x) *
  m)``, ``m`` of the same token from layer ``half``. No state.
* ``i`` odd, ``i > half + 1`` - **cross attention.** ``q = W_q x + b`` only;
  K and V are layer ``half + 1``'s, in the differential form above with this
  layer's own ``lq*``, ``lk*``, gain, ``W_o``, ``b_o``, causal. No state of
  its own.

A final LayerNorm; logits ``= LN(x) E^T`` with ``E`` the token table.

Weights are as ``benchmark/weights/phi4flash.py`` makes them (matrices
``[in, out]``, the convolution ``[K, channels]``).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from benchmark.weights import phi4flash as W

HI = jax.lax.Precision.HIGHEST
ROWS = 512  # query rows of one attention block


def _mm(a, b):
    return jnp.matmul(a, b, precision=HI)


def layer_norm(x, w, b, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * w + b


def silu(x):
    return x * jax.nn.sigmoid(x)


def causal_conv(x, c):
    """``x`` [s, C], ``c`` [K, C]: ``y_t = sum_j c_j x_{t-(K-1)+j}``."""
    k, s = c.shape[0], x.shape[0]
    xp = jnp.concatenate([jnp.zeros((k - 1, x.shape[1]), x.dtype), x])
    return sum(c[j] * xp[j:j + s] for j in range(k))


def mamba_mixer(x, p, sz: dict):
    """One sequence ``x`` [s, h] through the Mamba-1 mixer, the recurrence
    run token by token. Returns (mixer output, the scan output ``y``)."""
    di, n, r = sz["d_inner"], sz["d_state"], sz["dt_rank"]
    uz = _mm(x, p["in_w"])
    u, z = uz[:, :di], uz[:, di:]
    c = silu(causal_conv(u, p["conv_w"]) + p["conv_b"])
    proj = _mm(c, p["x_w"])
    d = jax.nn.softplus(_mm(proj[:, :r], p["dt_w"]) + p["dt_b"])  # [s, di]
    bm, cm = proj[:, r:r + n], proj[:, r + n:]
    a = -jnp.exp(p["A_log"])                                      # [di, N]

    def step(S, xs):                                              # S [di, N]
        d_t, c_t, b_t, c_out = xs
        S = jnp.exp(d_t[:, None] * a) * S \
            + (d_t * c_t)[:, None] * b_t[None, :]
        return S, jnp.sum(S * c_out[None, :], -1) + p["D"] * c_t

    _, y = jax.lax.scan(step, jnp.zeros((di, n), jnp.float32),
                        (d, c, bm, cm))
    return _mm(y * silu(z), p["out_w"]), y


def diff_attention(q, k, v, p, index, sz: dict, window, eps: float):
    """``q`` [s, h], ``k``, ``v`` [s, kv hd] -> [s, h] before ``W_o``:
    differential attention of every query row over the rows its mask
    allows (``window`` None: causal over everything), a block of ``ROWS``
    query rows at a time (``lax.map``: one block's scores exist at once).
    ``index`` is the layer's 0-based index (it may be traced: one compiled
    program then serves every layer of a kind)."""
    s = q.shape[0]
    hd, kv = sz["head_dim"], sz["num_key_value_heads"]
    pairs, groups = sz["num_attention_heads"] // 2, kv // 2
    rep = pairs // groups
    rows_n = min(ROWS, s)
    blocks = -(-s // rows_n)
    q = jnp.pad(q, ((0, blocks * rows_n - s), (0, 0)))
    q = q.reshape(blocks, rows_n, groups, rep, 2, hd)
    k = k.reshape(s, groups, 2, hd)
    vg = v.reshape(s, groups, 2 * hd)
    lam0 = 0.8 - 0.6 * jnp.exp(-0.3 * jnp.asarray(index, jnp.float32))
    lam = jnp.exp(jnp.sum(p["lq1"] * p["lk1"])) \
        - jnp.exp(jnp.sum(p["lq2"] * p["lk2"])) + lam0
    cols = jnp.arange(s)

    def one(xs):
        qb, r0 = xs
        rows = r0 + jnp.arange(rows_n)
        sc = jnp.einsum("rgjsd,tgsd->gjsrt", qb, k, precision=HI) \
            / math.sqrt(hd)
        mask = cols[None, :] <= rows[:, None]
        if window is not None:
            mask &= rows[:, None] - cols[None, :] < window
        sc = jnp.where(mask, sc, -jnp.inf)
        a = jnp.einsum("gjsrt,tgw->rgjsw", jax.nn.softmax(sc, -1), vg,
                       precision=HI)             # [rows, g, rep, 2, 2 hd]
        d = a[..., 0, :] - lam * a[..., 1, :]
        d = d * jax.lax.rsqrt(jnp.mean(d * d, -1, keepdims=True) + eps) \
            * p["subln"] * (1.0 - lam0)
        return d.reshape(rows_n, pairs * 2 * hd)

    out = jax.lax.map(one, (q, jnp.arange(blocks) * rows_n))
    return out.reshape(blocks * rows_n, pairs * 2 * hd)[:s]


def block(x, p, kind: str, index, sz: dict, eps: float, window: int,
          mem: dict, publishes: bool = False):
    """One decoder layer over one sequence [s, h]. ``mem`` carries what a
    layer hands the layers after it: ``"m"`` (the scan output of the Mamba
    layer that ``publishes``: layer ``half``) and ``"k"``, ``"v"`` (layer
    ``half + 1``'s). ``index`` may be traced. Returns ``(x, mem)``."""
    xn = layer_norm(x, p["ln1_w"], p["ln1_b"], eps)
    if kind == W.MAMBA:
        y, scan = mamba_mixer(xn, p, sz)
        if publishes:
            mem = dict(mem, m=scan)
    elif kind == W.GMU:
        y = _mm(silu(_mm(xn, p["in_w"])) * mem["m"], p["out_w"])
    else:
        q = _mm(xn, p["q_w"]) + p["q_b"]
        if kind == W.CROSS:
            k, v = mem["k"], mem["v"]
        else:
            kv = _mm(xn, p["kv_w"]) + p["kv_b"]
            k, v = jnp.split(kv, 2, axis=-1)
            if kind == W.FULL:
                mem = dict(mem, k=k, v=v)
        a = diff_attention(q, k, v, p, index, sz,
                           window if kind == W.SWA else None, eps)
        y = _mm(a, p["o_w"]) + p["o_b"]
    x = x + y
    gu = _mm(layer_norm(x, p["ln2_w"], p["ln2_b"], eps), p["up_w"])
    g, u = jnp.split(gu, 2, axis=-1)
    return x + _mm(silu(g) * u, p["down_w"]), mem


def _f32(tree):
    return jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), tree)


def _head(x, fin, table, eps):
    return _mm(layer_norm(x, fin["norm_w"], fin["norm_b"], eps), table.T)


def logits(weights: dict, cfg: dict, ids):
    """Float32 logits [s, vocab] of one sequence of token ids [s] (small
    sizes: everything is held at once)."""
    sz, eps = W.sizes(cfg), float(cfg["layer_norm_eps"])
    with jax.default_matmul_precision("highest"):
        table = _f32(weights["embed"])["embed"]
        x, mem = table[jnp.asarray(ids)], {}
        half = sz["num_hidden_layers"] // 2
        for i, p in enumerate(weights["layers"]):
            x, mem = block(x, _f32(p), W.kind_of(cfg, i), i, sz, eps,
                           sz["sliding_window"], mem, publishes=i == half)
        return _head(x, _f32(weights["final"]), table, eps)


# ------------------------------------------------------------------ serving


@functools.partial(jax.jit,
                   static_argnames=("kind", "sz", "eps", "publishes"))
def _block_jit(x, p, mem, index, kind, sz, eps, publishes):
    """``index`` is traced: one program a kind of layer (two for Mamba:
    the one that publishes its scan output) and a sequence length."""
    sz = dict(sz)
    with jax.default_matmul_precision("highest"):
        return block(x, _f32(p), kind, index, sz, eps, sz["sliding_window"],
                     mem, publishes)


@functools.partial(jax.jit, static_argnames=("eps", "cap"))
def _rows_logits(x, fin, table, start, eps, cap):
    rows = jax.lax.dynamic_slice_in_dim(x, start, cap, axis=0)
    with jax.default_matmul_precision("highest"):
        return _head(rows, _f32(fin), table.astype(jnp.float32), eps)


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
    sz = tuple(sorted(W.sizes(cfg).items()))
    eps = float(cfg["layer_norm_eps"])
    table = W.embed(seed, cfg, dtype)["embed"]
    x, mem = table[jnp.asarray(ids)].astype(jnp.float32), {}
    n_layers = int(cfg["num_hidden_layers"])
    for i in range(n_layers):
        x, mem = _block_jit(x, W.layer(seed, i, cfg, dtype), mem,
                            jnp.asarray(i, jnp.int32), W.kind_of(cfg, i),
                            sz, eps, i == n_layers // 2)
    start = min(plen - 1, padded - cap)
    off = plen - 1 - start
    out = _rows_logits(x, W.final(seed, cfg, dtype), table, start, eps, cap)
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
