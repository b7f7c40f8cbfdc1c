"""Xing4.0: latent (MLA) attention, sigmoid-routed experts beside a shared
one, and a hyper-connected residual path of ``hc_mult`` streams.

Config keys as the public ``config.json`` of ``XingChen-AGI/Xing4.0-29B-A4B``
(``model_type: xing4_0``). The equations, and what the config does not
state, are at the head of ``benchmark/reference/xing4.py``. What is specific
to the served form:

* **Streams.** Between embed and final norm the hidden states are
  ``[lanes, s, hc_mult * hidden]`` float32 (the embedding repeated, stream
  ``j`` the slice ``[j hidden, (j + 1) hidden)``; summed before the final
  norm). Each sublayer reads ``u = H_pre X`` and writes ``X' = H_res X +
  outer(H_post, y)``; mixers, Sinkhorn and the update are float32, the
  sublayer itself runs in the weights' dtype. A layer runs them as ONE
  kernel a sublayer (:mod:`paddle_tpu.ops.hyper_connection`: the update
  behind the previous sublayer, the mixers, the read-out and its RMSNorm
  in one pass over the streams); :class:`HyperConnection`'s ``mixers`` /
  ``pre`` / ``post`` are the same equations in ``jax.numpy`` over
  ``[lanes, s, hc_mult, hidden]``, which the tests hold the kernel to.
* **The cache is one row a token**: ``[RMSNorm(c_kv) | rotary(k_rope)]``,
  ``kv_lora_rank + qk_rope_head_dim`` values, declared as a
  :class:`~paddle_tpu.models.serving_seam.LatentKVLayerState`. A prefill
  EXPANDS the prompt's keys and values from its rows (``W_kvb``) and attends
  causally (keys 192 wide, values 128). A decode step is ABSORBED: ``q_lat =
  q_nope W_kvb^K`` per head, scores against the cached rows, the weighted
  rows' first ``kv_lora_rank`` values, then ``W_kvb^V``. The model applies
  rotary (YaRN frequencies from ``rope_scaling``) to the queries and to the
  row's rotary part at ``start_pos +`` the token's index, per lane.
* **Experts** (:mod:`paddle_tpu.ops.grouped_matmul`): sigmoid scores in
  float32, the 4 largest of score + selection bias, sort by expert, one
  grouped matmul over the stacked weights, unsort and combine. No token is
  dropped at any load. A layer is told which experts it holds
  (``expert_first``, ``expert_count``; ``shared_expert_here``): it routes
  over all of them and computes its own experts' part.
* **Multi-token prediction** (:meth:`Xing4ForCausalLM.mtp_logits`) is in
  the model when ``num_nextn_predict_layers`` is 1; the serving seam does
  not run it (``serving/spec_decode.py`` drafts with a second model only).
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from .. import nn
from ..core.tensor import Tensor
from ..nn import initializer as I
from ..ops import grouped_matmul as gm
from ..ops import hyper_connection as hc
from .serving_seam import (LatentKVLayerState, ServingSpec,
                           add_step_counters, serving_linear)

HI = jax.lax.Precision.HIGHEST
F32 = jnp.float32


def _yarn_default():
    return {"beta_fast": 32, "beta_slow": 1, "factor": 64, "mscale": 1,
            "mscale_all_dim": 1, "original_max_position_embeddings": 4096,
            "type": "yarn"}


@dataclass
class Xing4Config:
    vocab_size: int = 131072
    hidden_size: int = 3584
    intermediate_size: int = 9216
    moe_intermediate_size: int = 1024
    num_hidden_layers: int = 40
    num_attention_heads: int = 32
    q_lora_rank: int = 768
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    n_routed_experts: int = 64
    n_shared_experts: int = 1
    num_experts_per_tok: int = 4
    first_k_dense_replace: int = 2
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 2.0
    num_nextn_predict_layers: int = 1
    hc_mult: int = 4
    hc_sinkhorn_iters: int = 20
    hc_eps: float = 1e-6
    mhc_h_res_clamp_min: float = -30.0
    mhc_h_res_clamp_max: float = 30.0
    rms_norm_eps: float = 1e-6
    rope_theta: float = 10000.0
    rope_scaling: dict = field(default_factory=_yarn_default)
    max_position_embeddings: int = 262144
    #: the experts this chip holds of every expert layer (None: all), and
    #: whether the shared expert is counted here (one share counts it)
    expert_first: int = 0
    expert_count: Optional[int] = None
    shared_expert_here: bool = True

    def __post_init__(self):
        if int(self.n_shared_experts) != 1:
            raise ValueError("one shared expert is what the layer computes")
        if self.expert_count is None:
            self.expert_count = int(self.n_routed_experts) - self.expert_first
        if not 0 <= self.expert_first <= self.expert_first \
                + self.expert_count <= int(self.n_routed_experts):
            raise ValueError("the experts held are a range of those routed")

    def is_dense(self, index: int) -> bool:
        return index < int(self.first_k_dense_replace)

    @property
    def row_width(self) -> int:
        return int(self.kv_lora_rank) + int(self.qk_rope_head_dim)


def xing4_tiny(**kw) -> Xing4Config:
    """One dense and two expert layers at test widths: 4 heads, a latent
    row of 32 + 8, 8 experts of which 2 a token, 4 streams."""
    base = dict(vocab_size=512, hidden_size=64, intermediate_size=128,
                moe_intermediate_size=32, num_hidden_layers=3,
                num_attention_heads=4, q_lora_rank=32, kv_lora_rank=32,
                qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
                n_routed_experts=8, num_experts_per_tok=2,
                first_k_dense_replace=1, max_position_embeddings=256)
    base.update(kw)
    return Xing4Config(**base)


# ------------------------------------------------------------ plain pieces


def _arr(t):
    return t._data if isinstance(t, Tensor) else t


def _rms(x, w, eps: float, dtype=None):
    xf = x.astype(F32)
    out = xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + eps) \
        * w.astype(F32)
    return out.astype(dtype or x.dtype)


def _yarn_mscale(factor: float, m: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * m * math.log(factor) + 1.0


def yarn_frequencies(cfg: Xing4Config):
    """``(inv_freq [rope / 2] float32, the rotary's amplitude factor)``."""
    rs, dim = cfg.rope_scaling, int(cfg.qk_rope_head_dim)
    base, factor = float(cfg.rope_theta), float(rs["factor"])
    orig = float(rs["original_max_position_embeddings"])
    pos_freq = base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)

    def corr(rot):
        return dim * math.log(orig / (rot * 2 * math.pi)) \
            / (2 * math.log(base))

    low = max(math.floor(corr(float(rs["beta_fast"]))), 0)
    high = min(math.ceil(corr(float(rs["beta_slow"]))), dim - 1)
    high = high + 0.001 if low == high else high
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0, 1)
    inv = ramp / (factor * pos_freq) + (1 - ramp) / pos_freq
    amp = _yarn_mscale(factor, float(rs["mscale"])) \
        / _yarn_mscale(factor, float(rs["mscale_all_dim"]))
    return np.asarray(inv, np.float32), amp


def softmax_scale(cfg: Xing4Config) -> float:
    rs = cfg.rope_scaling
    m = _yarn_mscale(float(rs["factor"]), float(rs["mscale_all_dim"]))
    return m * m / math.sqrt(int(cfg.qk_nope_head_dim)
                             + int(cfg.qk_rope_head_dim))


def _rotary(x, positions, inv_freq, amp):
    """``x`` ``[b, s, ..., rope]`` at ``positions`` ``[b, s]``: consecutive
    pairs turned by ``position * inv_freq``; the result de-interleaved."""
    ang = positions.astype(F32)[..., None] * jnp.asarray(inv_freq)
    ang = ang.reshape(ang.shape[:2] + (1,) * (x.ndim - 3) + ang.shape[-1:])
    cos, sin = jnp.cos(ang) * amp, jnp.sin(ang) * amp
    x1, x2 = x[..., 0::2].astype(F32), x[..., 1::2].astype(F32)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           -1).astype(x.dtype)


def _positions(start_pos, b: int, s: int):
    """``[b, s]`` positions of a call's tokens: ``start_pos`` (a scalar,
    or one a lane) plus the token's index."""
    start = jnp.asarray(_arr(start_pos), jnp.int32)
    start = jnp.broadcast_to(start.reshape(-1, 1), (b, 1))
    return start + jnp.arange(s, dtype=jnp.int32)[None, :]


def _weight(lin):
    """A linear's ``[in, out]`` weight in its compute dtype (an int8
    serving weight dequantized)."""
    scale = getattr(lin, "weight_scale", None)
    w = lin.weight._data
    if scale is None:
        return w
    return w.astype(F32) * scale._data


def sinkhorn(m, iters: int, eps: float):
    """Rows, then columns, divided by their sums plus ``eps``; ``iters``
    rounds."""
    for _ in range(iters):
        m = m / (jnp.sum(m, -1, keepdims=True) + eps)
        m = m / (jnp.sum(m, -2, keepdims=True) + eps)
    return m


def _linear(fan_in: int, fan_out: int) -> nn.Linear:
    return nn.Linear(fan_in, fan_out, bias_attr=False)


class _SequenceView:
    """The latent view over one whole sequence from position 0, with no
    cache: what ``forward(input_ids)`` hands the layers. ``absorbed``
    chooses the form the layer computes (both give the same result)."""

    def __init__(self, absorbed: bool = False, latent_dim: int = 0):
        self.absorbed, self.latent_dim = absorbed, latent_dim

    def write_and_attend(self, q, rows, scale, kv=None):
        qa, ra = _arr(q), _arr(rows)
        s = qa.shape[1]
        mask = (jnp.arange(s)[None, :] <= jnp.arange(s)[:, None])[None, None]
        if self.absorbed:  # every row is every head's key, and its value
            sc = jnp.einsum("bqhw,bkw->bhqk", qa, ra) * scale
            va = ra[..., :self.latent_dim]
            pr = jax.nn.softmax(jnp.where(mask, sc, -1e30).astype(F32), -1)
            return jnp.einsum("bhqk,bkd->bqhd", pr.astype(qa.dtype), va), None
        ka, va = (_arr(t) for t in kv)
        sc = jnp.einsum("bqhd,bkhd->bhqk", qa, ka) * scale
        pr = jax.nn.softmax(jnp.where(mask, sc, -1e30).astype(F32), -1)
        return jnp.einsum("bhqk,bkhd->bqhd", pr.astype(qa.dtype), va), None


# ----------------------------------------------------------------- layers


class HyperConnection(nn.Layer):
    """One sublayer's stream mixers: ``pre`` reads the sublayer's input off
    the streams, ``post`` writes its output back into them."""

    def __init__(self, cfg: Xing4Config):
        super().__init__()
        self.n, self.cfg = int(cfg.hc_mult), cfg
        n, h = self.n, int(cfg.hidden_size)
        cols = 2 * n + n * n
        self.norm = self.create_parameter(
            [n * h], default_initializer=I.Constant(1.0))
        f32 = lambda shape, init: self.create_parameter(
            shape, dtype="float32", default_initializer=init)
        self.w = f32([n * h, cols], I.Normal(0.0, (n * h) ** -0.5))
        self.b = f32([cols], I.Constant(0.0))
        self.a = f32([3], I.Constant(0.5))

    def mixers(self, X):
        """``X`` ``[b, s, n, h]`` float32 -> ``(H_pre [b, s, n], H_post
        [b, s, n], H_res [b, s, n, n])``, float32."""
        c, n = self.cfg, self.n
        b, s = X.shape[:2]
        # RMSNorm(flatten(X)) W with the gain folded into the matrix and
        # the inverse root mean square applied to the 24 outputs: the same
        # numbers without a normalized copy of the streams
        xf = X.reshape(b, s, -1)
        z = jnp.einsum("bsk,kc->bsc", xf, self.w._data.astype(F32)
                       * self.norm._data.astype(F32)[:, None], precision=HI)
        z = z * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True)
                              + float(c.rms_norm_eps))
        a, bias = self.a._data.astype(F32), self.b._data.astype(F32)
        pre = jax.nn.sigmoid(a[0] * z[..., :n] + bias[:n])
        post = 2.0 * jax.nn.sigmoid(a[1] * z[..., n:2 * n] + bias[n:2 * n])
        res = (a[2] * z[..., 2 * n:] + bias[2 * n:]).reshape(b, s, n, n)
        res = jnp.exp(jnp.clip(res, float(c.mhc_h_res_clamp_min),
                               float(c.mhc_h_res_clamp_max)))
        return pre, post, sinkhorn(res, int(c.hc_sinkhorn_iters),
                                   float(c.hc_eps))

    def pre(self, X):
        """-> ``(u [b, s, h] float32, what :meth:`post` needs)``."""
        pre, post, res = self.mixers(X)
        return jnp.sum(pre[..., None] * X, axis=2), (post, res)

    def post(self, X, y, mix):
        post, res = mix
        mixed = sum(res[..., :, j, None] * X[..., None, j, :]
                    for j in range(self.n))
        return mixed + post[..., None] * y.astype(F32)[..., None, :]

    def _packed(self):
        return hc.pack_mixer_params(self.w._data, self.norm._data,
                                    self.a._data, self.b._data, self.n)

    def pack(self):
        """Keep the mixers' weights as the kernel reads them (buffers
        ``packed_w``, ``packed_ab``), from the parameters as they are now:
        :meth:`read` then derives nothing. Again after the weights change."""
        params = self._packed()
        self.register_buffer("packed_w", Tensor(params.w), persistable=False)
        self.register_buffer("packed_ab", Tensor(params.ab),
                             persistable=False)

    def read(self, X, gain, dtype, prev=None, want_f32: bool = False):
        """The kernel's form of :meth:`post` (of the sublayer before, ``prev``
        its ``(y, mix)``), :meth:`pre` and the sublayer's RMSNorm with
        ``gain``, on flat streams ``[b, s, n h]`` -> ``(X', u in dtype, u
        unrounded or None, mix)``."""
        c = self.cfg
        if "packed_w" in self._buffers:
            params = hc.MixerParams(self.packed_w._data, self.packed_ab._data)
        else:
            params = self._packed()
        return hc.hyper_connection(
            X, params, gain, n=self.n, iters=int(c.hc_sinkhorn_iters),
            rms_eps=float(c.rms_norm_eps), hc_eps=float(c.hc_eps),
            clamp=(c.mhc_h_res_clamp_min, c.mhc_h_res_clamp_max),
            out_dtype=dtype, prev=prev, want_f32=want_f32)


class LatentAttention(nn.Layer):
    """Latent (MLA) attention over a ``"latent"`` cache view, expanded or
    absorbed as the view says (the module's head): the mathematics every
    model with such a layer shares; the sizes, the softmax scale and the
    rotary's frequencies are the model's. ``q_scale`` multiplies the
    normed query latent before ``W_qb``, ``kv_scale`` the normed ``c_kv``
    before ``W_kvb``. The cached row holds ``c_kv`` unscaled, so
    ``kv_scale`` is folded where both forms meet it: into ``q_nope`` (the
    scores' no-rotary part is linear in the keys) and into the attention's
    output (linear in the values)."""

    def __init__(self, hidden: int, heads: int, q_lora_rank: int,
                 kv_lora_rank: int, nope: int, rope: int, v_dim: int,
                 eps: float, scale: float, inv_freq, amp: float = 1.0,
                 q_scale: float = 1.0, kv_scale: float = 1.0):
        super().__init__()
        h, self.heads = int(hidden), int(heads)
        self.nope, self.rope = int(nope), int(rope)
        self.vd, self.kvr = int(v_dim), int(kv_lora_rank)
        self.eps, self.scale = float(eps), float(scale)
        self.inv_freq, self.amp = inv_freq, amp
        self.q_scale, self.kv_scale = float(q_scale), float(kv_scale)
        self.q_a = _linear(h, q_lora_rank)
        self.q_b = _linear(q_lora_rank,
                           self.heads * (self.nope + self.rope))
        self.kv_a = _linear(h, self.kvr + self.rope)
        self.kv_b = _linear(self.kvr, self.heads * (self.nope + self.vd))
        self.o = _linear(self.heads * self.vd, h)
        one = I.Constant(1.0)
        self.q_a_norm = self.create_parameter([int(q_lora_rank)],
                                              default_initializer=one)
        self.kv_a_norm = self.create_parameter([self.kvr],
                                               default_initializer=one)

    def linears(self):
        return tuple((n, getattr(self, n))
                     for n in ("q_a", "q_b", "kv_a", "kv_b", "o"))

    def forward(self, x, cache, start_pos=0):
        b, s = x.shape[:2]
        heads, nope, kvr = self.heads, self.nope, self.kvr
        dtype = x._data.dtype
        pos = _positions(start_pos, b, s)
        cq = _rms(serving_linear(self.q_a, x)._data, self.q_a_norm._data,
                  self.eps)
        if self.q_scale != 1.0:
            cq = cq * self.q_scale
        q = serving_linear(self.q_b, Tensor(cq))._data.reshape(
            b, s, heads, nope + self.rope)
        q_nope = q[..., :nope]
        if self.kv_scale != 1.0:
            q_nope = q_nope * self.kv_scale
        q_rope = _rotary(q[..., nope:], pos, self.inv_freq, self.amp)
        row = serving_linear(self.kv_a, x)._data
        rows = jnp.concatenate(            # what the cache holds, no more
            [_rms(row[..., :kvr], self.kv_a_norm._data, self.eps),
             _rotary(row[..., kvr:], pos, self.inv_freq, self.amp)], -1)
        if cache.absorbed:
            w = _weight(self.kv_b).astype(dtype).reshape(
                kvr, heads, nope + self.vd)
            q_lat = jnp.einsum("bshd,chd->bshc", q_nope, w[..., :nope])
            o_lat, new_cache = cache.write_and_attend(
                jnp.concatenate([q_lat, q_rope], -1), rows, self.scale)
            o = jnp.einsum("bshc,chd->bshd", _arr(o_lat).astype(dtype),
                           w[..., nope:])
        else:
            kv = serving_linear(self.kv_b, Tensor(rows[..., :kvr]))._data
            kv = kv.reshape(b, s, heads, nope + self.vd)
            k_rope = jnp.broadcast_to(rows[:, :, None, kvr:],
                                      (b, s, heads, self.rope))
            o, new_cache = cache.write_and_attend(
                jnp.concatenate([q_nope, q_rope], -1), rows, self.scale,
                kv=(jnp.concatenate([kv[..., :nope], k_rope], -1),
                    kv[..., nope:]))
        o = _arr(o).astype(dtype).reshape(b, s, heads * self.vd)
        if self.kv_scale != 1.0:
            o = o * self.kv_scale
        return serving_linear(self.o, Tensor(o)), new_cache


class Xing4Attention(LatentAttention):
    def __init__(self, cfg: Xing4Config):
        inv_freq, amp = yarn_frequencies(cfg)
        super().__init__(
            cfg.hidden_size, cfg.num_attention_heads, cfg.q_lora_rank,
            cfg.kv_lora_rank, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
            cfg.v_head_dim, cfg.rms_norm_eps, softmax_scale(cfg), inv_freq,
            amp)


class Xing4MLP(nn.Layer):
    """SwiGLU; ``up`` is ``[gate | up]`` (any model's dense MLP of that
    form: LongCat-Flash's two a layer too)."""

    def __init__(self, hidden: int, width: int):
        super().__init__()
        self.width = int(width)
        self.up = _linear(hidden, 2 * self.width)
        self.down = _linear(self.width, hidden)

    def linears(self):
        return (("up", self.up), ("down", self.down))

    def forward(self, x, carry=None, x32=None):
        gu = serving_linear(self.up, x)._data
        act = jax.nn.silu(gu[..., :self.width].astype(F32)) \
            * gu[..., self.width:].astype(F32)
        return serving_linear(self.down, Tensor(act.astype(gu.dtype)))


class Xing4MoE(nn.Layer):
    """The expert layer, or the share of it that holds the experts
    ``cfg.expert_first .. + cfg.expert_count`` (and, with
    ``cfg.shared_expert_here``, the shared expert): routes over all
    ``n_routed_experts``."""

    def __init__(self, cfg: Xing4Config):
        super().__init__()
        h, w = int(cfg.hidden_size), int(cfg.moe_intermediate_size)
        self.experts, self.k = (int(cfg.n_routed_experts),
                                int(cfg.num_experts_per_tok))
        self.first, count = int(cfg.expert_first), int(cfg.expert_count)
        self.scaling = float(cfg.routed_scaling_factor)
        self.normalize = bool(cfg.norm_topk_prob)
        self.router = self.create_parameter([h, self.experts])
        self.e_bias = self.create_parameter(
            [self.experts], dtype="float32",
            default_initializer=I.Constant(0.0))
        self.e_up = self.create_parameter([count, h, 2 * w])
        self.e_down = self.create_parameter([count, w, h])
        self.shared = Xing4MLP(h, w) if cfg.shared_expert_here else None

    def linears(self):
        return () if self.shared is None else tuple(
            ("shared." + n, lin) for n, lin in self.shared.linears())

    def route(self, flat):
        return gm.route_sigmoid_topk(flat, self.router._data,
                                     self.e_bias._data, self.k, self.scaling,
                                     self.normalize)

    def forward(self, x, carry=None, x32=None):
        """``x32``: the layer's input before it was rounded to the
        weights' dtype; the router scores that (its choice is discrete: a
        rounding that moves a score past its neighbour gives the token
        another expert)."""
        b, s, h = x.shape
        flat = x._data.reshape(b * s, h)
        idx, w = self.route(flat if x32 is None else x32.reshape(b * s, h))
        y = gm.expert_ffn(flat, idx, w, self.e_up._data, self.e_down._data,
                          self.experts, self.first)
        if carry is not None and "lanes" in carry:  # a decode step
            add_step_counters(carry, gm.load_counters(
                idx, self.experts, rows=carry["lanes"].reshape(-1)))
        y = y.reshape(b, s, h)
        if self.shared is not None:
            y = y + self.shared(x)._data.astype(F32)
        return Tensor(y.astype(flat.dtype))


class Xing4DecoderLayer(nn.Layer):
    uses_step_carry = True  # the expert layer adds to the step's counters

    def __init__(self, cfg: Xing4Config, dense: bool, hands_on: bool = False):
        """``hands_on``: a layer follows in the stack, so under a step carry
        this one leaves its last update to that layer's first kernel
        (``carry["hc.y"]``, ``carry["hc.mix"]``) and returns the streams as
        they were BEFORE it: one pass over them a sublayer, not two."""
        super().__init__()
        self.dense, self.hands_on = dense, hands_on
        one = I.Constant(1.0)
        h = int(cfg.hidden_size)
        self.hc_attn = HyperConnection(cfg)
        self.attn_norm = self.create_parameter([h], default_initializer=one)
        self.attn = Xing4Attention(cfg)
        self.hc_mlp = HyperConnection(cfg)
        self.mlp_norm = self.create_parameter([h], default_initializer=one)
        self.mlp = (Xing4MLP(h, cfg.intermediate_size) if dense
                    else Xing4MoE(cfg))

    def forward(self, x, cache=None, start_pos=0, carry=None):
        X = x._data                                  # [b, s, n h] float32
        dtype = self.attn_norm._data.dtype           # the weights' dtype
        prev = None
        if carry is not None and "hc.y" in carry:    # the layer before's
            prev = carry.pop("hc.y"), carry.pop("hc.mix")
        with jax.named_scope("mhc"):
            X, u, _, mix = self.hc_attn.read(X, self.attn_norm._data, dtype,
                                             prev=prev)
        with jax.named_scope("mla"):
            y, new_cache = self.attn(Tensor(u), cache, start_pos)
        with jax.named_scope("mhc"):
            X, u, u32, mix = self.hc_mlp.read(
                X, self.mlp_norm._data, dtype, prev=(y._data, mix),
                want_f32=not self.dense)
        with jax.named_scope("mlp" if self.dense else "moe"):
            y = self.mlp(Tensor(u), carry, u32)
        if self.hands_on and carry is not None:
            carry["hc.y"], carry["hc.mix"] = y._data, mix
            return Tensor(X), new_cache
        with jax.named_scope("mhc"):
            X = hc.hyper_connection_update(X, y._data, mix,
                                           n=self.hc_mlp.n)
        return Tensor(X), new_cache


class Xing4MTP(nn.Layer):
    """The multi-token-prediction module: ``W_proj [RMSNorm(h) ;
    RMSNorm(Emb(next token))]`` and one expert decoder block."""

    def __init__(self, cfg: Xing4Config):
        super().__init__()
        h, one = int(cfg.hidden_size), I.Constant(1.0)
        self.hnorm = self.create_parameter([h], default_initializer=one)
        self.enorm = self.create_parameter([h], default_initializer=one)
        self.proj = _linear(2 * h, h)
        self.block = Xing4DecoderLayer(cfg, dense=False)


class Xing4Model(nn.Layer):
    def __init__(self, cfg: Xing4Config):
        super().__init__()
        self.cfg = cfg
        self.embed_tokens = nn.Embedding(cfg.vocab_size, cfg.hidden_size)
        depth = int(cfg.num_hidden_layers)
        self.layers = nn.LayerList(
            [Xing4DecoderLayer(cfg, cfg.is_dense(i), hands_on=i < depth - 1)
             for i in range(depth)])
        self.norm = self.create_parameter(
            [int(cfg.hidden_size)], default_initializer=I.Constant(1.0))


class Xing4ForCausalLM(nn.Layer):
    def __init__(self, cfg: Xing4Config, with_mtp: bool = False):
        super().__init__()
        self.cfg = cfg
        self.model = Xing4Model(cfg)
        self.lm_head = _linear(cfg.hidden_size, cfg.vocab_size)  # untied
        self.mtp = Xing4MTP(cfg) if with_mtp else None

    def _streams(self, emb):
        return jnp.tile(emb.astype(F32), (1, 1, int(self.cfg.hc_mult)))

    def _summed(self, x):
        """``[b, s, n h]`` streams -> their sum ``[b, s, h]``."""
        h = int(self.cfg.hidden_size)
        return sum(x[..., j:j + h] for j in range(0, x.shape[-1], h))

    def _final_streams(self, input_ids, absorbed: bool):
        x = self.serving_embed(input_ids, 0)
        view = _SequenceView(absorbed, int(self.cfg.kv_lora_rank))
        for layer in self.model.layers:
            x, _ = layer(x, cache=view)
        return x

    def forward(self, input_ids, absorbed: bool = False):
        """Logits ``[b, s, vocab]`` float32 of whole sequences from
        position 0: the served layers over a view that keeps no cache, in
        the expanded or the absorbed form of the attention."""
        h = self.serving_final(self._final_streams(input_ids, absorbed))
        return Tensor(self._logits(h._data))

    def mtp_logits(self, input_ids):
        """``[b, s - 1, vocab]``: row ``i`` predicts token ``i + 2`` from
        the summed streams at ``i`` and the embedding of token ``i + 1``."""
        m, eps = self.mtp, float(self.cfg.rms_norm_eps)
        ids = _arr(input_ids)
        h = self._summed(self._final_streams(input_ids, False)._data)[:, :-1]
        e = self.model.embed_tokens(Tensor(ids[:, 1:]))._data
        z = serving_linear(m.proj, Tensor(jnp.concatenate(
            [_rms(h, m.hnorm._data, eps, e.dtype),
             _rms(e, m.enorm._data, eps)], -1)))._data
        x, _ = m.block(Tensor(self._streams(z)), cache=_SequenceView())
        return Tensor(self._logits(self.serving_final(x)._data))

    def _logits(self, h):
        return jnp.matmul(h, _weight(self.lm_head).astype(h.dtype),
                          preferred_element_type=F32)

    # ---- the engine<->model seam (models/serving_seam.py)

    def serving_spec(self) -> ServingSpec:
        c = self.cfg
        state = LatentKVLayerState(int(c.kv_lora_rank),
                                   int(c.qk_rope_head_dim),
                                   int(c.num_attention_heads))
        return ServingSpec(vocab_size=int(c.vocab_size),
                           max_positions=int(c.max_position_embeddings),
                           layers=(state,) * int(c.num_hidden_layers),
                           kernels=("hyper_connection",))

    def serving_prepare(self):
        """Every hyper-connection's weights in the kernel's form, once."""
        for layer in self.sublayers():
            if isinstance(layer, HyperConnection):
                layer.pack()

    def serving_embed(self, ids, positions):
        """The token's embedding repeated into the streams (positions are
        the attention layers' to apply)."""
        return Tensor(self._streams(self.model.embed_tokens(ids)._data))

    def serving_layers(self):
        return self.model.layers

    def serving_final(self, x):
        """The streams summed, then the final norm: ``[b, s, hidden]`` in
        the weights' dtype."""
        dtype = self.model.embed_tokens.weight._data.dtype
        return Tensor(_rms(self._summed(x._data), self.model.norm._data,
                           float(self.cfg.rms_norm_eps), dtype))

    def serving_head(self, h_last):
        return self._logits(h_last)

    def serving_linears(self):
        out = []
        for li, layer in enumerate(self.model.layers):
            out += [(f"{li}.attn.{n}", lin) for n, lin in layer.attn.linears()]
            out += [(f"{li}.mlp.{n}", lin) for n, lin in layer.mlp.linears()]
        return out

    def serving_embedding(self):
        return self.model.embed_tokens
