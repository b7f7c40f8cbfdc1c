"""Trinity (``model_type: afmoe``): gated grouped-query attention over a
sliding window in three layers of four (rotary) and over the whole context
in the fourth (no positions), four norms a layer, sigmoid-routed experts
beside a shared one.

Config keys as the public ``config.json`` of
``arcee-ai/Trinity-Large-Preview``. The equations, and what the config does
not state, are at the head of ``benchmark/reference/trinity.py``. One layer
(``N`` RMSNorm with a gain; ``x`` the residual stream)::

    h  = N_in(x)
    q  = N_q(h Wq)   k = N_k(h Wk)   v = h Wv            # N_q, N_k a head
    sliding layers only: q, k = rotary(q, k; position)
    a  = attention(q, k, v) * sigmoid(h Wg)              # window or causal
    x1 = x + N_post_attn(a Wo)
    u  = N_pre_mlp(x1)
    m  = SwiGLU(u)  or  SwiGLU_shared(u) + sum_top4 w_e SwiGLU_e(u)
    y  = x1 + N_post_mlp(m)

What is specific to the served form:

* **Two kinds of cache in one model.** ``serving_spec()`` declares a
  :class:`~paddle_tpu.models.serving_seam.WindowLayerState` for every
  sliding layer (a ring of ``sliding_window`` rows a lane in the
  slot-indexed store) and a
  :class:`~paddle_tpu.models.serving_seam.KVLayerState` for every full one
  (the paged pool), both with ``num_key_value_heads`` stored heads: query
  head ``i`` reads K/V head ``i // (heads / kv_heads)``.
* **The model applies rotary**, to queries and keys of the sliding layers,
  at ``start_pos +`` the token's index (a scalar in a prefill, one a lane
  in the decode step), BEFORE the keys go to the view: a ring row carries
  its position in its values, so the order inside the ring stays free. A
  full layer has no positions at all.
* **The residual stream is float32** between embed and final norm (the
  sublayers run in the weights' dtype): the router scores the unrounded
  ``N_pre_mlp(x1)``, its choice being discrete.
* **Experts** (:mod:`paddle_tpu.ops.grouped_matmul`): sigmoid scores in
  float32, the ``num_experts_per_tok`` largest of score + selection bias,
  weights the chosen scores normalized to sum 1 (``route_norm``) times
  ``route_scale``. A chip is told which experts it holds (``expert_first``,
  ``expert_count``; ``shared_expert_here``): it routes over all
  ``num_experts``, moves the rows its own experts take and drops no token.
* **Counters**: the expert layers' ``moe.*``; and from every sliding layer
  of a decode step ``window.rows_live`` (over the lanes that hold a
  request, the ring rows that hold a key: ``min(position + 1, window)``)
  and ``window.rows_read`` (those lanes times ``window``: what the step's
  attention over the whole ring reads).
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import nn
from ..core.tensor import Tensor
from ..nn import initializer as I
from ..ops import grouped_matmul as gm
from .longcat_flash import _stream
from .phi4flash import _SequenceView
from .serving_seam import (KVLayerState, ServingSpec, WindowLayerState,
                           add_step_counters, serving_linear)
from .xing4 import F32, Xing4MLP, _arr, _linear, _positions, _rms, _weight

SLIDING, FULL = "sliding_attention", "full_attention"


@dataclass
class TrinityConfig:
    vocab_size: int = 200192
    hidden_size: int = 3072
    intermediate_size: int = 12288
    moe_intermediate_size: int = 3072
    num_hidden_layers: int = 60
    num_attention_heads: int = 48
    num_key_value_heads: int = 8
    head_dim: int = 128
    num_dense_layers: int = 6
    num_experts: int = 256
    num_experts_per_tok: int = 4
    num_shared_experts: int = 1
    score_func: str = "sigmoid"
    route_norm: bool = True
    route_scale: float = 2.448
    sliding_window: int = 4096
    global_attn_every_n_layers: int = 4
    #: one of ``sliding_attention`` / ``full_attention`` a layer (None:
    #: every ``global_attn_every_n_layers``-th layer full)
    layer_types: Optional[Tuple[str, ...]] = None
    rope_theta: float = 10000.0
    rms_norm_eps: float = 1e-5
    mup_enabled: bool = True
    max_position_embeddings: int = 262144
    #: the experts this chip holds of every expert layer (None: all), and
    #: whether the shared expert is counted here (one share counts it)
    expert_first: int = 0
    expert_count: Optional[int] = None
    shared_expert_here: bool = True

    def __post_init__(self):
        if self.score_func != "sigmoid" or int(self.num_shared_experts) != 1:
            raise ValueError("sigmoid routing beside one shared expert is "
                             "what the layer computes")
        n, every = (int(self.num_hidden_layers),
                    int(self.global_attn_every_n_layers))
        if self.layer_types is None:
            self.layer_types = tuple(FULL if (i + 1) % every == 0 else SLIDING
                                     for i in range(n))
        self.layer_types = tuple(self.layer_types)
        if len(self.layer_types) != n or set(self.layer_types) - {SLIDING,
                                                                  FULL}:
            raise ValueError("layer_types names one of sliding_attention / "
                             "full_attention for each layer")
        if int(self.num_attention_heads) % int(self.num_key_value_heads):
            raise ValueError("the query heads are a multiple of the K/V "
                             "heads")
        if self.expert_count is None:
            self.expert_count = int(self.num_experts) - self.expert_first
        if not 0 <= self.expert_first <= self.expert_first \
                + self.expert_count <= int(self.num_experts):
            raise ValueError("the experts held are a range of those routed")

    def is_dense(self, index: int) -> bool:
        return index < int(self.num_dense_layers)

    def is_sliding(self, index: int) -> bool:
        return self.layer_types[index] == SLIDING


def trinity_tiny(**kw) -> TrinityConfig:
    """One dense and four expert layers (``[s, s, s, full, s]``) at test
    widths: 6 query heads over 2 K/V heads of 16, a window of 8, 8 experts
    of which 2 a token."""
    base = dict(vocab_size=512, hidden_size=64, intermediate_size=128,
                moe_intermediate_size=32, num_hidden_layers=5,
                num_attention_heads=6, num_key_value_heads=2, head_dim=16,
                num_dense_layers=1, num_experts=8, num_experts_per_tok=2,
                sliding_window=8, max_position_embeddings=256)
    base.update(kw)
    return TrinityConfig(**base)


def rotary_frequencies(cfg: TrinityConfig):
    """Plain rotary over the whole head: ``inv_freq [head_dim / 2]``."""
    dim = int(cfg.head_dim)
    inv = float(cfg.rope_theta) ** (
        -np.arange(0, dim, 2, dtype=np.float64) / dim)
    return np.asarray(inv, np.float32)


def _rotary(x, positions, inv_freq):
    """``x`` ``[b, s, heads, dim]`` at ``positions`` ``[b, s]``: value ``i``
    of the first half is paired with value ``i`` of the second
    (rotate-half), the pair turned by ``position * inv_freq[i]``."""
    ang = positions.astype(F32)[..., None, None] * jnp.asarray(inv_freq)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half].astype(F32), x[..., half:].astype(F32)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           -1).astype(x.dtype)


class TrinityAttention(nn.Layer):
    """Grouped-query attention with a norm a head on queries and keys,
    rotary where ``sliding``, and a sigmoid gate (of the layer's input) on
    the attention's output before ``Wo``; over a ``"window"`` or a ``"kv"``
    cache view."""

    def __init__(self, cfg: TrinityConfig, sliding: bool):
        super().__init__()
        h, d = int(cfg.hidden_size), int(cfg.head_dim)
        self.heads, self.kv_heads = (int(cfg.num_attention_heads),
                                     int(cfg.num_key_value_heads))
        self.d, self.sliding = d, bool(sliding)
        self.window = int(cfg.sliding_window)
        self.eps = float(cfg.rms_norm_eps)
        self.inv_freq = rotary_frequencies(cfg)
        self.q_proj = _linear(h, self.heads * d)
        self.k_proj = _linear(h, self.kv_heads * d)
        self.v_proj = _linear(h, self.kv_heads * d)
        self.gate_proj = _linear(h, self.heads * d)
        self.o_proj = _linear(self.heads * d, h)
        one = I.Constant(1.0)
        self.q_norm = self.create_parameter([d], default_initializer=one)
        self.k_norm = self.create_parameter([d], default_initializer=one)

    def linears(self):
        return tuple((n, getattr(self, n)) for n in (
            "q_proj", "k_proj", "v_proj", "gate_proj", "o_proj"))

    def forward(self, x, cache, start_pos=0, carry=None):
        b, s = x.shape[:2]
        d = self.d
        q = serving_linear(self.q_proj, x)._data.reshape(b, s, self.heads, d)
        k = serving_linear(self.k_proj, x)._data.reshape(b, s, self.kv_heads,
                                                         d)
        v = serving_linear(self.v_proj, x)._data.reshape(b, s, self.kv_heads,
                                                         d)
        q = _rms(q, self.q_norm._data, self.eps)
        k = _rms(k, self.k_norm._data, self.eps)
        if self.sliding:
            pos = _positions(start_pos, b, s)
            q = _rotary(q, pos, self.inv_freq)
            k = _rotary(k, pos, self.inv_freq)
            if carry is not None and "lanes" in carry:  # a decode step
                lanes = carry["lanes"].reshape(-1)
                live = jnp.minimum(pos[:, 0] + 1, self.window)
                add_step_counters(carry, {
                    "window.rows_live": jnp.sum(jnp.where(lanes, live, 0),
                                                dtype=jnp.int32),
                    "window.rows_read": jnp.sum(lanes, dtype=jnp.int32)
                    * self.window})
        with jax.named_scope("swa" if self.sliding else "full_attn"):
            a, new_cache = cache.update_and_attend(Tensor(q), Tensor(k),
                                                   Tensor(v))
        with jax.named_scope("attn_gate"):
            a = _arr(a).reshape(b, s, self.heads * d).astype(F32) \
                * self.gate(x)
        return serving_linear(self.o_proj, Tensor(a.astype(q.dtype))), \
            new_cache

    def gate(self, x):
        """``[b, s, heads * head_dim]`` float32: one gate a value of the
        attention's output, from the layer's normed input."""
        return jax.nn.sigmoid(
            serving_linear(self.gate_proj, x)._data.astype(F32))


class TrinityMoE(nn.Layer):
    """The expert layer, or the share of it that holds the experts
    ``cfg.expert_first .. + cfg.expert_count`` (and, with
    ``cfg.shared_expert_here``, the shared expert): routes over all
    ``num_experts``."""

    def __init__(self, cfg: TrinityConfig):
        super().__init__()
        h, w = int(cfg.hidden_size), int(cfg.moe_intermediate_size)
        self.experts, self.k = (int(cfg.num_experts),
                                int(cfg.num_experts_per_tok))
        self.first, self.count = int(cfg.expert_first), int(cfg.expert_count)
        self.scaling = float(cfg.route_scale)
        self.normalize = bool(cfg.route_norm)
        self.router = self.create_parameter([h, self.experts])
        self.e_bias = self.create_parameter(
            [self.experts], dtype="float32",
            default_initializer=I.Constant(0.0))
        self.e_up = self.create_parameter([self.count, h, 2 * w])
        self.e_down = self.create_parameter([self.count, w, h])
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
        weights' dtype, which the router scores."""
        b, s, h = x.shape
        flat = x._data.reshape(b * s, h)
        idx, w = self.route(flat if x32 is None else x32.reshape(b * s, h))
        y = gm.expert_ffn(flat, idx, w, self.e_up._data, self.e_down._data,
                          self.experts, self.first)
        if carry is not None and "lanes" in carry:  # a decode step
            add_step_counters(carry, gm.load_counters(
                idx, self.experts, rows=carry["lanes"].reshape(-1),
                first=self.first, held=self.count))
        y = y.reshape(b, s, h)
        if self.shared is not None:
            y = y + self.shared(x)._data.astype(F32)
        return Tensor(y.astype(flat.dtype))


class TrinityDecoderLayer(nn.Layer):
    uses_step_carry = True  # the layers add to the step's counters

    def __init__(self, cfg: TrinityConfig, index: int):
        super().__init__()
        h, one = int(cfg.hidden_size), I.Constant(1.0)
        self.eps = float(cfg.rms_norm_eps)
        self.dense = cfg.is_dense(index)
        self.attn = TrinityAttention(cfg, cfg.is_sliding(index))
        for name in ("input_norm", "post_attn_norm", "pre_mlp_norm",
                     "post_mlp_norm"):
            setattr(self, name, self.create_parameter(
                [h], default_initializer=one))
        self.mlp = (Xing4MLP(h, cfg.intermediate_size) if self.dense
                    else TrinityMoE(cfg))

    def forward(self, x, cache=None, start_pos=0, carry=None):
        X = x._data                                   # [b, s, h] float32
        dtype = self.input_norm._data.dtype           # the weights' dtype
        h = _rms(X, self.input_norm._data, self.eps, dtype)
        y, new_cache = self.attn(Tensor(h), cache, start_pos, carry)
        X = _stream(X + _rms(y._data, self.post_attn_norm._data, self.eps,
                             F32))
        u32 = _rms(X, self.pre_mlp_norm._data, self.eps, F32)
        with jax.named_scope("mlp" if self.dense else "moe"):
            m = self.mlp(Tensor(u32.astype(dtype)), carry, u32)
        X = _stream(X + _rms(m._data, self.post_mlp_norm._data, self.eps,
                             F32))
        return Tensor(X), new_cache


class TrinityModel(nn.Layer):
    def __init__(self, cfg: TrinityConfig):
        super().__init__()
        self.cfg = cfg
        self.embed_tokens = nn.Embedding(cfg.vocab_size, cfg.hidden_size)
        self.layers = nn.LayerList(
            [TrinityDecoderLayer(cfg, i)
             for i in range(int(cfg.num_hidden_layers))])
        self.norm = self.create_parameter(
            [int(cfg.hidden_size)], default_initializer=I.Constant(1.0))


class TrinityForCausalLM(nn.Layer):
    def __init__(self, cfg: TrinityConfig):
        super().__init__()
        self.cfg = cfg
        self.model = TrinityModel(cfg)
        self.lm_head = _linear(cfg.hidden_size, cfg.vocab_size)  # untied

    def forward(self, input_ids):
        """Logits ``[b, s, vocab]`` float32 of whole sequences from
        position 0: the served layers over views that keep no cache."""
        c = self.cfg
        x = self.serving_embed(input_ids, 0)
        for i, layer in enumerate(self.model.layers):
            view = _SequenceView(
                window=int(c.sliding_window) if c.is_sliding(i) else None)
            x, _ = layer(x, cache=view)
        return Tensor(self._logits(self.serving_final(x)._data))

    def _logits(self, h):
        return jnp.matmul(h, _weight(self.lm_head).astype(h.dtype),
                          preferred_element_type=F32)

    # ---- the engine<->model seam (models/serving_seam.py)

    def serving_spec(self) -> ServingSpec:
        c = self.cfg
        heads, kv, d = (int(c.num_attention_heads),
                        int(c.num_key_value_heads), int(c.head_dim))
        window = WindowLayerState(heads, d, int(c.sliding_window),
                                  num_kv_heads=kv)
        full = KVLayerState(heads, d, num_kv_heads=kv)
        return ServingSpec(
            vocab_size=int(c.vocab_size),
            max_positions=int(c.max_position_embeddings),
            layers=tuple(window if c.is_sliding(i) else full
                         for i in range(int(c.num_hidden_layers))),
            kernels=("swa_prefill_flash",))

    def serving_embed(self, ids, positions):
        """The token's embedding in float32, times ``sqrt(hidden)`` where
        ``mup_enabled`` (positions are the sliding layers' to apply)."""
        x = self.model.embed_tokens(ids)._data.astype(F32)
        if self.cfg.mup_enabled:
            x = x * math.sqrt(int(self.cfg.hidden_size))
        return Tensor(x)

    def serving_layers(self):
        return self.model.layers

    def serving_final(self, x):
        dtype = self.model.embed_tokens.weight._data.dtype
        return Tensor(_rms(_arr(x), self.model.norm._data,
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
