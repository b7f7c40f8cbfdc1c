"""LongCat-Flash (the language model of LongCat-Flash-Omni / -Chat): two
latent-attention sublayers a layer with a shortcut-connected expert layer
across them, softmax-routed experts of which a chip holds a share, and
zero-compute (identity) experts.

Config keys as the public ``config.json`` of
``meituan-longcat/LongCat-Flash-Omni`` (its language model; the audio and
vision encoders and the codec decoder are not here). The equations, and
what the config does not state, are at the head of
``benchmark/reference/longcat_flash.py``. One layer (``N`` RMSNorm with a
gain, sublayers ``a`` and ``b``)::

    x1 = x  + MLA_a(N1a(x))
    u  = N2a(x1)
    s  = MoE(u)                  # the shortcut: joins at the layer's end
    x2 = x1 + MLP_a(u)
    x3 = x2 + MLA_b(N1b(x2))
    x4 = x3 + MLP_b(N2b(x3))
    out = x4 + s

What is specific to the served form:

* **A layer with two cache entries meets the seam as two half-layers.**
  The seam hands one cache view to one serving layer
  (``serving_seam.forward_cached``), so ``serving_layers()`` lists
  :class:`LongcatHalfLayer` ``a`` then ``b`` of every layer and
  ``serving_spec()`` declares one :class:`LatentKVLayerState` each (8 for 4
  layers). The expert layer's output ``s`` crosses from ``a`` to the end of
  ``b`` in the step carry (``carry["scmoe.s"]``, ``[lanes, s, hidden]``
  float32; published by ``a``, popped by ``b``), as Xing4.0's layers hand
  their last stream update on. Nothing of it outlives the call, and the
  seam, the engine and the arena learn nothing new: a second kind of
  serving layer with two views would have touched all three for the same
  program.
* **The cache is one row a token a half-layer**: ``[RMSNorm(c_kv) |
  rotary(k_rope)]``, ``c_kv`` UNSCALED as the reference caches it. The
  attention is :class:`~paddle_tpu.models.xing4.LatentAttention`, shared
  with Xing4.0: expanded at prefill, absorbed at decode;
  ``mla_scale_q_lora`` and ``mla_scale_kv_lora`` are its ``q_scale`` =
  ``sqrt(hidden / q_lora_rank)`` and ``kv_scale`` = ``sqrt(hidden /
  kv_lora_rank)``. Rotary is plain (``rope_theta``, no scaling).
* **The residual stream is float32** between embed and final norm (the
  sublayers run in the weights' dtype): the router scores the unrounded
  ``N2a(x1)``, its choice being discrete.
* **Experts** (:mod:`paddle_tpu.ops.grouped_matmul`): softmax in float32
  over ``n_routed_experts + zero_expert_num`` columns, the ``moe_topk``
  largest of probability + selection bias, weights the chosen
  probabilities times ``routed_scaling_factor``, not renormalized. A chip
  is told which routed experts it holds (``expert_first``,
  ``expert_count``) and whether the zero-compute experts are counted here
  (``zero_experts_here``: they are computed where the token lives, by one
  share): it routes over all columns, moves only the rows its own experts
  take (``expert_ffn``'s passes), adds ``w u`` for each zero-compute pick,
  and drops no token at any load. Nothing stands in for the other chips.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from .. import nn
from ..core.tensor import Tensor
from ..nn import initializer as I
from ..ops import grouped_matmul as gm
from .serving_seam import LatentKVLayerState, ServingSpec, add_step_counters
from .xing4 import (F32, LatentAttention, Xing4MLP, _arr, _linear, _rms,
                    _SequenceView, _weight)


@dataclass
class LongcatFlashConfig:
    vocab_size: int = 131072
    hidden_size: int = 6144
    ffn_hidden_size: int = 12288
    expert_ffn_hidden_size: int = 2048
    num_layers: int = 28
    num_attention_heads: int = 64
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    mla_scale_q_lora: bool = True
    mla_scale_kv_lora: bool = True
    attention_method: str = "MLA"
    attention_bias: bool = False
    n_routed_experts: int = 512
    zero_expert_num: int = 256
    zero_expert_type: str = "identity"
    moe_topk: int = 12
    routed_scaling_factor: float = 6.0
    rms_norm_eps: float = 1e-5
    rope_theta: float = 10000000.0
    max_position_embeddings: int = 131072
    #: the routed experts this chip holds of every layer (None: all), and
    #: whether the zero-compute experts are counted here (one share does)
    expert_first: int = 0
    expert_count: Optional[int] = None
    zero_experts_here: bool = True

    def __post_init__(self):
        if self.attention_method != "MLA" or self.attention_bias:
            raise ValueError("latent attention without biases is what the "
                             "layer computes")
        if self.zero_expert_num and self.zero_expert_type != "identity":
            raise ValueError("zero_expert_type 'identity' is the one kind "
                             "of zero-compute expert the layer computes")
        if self.expert_count is None:
            self.expert_count = int(self.n_routed_experts) - self.expert_first
        if not 0 <= self.expert_first <= self.expert_first \
                + self.expert_count <= int(self.n_routed_experts):
            raise ValueError("the experts held are a range of those routed")

    @property
    def router_columns(self) -> int:
        return int(self.n_routed_experts) + int(self.zero_expert_num)

    @property
    def row_width(self) -> int:
        return int(self.kv_lora_rank) + int(self.qk_rope_head_dim)


def longcat_flash_tiny(**kw) -> LongcatFlashConfig:
    """Two layers (four half-layers) at test widths: 4 heads, a latent row
    of 32 + 8, 8 routed and 4 zero-compute experts of which 3 a token."""
    base = dict(vocab_size=512, hidden_size=64, ffn_hidden_size=128,
                expert_ffn_hidden_size=32, num_layers=2,
                num_attention_heads=4, q_lora_rank=32, kv_lora_rank=32,
                qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
                n_routed_experts=8, zero_expert_num=4, moe_topk=3,
                max_position_embeddings=256)
    base.update(kw)
    return LongcatFlashConfig(**base)


def rotary_frequencies(cfg: LongcatFlashConfig):
    """Plain rotary: ``inv_freq [rope / 2]`` float32."""
    dim = int(cfg.qk_rope_head_dim)
    inv = float(cfg.rope_theta) ** (
        -np.arange(0, dim, 2, dtype=np.float64) / dim)
    return np.asarray(inv, np.float32)


def softmax_scale(cfg: LongcatFlashConfig) -> float:
    return 1.0 / math.sqrt(int(cfg.qk_nope_head_dim)
                           + int(cfg.qk_rope_head_dim))


def _stream(x):
    """The residual stream behind a sublayer, MADE: without the barrier
    XLA never writes the running sum; it keeps every sublayer's output
    (float32, ``[s, hidden]``) alive to the end of the program and sums
    them again inside each norm that reads the stream: 14 such buffers at
    a prefill's peak (2.4 GB at 7,168 positions, compiled for a described
    v5e, PR 39), which the largest bucket does not have beside the
    weights."""
    return jax.lax.optimization_barrier(x)


class LongcatAttention(LatentAttention):
    def __init__(self, cfg: LongcatFlashConfig):
        h = int(cfg.hidden_size)
        super().__init__(
            h, cfg.num_attention_heads, cfg.q_lora_rank, cfg.kv_lora_rank,
            cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim,
            cfg.rms_norm_eps, softmax_scale(cfg), rotary_frequencies(cfg),
            q_scale=math.sqrt(h / int(cfg.q_lora_rank))
            if cfg.mla_scale_q_lora else 1.0,
            kv_scale=math.sqrt(h / int(cfg.kv_lora_rank))
            if cfg.mla_scale_kv_lora else 1.0)


class LongcatMoE(nn.Layer):
    """The shortcut-connected expert layer, or the share of it that holds
    the routed experts ``cfg.expert_first .. + cfg.expert_count`` (and,
    with ``cfg.zero_experts_here``, the zero-compute experts' part): routes
    over all ``n_routed_experts + zero_expert_num`` columns."""

    def __init__(self, cfg: LongcatFlashConfig):
        super().__init__()
        h, w = int(cfg.hidden_size), int(cfg.expert_ffn_hidden_size)
        self.routed, self.columns = (int(cfg.n_routed_experts),
                                     cfg.router_columns)
        self.k = int(cfg.moe_topk)
        self.first, self.count = int(cfg.expert_first), int(cfg.expert_count)
        self.scaling = float(cfg.routed_scaling_factor)
        self.zero_here = bool(cfg.zero_experts_here)
        self.router = self.create_parameter([h, self.columns])
        self.e_bias = self.create_parameter(
            [self.columns], dtype="float32",
            default_initializer=I.Constant(0.0))
        self.e_up = self.create_parameter([self.count, h, 2 * w])
        self.e_down = self.create_parameter([self.count, w, h])

    def route(self, flat):
        return gm.route_softmax_topk(flat, self.router._data,
                                     self.e_bias._data, self.k, self.scaling)

    def forward(self, u, u32, carry=None):
        """``u`` ``[b, s, h]`` in the weights' dtype, ``u32`` the same
        before it was rounded (the router scores that, and an identity
        expert returns that) -> ``[b, s, h]`` float32."""
        b, s, h = u.shape
        flat = u.reshape(b * s, h)
        flat32 = u32.reshape(b * s, h)
        idx, w = self.route(flat32)
        y = gm.expert_ffn(flat, idx, w, self.e_up._data, self.e_down._data,
                          self.columns, self.first)
        if self.zero_here:
            y = y + gm.zero_expert_weight(idx, w, self.routed)[:, None] \
                * flat32.astype(F32)
        if carry is not None and "lanes" in carry:  # a decode step
            add_step_counters(carry, gm.load_counters(
                idx, self.columns, rows=carry["lanes"].reshape(-1),
                first=self.first, held=self.count, routed=self.routed))
        return y.reshape(b, s, h)


class LongcatHalfLayer(nn.Layer):
    """Sublayers ``a`` (``expert`` True: attention, the expert layer's
    shortcut taken off, the dense MLP) or ``b`` (attention, the dense MLP,
    the shortcut joined) of one decoder layer: what the seam serves as one
    layer, with one latent cache entry."""

    uses_step_carry = True  # the shortcut, and the expert layer's counters

    def __init__(self, cfg: LongcatFlashConfig, expert: bool):
        super().__init__()
        h, one = int(cfg.hidden_size), I.Constant(1.0)
        self.eps, self.expert = float(cfg.rms_norm_eps), bool(expert)
        self.attn_norm = self.create_parameter([h], default_initializer=one)
        self.attn = LongcatAttention(cfg)
        self.mlp_norm = self.create_parameter([h], default_initializer=one)
        self.mlp = Xing4MLP(h, cfg.ffn_hidden_size)
        self.moe = LongcatMoE(cfg) if expert else None

    def forward(self, x, cache=None, start_pos=0, carry=None):
        X = x._data                                   # [b, s, h] float32
        dtype = self.attn_norm._data.dtype            # the weights' dtype
        with jax.named_scope("mla"):
            y, new_cache = self.attn(
                Tensor(_rms(X, self.attn_norm._data, self.eps, dtype)),
                cache, start_pos)
        X = _stream(X + y._data.astype(F32))
        u32 = _rms(X, self.mlp_norm._data, self.eps, F32)
        u = u32.astype(dtype)
        if self.expert:
            with jax.named_scope("scmoe"):
                carry["scmoe.s"] = self.moe(u, u32, carry)
        with jax.named_scope("mlp"):
            X = X + self.mlp(Tensor(u))._data.astype(F32)
        if not self.expert:
            X = X + carry.pop("scmoe.s")
        return Tensor(_stream(X)), new_cache


class LongcatDecoderLayer(nn.Layer):
    def __init__(self, cfg: LongcatFlashConfig):
        super().__init__()
        self.a = LongcatHalfLayer(cfg, expert=True)
        self.b = LongcatHalfLayer(cfg, expert=False)

    def halves(self):
        return (self.a, self.b)


class LongcatFlashModel(nn.Layer):
    def __init__(self, cfg: LongcatFlashConfig):
        super().__init__()
        self.cfg = cfg
        self.embed_tokens = nn.Embedding(cfg.vocab_size, cfg.hidden_size)
        self.layers = nn.LayerList(
            [LongcatDecoderLayer(cfg) for _ in range(int(cfg.num_layers))])
        self.norm = self.create_parameter(
            [int(cfg.hidden_size)], default_initializer=I.Constant(1.0))


class LongcatFlashForCausalLM(nn.Layer):
    def __init__(self, cfg: LongcatFlashConfig):
        super().__init__()
        self.cfg = cfg
        self.model = LongcatFlashModel(cfg)
        self.lm_head = _linear(cfg.hidden_size, cfg.vocab_size)  # untied

    def forward(self, input_ids, absorbed: bool = False):
        """Logits ``[b, s, vocab]`` float32 of whole sequences from
        position 0: the served half-layers over a view that keeps no
        cache, in the expanded or the absorbed form of the attention."""
        x = self.serving_embed(input_ids, 0)
        view = _SequenceView(absorbed, int(self.cfg.kv_lora_rank))
        carry = {}
        for half in self.serving_layers():
            x, _ = half(x, cache=view, carry=carry)
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
                           layers=(state,) * (2 * int(c.num_layers)),
                           kernels=("grouped_matmul",))

    def serving_embed(self, ids, positions):
        """The token's embedding in float32 (positions are the attention
        layers' to apply)."""
        return Tensor(self.model.embed_tokens(ids)._data.astype(F32))

    def serving_layers(self):
        return [half for layer in self.model.layers
                for half in layer.halves()]

    def serving_final(self, x):
        dtype = self.model.embed_tokens.weight._data.dtype
        return Tensor(_rms(_arr(x), self.model.norm._data,
                           float(self.cfg.rms_norm_eps), dtype))

    def serving_head(self, h_last):
        return self._logits(h_last)

    def serving_linears(self):
        out = []
        for li, layer in enumerate(self.model.layers):
            for name, half in zip("ab", layer.halves()):
                out += [(f"{li}.{name}.attn.{n}", lin)
                        for n, lin in half.attn.linears()]
                out += [(f"{li}.{name}.mlp.{n}", lin)
                        for n, lin in half.mlp.linears()]
        return out

    def serving_embedding(self):
        return self.model.embed_tokens
