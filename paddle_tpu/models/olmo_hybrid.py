"""Olmo-Hybrid: gated-delta linear-attention layers beside full attention.

Config keys as the public ``config.json`` of ``allenai/Olmo-Hybrid-7B``
(``model_type: olmo_hybrid``): ``layer_types`` mixes ``linear_attention``
(Gated DeltaNet, arXiv:2412.06464: a float32 matrix state per head behind a
short causal convolution) and ``full_attention`` (softmax attention with
QK-norm, no rotary positions), each followed by a SwiGLU MLP; RMSNorm after
each sub-block (``h = x + norm(mixer(x))``), a final RMSNorm and an untied
head. The equations, and what the config does not state, are at the head of
``tests/refs/olmo_hybrid_ref.py``.

Served through the engine<->model seam (``models/serving_seam.py``): a
full-attention layer keeps ``"kv"`` state (the paged arena), a
linear-attention layer ``"recurrent"`` state (a ``[30, 192, 96]`` float32
matrix and the convolution's last three input rows per lane, at the 7B
widths). Every matmul goes through ``serving_linear``, so the int8 weights
and the LoRA arena find them.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from .. import nn
from ..core.dispatch import apply
from ..core.tensor import Tensor
from ..nn import functional as F
from ..nn import initializer as I
from ..ops import gated_delta as gd
from ..ops import manipulation as M
from .serving_seam import (
    KVLayerState,
    RecurrentLayerState,
    ServingSpec,
    serving_linear,
)

LINEAR, FULL = "linear_attention", "full_attention"


@dataclass
class OlmoHybridConfig:
    vocab_size: int = 100352
    hidden_size: int = 3840
    intermediate_size: int = 11008
    num_hidden_layers: int = 32
    num_attention_heads: int = 30
    num_key_value_heads: int = 30
    max_position_embeddings: int = 65536
    rms_norm_eps: float = 1e-6
    #: one kind per layer; None = three linear then one full, repeated. A
    #: longer list (the published 32) is cut to ``num_hidden_layers``
    layer_types: Optional[Tuple[str, ...]] = None
    linear_num_key_heads: int = 30
    linear_num_value_heads: int = 30
    linear_key_head_dim: int = 96
    linear_value_head_dim: int = 192
    linear_conv_kernel_dim: int = 4
    linear_allow_neg_eigval: bool = True

    def __post_init__(self):
        n = int(self.num_hidden_layers)
        kinds = (tuple(self.layer_types) if self.layer_types is not None
                 else (LINEAR, LINEAR, LINEAR, FULL) * -(-n // 4))
        if len(kinds) < n or set(kinds) - {LINEAR, FULL}:
            raise ValueError(f"layer_types must give {n} layers of "
                             f"{LINEAR!r} or {FULL!r}")
        self.layer_types = kinds[:n]
        if self.num_key_value_heads != self.num_attention_heads:
            raise ValueError("fewer KV heads than query heads is not "
                             "served yet (the arena has one head count)")
        if self.linear_num_key_heads != self.linear_num_value_heads:
            raise ValueError("linear-attention key and value head counts "
                             "must be equal")
        if self.hidden_size % self.num_attention_heads:
            raise ValueError("hidden_size must divide into the heads")

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads


def olmo_hybrid_tiny(**kw) -> OlmoHybridConfig:
    """One period of the layer pattern at test widths."""
    base = dict(vocab_size=512, hidden_size=64, intermediate_size=128,
                num_hidden_layers=4, num_attention_heads=4,
                num_key_value_heads=4, max_position_embeddings=256,
                linear_num_key_heads=4, linear_num_value_heads=4,
                linear_key_head_dim=8, linear_value_head_dim=16)
    base.update(kw)
    return OlmoHybridConfig(**base)


def _linear(fan_in: int, fan_out: int) -> nn.Linear:
    return nn.Linear(fan_in, fan_out, bias_attr=False)


class OlmoMLP(nn.Layer):
    def __init__(self, cfg: OlmoHybridConfig):
        super().__init__()
        self.gate_proj = _linear(cfg.hidden_size, cfg.intermediate_size)
        self.up_proj = _linear(cfg.hidden_size, cfg.intermediate_size)
        self.down_proj = _linear(cfg.intermediate_size, cfg.hidden_size)

    def forward(self, x):
        return serving_linear(
            self.down_proj,
            F.silu(serving_linear(self.gate_proj, x))
            * serving_linear(self.up_proj, x))


class OlmoFullAttention(nn.Layer):
    """Softmax attention, QK-norm over the whole width, no rotary."""

    def __init__(self, cfg: OlmoHybridConfig):
        super().__init__()
        h = cfg.hidden_size
        self.num_heads, self.head_dim = cfg.num_attention_heads, cfg.head_dim
        self.q_proj, self.k_proj = _linear(h, h), _linear(h, h)
        self.v_proj, self.o_proj = _linear(h, h), _linear(h, h)
        self.q_norm = nn.RMSNorm(h, cfg.rms_norm_eps)
        self.k_norm = nn.RMSNorm(h, cfg.rms_norm_eps)

    def linears(self):
        return (("q_proj", self.q_proj), ("k_proj", self.k_proj),
                ("v_proj", self.v_proj), ("o_proj", self.o_proj))

    def forward(self, x, cache=None):
        b, s, h = x.shape
        heads = [b, s, self.num_heads, self.head_dim]
        q = M.reshape(self.q_norm(serving_linear(self.q_proj, x)), heads)
        k = M.reshape(self.k_norm(serving_linear(self.k_proj, x)), heads)
        v = M.reshape(serving_linear(self.v_proj, x), heads)
        if cache is None:
            o = F.scaled_dot_product_attention(q, k, v, is_causal=True)
            return serving_linear(self.o_proj, M.reshape(o, [b, s, h]))
        with jax.named_scope("kv_attend"):
            o, new_cache = cache.update_and_attend(q, k, v)
        oa = o._data if isinstance(o, Tensor) else o
        out = M.reshape(Tensor(oa), [b, s, h])
        return serving_linear(self.o_proj, out), new_cache


def _gdn_core(qkv, a, b, gate, conv_w, a_log, dt_bias, norm_w, state, tail,
              valid_len=None, *, heads, dk, dv, eps, beta_scale):
    """Everything of the linear-attention mixer between its input
    projections and its output projection, on raw arrays. ``qkv`` [B, T,
    H(2dk + dv)] before the convolution; ``a``, ``b`` [B, T, H] the gate
    and write projections; ``gate`` [B, T, H dv]. Returns the gated,
    normalized output [B, T, H dv] in ``qkv``'s dtype, the final state and
    the convolution's new tail. Positions at or past ``valid_len`` (a
    padded prefill's true length) change nothing."""
    f32 = jnp.float32
    bsz, t = qkv.shape[:2]
    conv, new_tail = gd.causal_conv(qkv, conv_w, tail, valid_len)
    conv = jax.nn.silu(conv)
    q = gd.l2norm(conv[..., :heads * dk].reshape(bsz, t, heads, dk)) \
        * dk ** -0.5
    k = gd.l2norm(conv[..., heads * dk:2 * heads * dk]
                  .reshape(bsz, t, heads, dk))
    v = conv[..., 2 * heads * dk:].reshape(bsz, t, heads, dv)
    beta = beta_scale * jax.nn.sigmoid(b.astype(f32))
    g = -jnp.exp(a_log.astype(f32)) * jax.nn.softplus(
        a.astype(f32) + dt_bias.astype(f32))
    if t == 1:
        o, new_state = gd.gated_delta_step(q[:, 0], k[:, 0], v[:, 0],
                                           g[:, 0], beta[:, 0], state)
        o = o[:, None]
    else:
        o, new_state = gd.gated_delta_chunked(
            q, k, v, g, beta, state, valid_len, mm_dtype=qkv.dtype)
    o = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True) + eps) \
        * norm_w.astype(f32)
    o = o.reshape(bsz, t, heads * dv) * jax.nn.silu(gate.astype(f32))
    return o.astype(qkv.dtype), new_state, new_tail.astype(tail.dtype)


class OlmoLinearAttention(nn.Layer):
    """Gated DeltaNet mixer: short convolutions, the gated delta rule over
    a float32 matrix state per head, a gated RMSNorm on the way out."""

    def __init__(self, cfg: OlmoHybridConfig):
        super().__init__()
        h = cfg.hidden_size
        self.heads = cfg.linear_num_key_heads
        self.dk, self.dv = cfg.linear_key_head_dim, cfg.linear_value_head_dim
        self.kernel = cfg.linear_conv_kernel_dim
        self.eps = cfg.rms_norm_eps
        self.beta_scale = 2.0 if cfg.linear_allow_neg_eigval else 1.0
        kw, vw = self.heads * self.dk, self.heads * self.dv
        self.conv_width = 2 * kw + vw
        self.q_proj, self.k_proj = _linear(h, kw), _linear(h, kw)
        self.v_proj, self.g_proj = _linear(h, vw), _linear(h, vw)
        self.a_proj, self.b_proj = _linear(h, self.heads), \
            _linear(h, self.heads)
        self.o_proj = _linear(vw, h)
        one = I.Constant(1.0)
        self.q_conv = self.create_parameter([self.kernel, kw])
        self.k_conv = self.create_parameter([self.kernel, kw])
        self.v_conv = self.create_parameter([self.kernel, vw])
        self.A_log = self.create_parameter(
            [self.heads], default_initializer=I.Constant(0.0))
        self.dt_bias = self.create_parameter(
            [self.heads], default_initializer=I.Constant(0.0))
        self.o_norm = self.create_parameter([self.dv],
                                            default_initializer=one)

    def linears(self):
        return (("q_proj", self.q_proj), ("k_proj", self.k_proj),
                ("v_proj", self.v_proj), ("g_proj", self.g_proj),
                ("o_proj", self.o_proj))

    def state_arrays(self, dtype: str):
        """Per-lane state: the matrix state (float32) and the last
        ``kernel - 1`` rows that went into the convolution (the compute
        dtype)."""
        return (("S", (self.heads, self.dv, self.dk), "float32"),
                ("conv", (self.kernel - 1, self.conv_width), dtype))

    def forward(self, x, cache=None):
        b = x.shape[0]
        qkv = M.concat([serving_linear(self.q_proj, x),
                        serving_linear(self.k_proj, x),
                        serving_linear(self.v_proj, x)], axis=-1)
        conv_w = M.concat([self.q_conv, self.k_conv, self.v_conv], axis=-1)
        if cache is None:
            state = jnp.zeros((b, self.heads, self.dv, self.dk), jnp.float32)
            tail = jnp.zeros((b, self.kernel - 1, self.conv_width),
                             qkv._data.dtype)
            valid_len = None
        else:
            state, tail = cache.read()
            valid_len = cache.valid_len
        args = (qkv, self.a_proj(x), self.b_proj(x),
                serving_linear(self.g_proj, x), conv_w, self.A_log,
                self.dt_bias, self.o_norm, state, tail)
        if valid_len is not None:
            args += (valid_len,)
        o, new_state, new_tail = apply(
            _gdn_core, args,
            dict(heads=self.heads, dk=self.dk, dv=self.dv,
                 eps=float(self.eps), beta_scale=self.beta_scale),
            name="gated_delta_mixer")
        y = serving_linear(self.o_proj, o)
        if cache is None:
            return y
        return y, cache.write((new_state._data, new_tail._data))


class OlmoHybridDecoderLayer(nn.Layer):
    def __init__(self, cfg: OlmoHybridConfig, kind: str):
        super().__init__()
        self.kind = kind
        self.mixer = (OlmoLinearAttention(cfg) if kind == LINEAR
                      else OlmoFullAttention(cfg))
        self.post_attention_layernorm = nn.RMSNorm(cfg.hidden_size,
                                                   cfg.rms_norm_eps)
        self.mlp = OlmoMLP(cfg)
        self.post_feedforward_layernorm = nn.RMSNorm(cfg.hidden_size,
                                                     cfg.rms_norm_eps)

    def forward(self, x, cache=None, start_pos=0):
        """``start_pos`` is part of the seam's layer call; no layer of this
        model has positions to apply."""
        if cache is None:
            x = x + self.post_attention_layernorm(self.mixer(x))
            return x + self.post_feedforward_layernorm(self.mlp(x))
        with jax.named_scope("attention"):
            y, new_cache = self.mixer(x, cache=cache)
            x = x + self.post_attention_layernorm(y)
        with jax.named_scope("mlp"):
            x = x + self.post_feedforward_layernorm(self.mlp(x))
        return x, new_cache


class OlmoHybridModel(nn.Layer):
    def __init__(self, cfg: OlmoHybridConfig):
        super().__init__()
        self.cfg = cfg
        self.embed_tokens = nn.Embedding(cfg.vocab_size, cfg.hidden_size)
        self.layers = nn.LayerList([OlmoHybridDecoderLayer(cfg, kind)
                                    for kind in cfg.layer_types])
        self.norm = nn.RMSNorm(cfg.hidden_size, cfg.rms_norm_eps)

    def forward(self, input_ids):
        x = self.embed_tokens(input_ids)
        for layer in self.layers:
            x = layer(x)
        return self.norm(x)


class OlmoHybridForCausalLM(nn.Layer):
    def __init__(self, cfg: OlmoHybridConfig):
        super().__init__()
        self.cfg = cfg
        self.model = OlmoHybridModel(cfg)
        self.lm_head = _linear(cfg.hidden_size, cfg.vocab_size)

    def forward(self, input_ids):
        """Logits ``[b, s, vocab]`` of a whole sequence from position 0."""
        return self.lm_head(self.model(input_ids))

    # ---- the engine<->model seam (models/serving_seam.py)

    def serving_spec(self) -> ServingSpec:
        c = self.cfg
        dtype = str(self.model.embed_tokens.weight._data.dtype)
        layers = tuple(
            KVLayerState(int(c.num_attention_heads), int(c.head_dim))
            if layer.kind == FULL
            else RecurrentLayerState(layer.mixer.state_arrays(dtype))
            for layer in self.model.layers)
        return ServingSpec(vocab_size=int(c.vocab_size),
                           max_positions=int(c.max_position_embeddings),
                           layers=layers, kernels=("gdn_chunk",))

    def serving_embed(self, ids, positions):
        return self.model.embed_tokens(ids)  # no positions to add

    def serving_layers(self):
        return self.model.layers

    def serving_final(self, x):
        return self.model.norm(x)

    def serving_head(self, h_last):
        return self.lm_head(Tensor(h_last[:, None]))._data[:, 0]

    def serving_linears(self):
        out = []
        for li, layer in enumerate(self.model.layers):
            out += [(f"{li}.mixer.{n}", lin)
                    for n, lin in layer.mixer.linears()]
            out += [(f"{li}.mlp.{n}", getattr(layer.mlp, n))
                    for n in ("gate_proj", "up_proj", "down_proj")]
        return out

    def serving_embedding(self):
        return self.model.embed_tokens
