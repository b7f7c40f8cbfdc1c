"""Keye-VL-2.0's language model (``model_type: KeyeVL2``): grouped-query
attention that reads, for every query, only the ``topk`` earlier tokens a
learned indexer picks, and softmax-routed experts with no shared one.

Config keys as the public ``config.json`` of
``Kwai-Keye/Keye-VL-2.0-30B-A3B`` (``sa_config`` the indexer's). The
equations, and what the config does not state, are at the head of
``benchmark/reference/keye.py``. One layer (``N`` RMSNorm with a gain; ``x``
the residual stream; ``t`` a query's position, ``s <= t`` a key's)::

    h   = N_in(x)
    q   = rot_t(N_q(h Wq))   k = rot_s(N_k(h Wk))   v = h Wv  # N_q, N_k a head
    qI  = rot_t(h WqI)       kI = rot_s(LN(h WkI))   w = h Ww     # the indexer
    I[t, s] = (heads_I d_I)^-1/2 sum_j w[t, j] relu(qI[t, j] . kI[s])
    S_t = the topk positions s <= t of largest I[t, s]
    a   = softmax-attention of q over k, v at S_t alone
    x1  = x + a Wo
    u   = N_post(x1);  p = softmax(u Wr);  E = the 8 largest
    g   = p[E] / sum p[E]
    y   = x1 + sum_{e in E, held here} g_e SwiGLU_e(u)

What is specific to the served form:

* **One kind of cache, three rows a token.** ``serving_spec()`` declares a
  :class:`~paddle_tpu.models.serving_seam.SparseKVLayerState` for every
  layer: K and V rows and one index key, in the paged arena. The layer
  hands its view everything a token brings and the view scores, selects and
  attends (``select_and_attend``).
* **The model applies rotary**, to queries, keys, index queries and the
  index key, at ``start_pos +`` the token's index (a scalar in a prefill,
  one a lane in the decode step). One position id a token: the three
  ``mrope_section`` axes coincide and the rotary is plain.
* **The residual stream is float32** between embed and final norm (the
  sublayers run in the weights' dtype). Both discrete choices read it
  unrounded: the router scores ``N_post(x1)`` and the indexer's head
  weights ``w`` are ``N_in(x) Ww``, in float32 at full precision.
* **Experts** (:mod:`paddle_tpu.ops.grouped_matmul`): softmax over all
  ``num_experts`` columns in float32, the ``num_experts_per_tok`` largest,
  their probabilities renormalized to sum 1 (``norm_topk_prob``). A chip is
  told which experts it holds (``expert_first``, ``expert_count``): it
  routes over all of them, moves the rows its own experts take and drops no
  token.
* **Counters** (a decode step's, over the lanes that hold a request): the
  expert layers' ``moe.*``; ``sparse.rows_live`` (K/V rows a dense read
  would have touched: ``position + 1``) and ``sparse.layer_steps``, which
  the layer states; ``sparse.rows_read`` (rows of K and of V the attention
  was handed: ``min(position + 1, topk)`` a lane) and
  ``sparse.index_rows_scored`` (live index keys), which the VIEW counts
  where it reads (``SparseDecodeView.counts``) and the layer passes on.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from .. import nn
from ..core.tensor import Tensor
from ..nn import initializer as I
from ..ops import grouped_matmul as gm
from ..ops import sparse_attention as sa
from .longcat_flash import _stream
from .serving_seam import (ServingSpec, SparseKVLayerState,
                           add_step_counters, serving_linear)
from .trinity import _rotary
from .xing4 import F32, _arr, _linear, _positions, _rms, _weight


def _sa_default():
    return {"indexer_head_dim": 64, "indexer_num_heads": 16,
            "indexer_num_kv_heads": 1, "kv_chunk_size": 512,
            "q_chunk_size": 512, "topk": 2048}


@dataclass
class KeyeConfig:
    vocab_size: int = 151936
    hidden_size: int = 2048
    moe_intermediate_size: int = 768
    num_hidden_layers: int = 48
    num_attention_heads: int = 32
    num_key_value_heads: int = 4
    head_dim: int = 128
    num_experts: int = 128
    num_experts_per_tok: int = 8
    norm_topk_prob: bool = True
    rms_norm_eps: float = 1e-6
    rope_theta: float = 1e7
    max_position_embeddings: int = 262144
    #: the indexer: ``indexer_num_heads`` queries of ``indexer_head_dim``
    #: over ONE key head, the ``topk`` best tokens kept
    sa_config: dict = field(default_factory=_sa_default)
    #: the experts this chip holds of every layer (None: all)
    expert_first: int = 0
    expert_count: Optional[int] = None

    def __post_init__(self):
        self.sa_config = {**_sa_default(), **dict(self.sa_config)}
        if int(self.sa_config["indexer_num_kv_heads"]) != 1:
            raise ValueError("one index key head is what the layer keeps")
        if int(self.num_attention_heads) % int(self.num_key_value_heads):
            raise ValueError("the query heads are a multiple of the K/V "
                             "heads")
        if self.expert_count is None:
            self.expert_count = int(self.num_experts) - self.expert_first
        if not 0 <= self.expert_first <= self.expert_first \
                + self.expert_count <= int(self.num_experts):
            raise ValueError("the experts held are a range of those routed")

    @property
    def index_dim(self) -> int:
        return int(self.sa_config["indexer_head_dim"])

    @property
    def index_heads(self) -> int:
        return int(self.sa_config["indexer_num_heads"])

    @property
    def topk(self) -> int:
        return int(self.sa_config["topk"])


def keye_tiny(**kw) -> KeyeConfig:
    """Three layers at test widths: 4 query heads over 2 K/V heads of 16,
    an indexer of 8 heads of 8 that keeps 8 tokens (eight heads: with two,
    a quarter of all index scores are the exact zero every ``relu`` left,
    and rows whose eighth score ties with its ninth are common), 8 experts
    of which 2 a token."""
    base = dict(vocab_size=512, hidden_size=64, moe_intermediate_size=32,
                num_hidden_layers=3, num_attention_heads=4,
                num_key_value_heads=2, head_dim=16, num_experts=8,
                num_experts_per_tok=2, max_position_embeddings=256,
                sa_config={"indexer_head_dim": 8, "indexer_num_heads": 8,
                           "topk": 8})
    base.update(kw)
    return KeyeConfig(**base)


def rotary_frequencies(theta: float, dim: int):
    """Plain rotary over ``dim`` values: ``inv_freq [dim / 2]``."""
    inv = float(theta) ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)
    return np.asarray(inv, np.float32)


def _layer_norm(x, gain, bias, eps: float, dtype):
    xf = x.astype(F32)
    mu = jnp.mean(xf, -1, keepdims=True)
    var = jnp.mean(jnp.square(xf - mu), -1, keepdims=True)
    return ((xf - mu) * jax.lax.rsqrt(var + eps) * gain.astype(F32)
            + bias.astype(F32)).astype(dtype)


class _SparseSequenceView:
    """The ``"sparse"`` protocol over one whole sequence from position 0,
    with no cache: what ``forward(input_ids)`` hands the layers."""

    def __init__(self, topk: int):
        self.topk = int(topk)

    def select_and_attend(self, q, k, v, qi, ki, w):
        qa, ka, va, qia, kia, wa = (_arr(a)[0] for a in (q, k, v, qi, ki, w))
        tau = sa.index_thresholds(qia, kia, wa, self.topk)
        return sa.sparse_prefill_attention(qa, ka, va, qia, kia, wa,
                                           tau)[None], None


class KeyeAttention(nn.Layer):
    """Grouped-query attention with a norm a head on queries and keys and
    rotary on both, under an indexer (``index_heads`` queries, one key and
    ``index_heads`` head weights a token) whose scores choose the tokens a
    query attends; over a ``"sparse"`` cache view."""

    def __init__(self, cfg: KeyeConfig):
        super().__init__()
        h, d = int(cfg.hidden_size), int(cfg.head_dim)
        self.heads, self.kv_heads = (int(cfg.num_attention_heads),
                                     int(cfg.num_key_value_heads))
        self.d, self.di, self.hi = d, cfg.index_dim, cfg.index_heads
        self.topk = cfg.topk
        self.eps = float(cfg.rms_norm_eps)
        self.index_scale = float(self.hi * self.di) ** -0.5
        self.inv_freq = rotary_frequencies(cfg.rope_theta, d)
        self.index_inv_freq = rotary_frequencies(cfg.rope_theta, self.di)
        self.q_proj = _linear(h, self.heads * d)
        self.k_proj = _linear(h, self.kv_heads * d)
        self.v_proj = _linear(h, self.kv_heads * d)
        self.o_proj = _linear(self.heads * d, h)
        self.iq_proj = _linear(h, self.hi * self.di)
        self.ik_proj = _linear(h, self.di)
        # the head weights make a discrete choice, as a router does: a
        # parameter multiplied in float32, not a served linear
        self.iw = self.create_parameter([h, self.hi])
        one, zero = I.Constant(1.0), I.Constant(0.0)
        self.q_norm = self.create_parameter([d], default_initializer=one)
        self.k_norm = self.create_parameter([d], default_initializer=one)
        self.ik_norm = self.create_parameter([self.di],
                                             default_initializer=one)
        self.ik_bias = self.create_parameter([self.di],
                                             default_initializer=zero)

    def linears(self):
        return tuple((n, getattr(self, n)) for n in (
            "q_proj", "k_proj", "v_proj", "o_proj", "iq_proj", "ik_proj"))

    def index_inputs(self, x, x32, pos):
        """What the indexer brings for the tokens ``x`` ``[b, s, h]`` at
        ``pos`` ``[b, s]``: ``(qI [b, s, heads_I, d_I], kI [b, s, d_I], w
        [b, s, heads_I] float32, the scale folded in)``; ``x32`` is ``x``
        before it was rounded to the weights' dtype, which ``w`` reads."""
        b, s = x.shape[:2]
        qi = serving_linear(self.iq_proj, x)._data.reshape(b, s, self.hi,
                                                           self.di)
        ki = serving_linear(self.ik_proj, x)._data
        ki = _layer_norm(ki, self.ik_norm._data, self.ik_bias._data,
                         self.eps, ki.dtype)
        qi = _rotary(qi, pos, self.index_inv_freq)
        ki = _rotary(ki[:, :, None], pos, self.index_inv_freq)[:, :, 0]
        w = jnp.matmul(x32, self.iw._data.astype(F32),
                       precision=jax.lax.Precision.HIGHEST)
        return qi, ki, w * self.index_scale

    def forward(self, x, x32, cache, start_pos=0, carry=None):
        b, s = x.shape[:2]
        d = self.d
        pos = _positions(start_pos, b, s)
        q = serving_linear(self.q_proj, x)._data.reshape(b, s, self.heads, d)
        k = serving_linear(self.k_proj, x)._data.reshape(b, s, self.kv_heads,
                                                         d)
        v = serving_linear(self.v_proj, x)._data.reshape(b, s, self.kv_heads,
                                                         d)
        q = _rotary(_rms(q, self.q_norm._data, self.eps), pos, self.inv_freq)
        k = _rotary(_rms(k, self.k_norm._data, self.eps), pos, self.inv_freq)
        with jax.named_scope("indexer"):
            qi, ki, w = self.index_inputs(x, x32, pos)
        a, new_cache = cache.select_and_attend(
            Tensor(q), Tensor(k), Tensor(v), Tensor(qi), Tensor(ki),
            Tensor(w))
        if carry is not None and "lanes" in carry:  # a decode step
            lanes = carry["lanes"].reshape(-1)
            add_step_counters(carry, {
                "sparse.rows_live": jnp.sum(
                    jnp.where(lanes, pos[:, 0] + 1, 0), dtype=jnp.int32),
                "sparse.layer_steps": jnp.int32(1),
                # what the view scored and read, counted where it reads
                **new_cache.counts})
        a = _arr(a).reshape(b, s, self.heads * d).astype(q.dtype)
        return serving_linear(self.o_proj, Tensor(a)), new_cache


class KeyeMoE(nn.Layer):
    """The expert layer, or the share of it that holds the experts
    ``cfg.expert_first .. + cfg.expert_count``: routes over all
    ``num_experts``; no shared expert."""

    def __init__(self, cfg: KeyeConfig):
        super().__init__()
        h, w = int(cfg.hidden_size), int(cfg.moe_intermediate_size)
        self.experts, self.k = (int(cfg.num_experts),
                                int(cfg.num_experts_per_tok))
        self.first, self.count = int(cfg.expert_first), int(cfg.expert_count)
        self.normalize = bool(cfg.norm_topk_prob)
        self.router = self.create_parameter([h, self.experts])
        self.e_up = self.create_parameter([self.count, h, 2 * w])
        self.e_down = self.create_parameter([self.count, w, h])

    def route(self, flat):
        """Softmax over every column in float32, the ``k`` largest, their
        probabilities renormalized where ``norm_topk_prob``."""
        idx, w = gm.route_softmax_topk(
            flat, self.router._data, jnp.zeros((self.experts,), F32), self.k,
            1.0)
        if self.normalize:
            w = w / jnp.sum(w, -1, keepdims=True)
        return idx, w

    def forward(self, x, carry=None, x32=None):
        """``x32``: the layer's input before it was rounded to the
        weights' dtype, which the router scores. -> float32."""
        b, s, h = x.shape
        flat = x._data.reshape(b * s, h)
        idx, w = self.route(flat if x32 is None else x32.reshape(b * s, h))
        y = gm.expert_ffn(flat, idx, w, self.e_up._data, self.e_down._data,
                          self.experts, self.first)
        if carry is not None and "lanes" in carry:  # a decode step
            add_step_counters(carry, gm.load_counters(
                idx, self.experts, rows=carry["lanes"].reshape(-1),
                first=self.first, held=self.count))
        return y.reshape(b, s, h)


class KeyeDecoderLayer(nn.Layer):
    uses_step_carry = True  # the layers add to the step's counters

    def __init__(self, cfg: KeyeConfig):
        super().__init__()
        h, one = int(cfg.hidden_size), I.Constant(1.0)
        self.eps = float(cfg.rms_norm_eps)
        self.attn = KeyeAttention(cfg)
        self.input_norm = self.create_parameter([h], default_initializer=one)
        self.post_attn_norm = self.create_parameter([h],
                                                    default_initializer=one)
        self.mlp = KeyeMoE(cfg)

    def forward(self, x, cache=None, start_pos=0, carry=None):
        X = x._data                                   # [b, s, h] float32
        dtype = self.input_norm._data.dtype           # the weights' dtype
        h32 = _rms(X, self.input_norm._data, self.eps, F32)
        y, new_cache = self.attn(Tensor(h32.astype(dtype)), h32, cache,
                                 start_pos, carry)
        X = _stream(X + y._data.astype(F32))
        u32 = _rms(X, self.post_attn_norm._data, self.eps, F32)
        with jax.named_scope("moe"):
            m = self.mlp(Tensor(u32.astype(dtype)), carry, u32)
        return Tensor(_stream(X + m)), new_cache


class KeyeModel(nn.Layer):
    def __init__(self, cfg: KeyeConfig):
        super().__init__()
        self.cfg = cfg
        self.embed_tokens = nn.Embedding(cfg.vocab_size, cfg.hidden_size)
        self.layers = nn.LayerList(
            [KeyeDecoderLayer(cfg)
             for _ in range(int(cfg.num_hidden_layers))])
        self.norm = self.create_parameter(
            [int(cfg.hidden_size)], default_initializer=I.Constant(1.0))


class KeyeForCausalLM(nn.Layer):
    def __init__(self, cfg: KeyeConfig):
        super().__init__()
        self.cfg = cfg
        self.model = KeyeModel(cfg)
        self.lm_head = _linear(cfg.hidden_size, cfg.vocab_size)  # untied

    def forward(self, input_ids):
        """Logits ``[b, s, vocab]`` float32 of whole sequences from
        position 0: the served layers over views that keep no cache."""
        x = self.serving_embed(input_ids, 0)
        rows = []
        for r in range(x.shape[0]):  # the view takes one sequence
            xr = Tensor(x._data[r:r + 1])
            for layer in self.model.layers:
                xr, _ = layer(xr, cache=_SparseSequenceView(self.cfg.topk))
            rows.append(self.serving_final(xr)._data)
        return Tensor(self._logits(jnp.concatenate(rows, 0)))

    def _logits(self, h):
        return jnp.matmul(h, _weight(self.lm_head).astype(h.dtype),
                          preferred_element_type=F32)

    # ---- the engine<->model seam (models/serving_seam.py)

    def serving_spec(self) -> ServingSpec:
        c = self.cfg
        state = SparseKVLayerState(
            int(c.num_attention_heads), int(c.head_dim),
            int(c.num_key_value_heads), c.index_dim, c.index_heads, c.topk)
        return ServingSpec(
            vocab_size=int(c.vocab_size),
            max_positions=int(c.max_position_embeddings),
            layers=(state,) * int(c.num_hidden_layers))

    def serving_embed(self, ids, positions):
        """The token's embedding in float32 (positions are the layers' to
        apply)."""
        return Tensor(self.model.embed_tokens(ids)._data.astype(F32))

    def serving_layers(self):
        return self.model.layers

    def serving_final(self, x):
        dtype = self.model.embed_tokens.weight._data.dtype
        return Tensor(_rms(_arr(x), self.model.norm._data,
                           float(self.cfg.rms_norm_eps), dtype))

    def serving_head(self, h_last):
        return self._logits(h_last)

    def serving_linears(self):
        return [(f"{li}.attn.{n}", lin)
                for li, layer in enumerate(self.model.layers)
                for n, lin in layer.attn.linears()]

    def serving_embedding(self):
        return self.model.embed_tokens
