"""Phi-4-mini-flash: the SambaY decoder-hybrid-decoder (arXiv:2507.06607).

Config keys as the public ``config.json`` of
``microsoft/Phi-4-mini-flash-reasoning`` (``model_type: phi4flash``). The
first half of the layers (the self-decoder) alternates Mamba-1 state-space
layers with differential attention over a sliding window; ONE
full-attention layer follows, and the second half (the cross-decoder)
alternates gated memory units, which multiply in the last Mamba layer's scan
output, with cross attention that reads the full-attention layer's K/V. No
positions anywhere; LayerNorm (with bias) before mixer and MLP; a SiLU-gated
MLP after every mixer; the head tied to the token table. The equations, and
what the config does not state, are at the head of
``benchmark/reference/phi4flash.py``.

Layer ``i`` of ``n`` (0-based, ``half = n // 2``):

====================  =========================  ===========================
``i``                 mixer                      per-request state
====================  =========================  ===========================
even, ``<= half``     Mamba-1                    ``"recurrent"``: float32
                                                 ``[d_state, d_inner]`` and
                                                 ``d_conv - 1`` conv rows
odd, ``< half + 1``   window attention           ``"window"``: the last
                                                 ``sliding_window`` K/V
``half + 1``          full attention             ``"kv"``: the paged pool
even, ``> half``      gated memory unit          ``"none"``
odd, ``> half + 1``   cross attention            ``"shared"``: reads layer
                                                 ``half + 1``'s pool
====================  =========================  ===========================

Served through the engine<->model seam (``models/serving_seam.py``). The
differential form runs on ordinary grouped-query attention: a token's K is
stored as ``kv/2`` heads of ``[k_1 | k_2]`` (twice the head width) and V as
``kv/2`` heads of ``[v_1 | v_2]``, and the ``heads`` query heads are handed
over zero-padded to that width, ``[sqrt(2) q_1 | 0]`` and ``[0 | sqrt(2)
q_2]``: four query heads a K/V head give both softmaxes exactly and read each
K/V row once. Layer ``half`` publishes its scan output as the step carry
``"m"``; a prefill runs the layers from ``half + 2`` on each request's last
valid token only, and layer ``half + 1`` narrows itself after its K/V.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp

from .. import nn
from ..core.dispatch import apply
from ..core.tensor import Tensor
from ..nn import functional as F
from ..nn import initializer as I
from ..ops import manipulation as M
from ..ops import selective_scan as ssm
from ..ops.gated_delta import causal_conv
from .serving_seam import (
    KVLayerState,
    RecurrentLayerState,
    ServingSpec,
    SharedKVLayerState,
    StatelessLayerState,
    WindowLayerState,
    last_row,
    masked_attention,
    serving_linear,
)

MAMBA, SWA, FULL, GMU, CROSS = ("mamba", "window_attention",
                                "full_attention", "gmu", "cross_attention")


@dataclass
class Phi4FlashConfig:
    vocab_size: int = 200064
    hidden_size: int = 2560
    intermediate_size: int = 10240
    num_hidden_layers: int = 32
    num_attention_heads: int = 40
    num_key_value_heads: int = 20
    max_position_embeddings: int = 262144
    layer_norm_eps: float = 1e-5
    sliding_window: int = 512
    mb_per_layer: int = 2
    #: the Mamba sizes, the family's defaults (not keys of the config)
    mamba_d_state: int = 16
    mamba_d_conv: int = 4
    mamba_expand: int = 2
    mamba_dt_rank: Optional[int] = None  # None: ceil(hidden_size / 16)

    def __post_init__(self):
        n = int(self.num_hidden_layers)
        if n < 8 or n % 4:
            raise ValueError("num_hidden_layers must be a multiple of 4, at "
                             "least 8: both decoders alternate two kinds")
        if int(self.mb_per_layer) != 2:
            raise ValueError("mb_per_layer must be 2 (a Mamba layer at "
                             "every even index of the self-decoder)")
        h, heads, kv = (int(self.hidden_size), int(self.num_attention_heads),
                        int(self.num_key_value_heads))
        if h % heads or heads % 2 or kv % 2 or (heads // 2) % (kv // 2):
            raise ValueError("differential attention pairs the heads: "
                             "hidden_size must divide into an even number "
                             "of heads, a multiple of the K/V heads")
        if self.mamba_dt_rank is None:
            self.mamba_dt_rank = -(-h // 16)

    @property
    def half(self) -> int:
        """The last Mamba layer: it publishes the memory."""
        return int(self.num_hidden_layers) // 2

    @property
    def head_dim(self) -> int:
        return int(self.hidden_size) // int(self.num_attention_heads)

    @property
    def d_inner(self) -> int:
        return int(self.mamba_expand) * int(self.hidden_size)

    def kind_of(self, i: int) -> str:
        if i % 2 == 0:
            return MAMBA if i <= self.half else GMU
        if i <= self.half:
            return SWA
        return FULL if i == self.half + 1 else CROSS


def phi4flash_tiny(**kw) -> Phi4FlashConfig:
    """Every kind of layer at test widths: three Mamba, two window, one
    full, one gated memory unit, one cross; 8 query heads over 2 stored K/V
    heads (4 to 1, as published)."""
    base = dict(vocab_size=512, hidden_size=64, intermediate_size=128,
                num_hidden_layers=8, num_attention_heads=8,
                num_key_value_heads=4, max_position_embeddings=256,
                sliding_window=8, mamba_d_state=8)
    base.update(kw)
    return Phi4FlashConfig(**base)


def _linear(fan_in: int, fan_out: int, bias: bool = False) -> nn.Linear:
    return nn.Linear(fan_in, fan_out, bias_attr=None if bias else False)


def _arr(t):
    return t._data if isinstance(t, Tensor) else t


class _SequenceView:
    """The cache protocols over one whole sequence from position 0, with no
    cache: what ``forward(input_ids)`` hands the layers. ``window`` None is
    causal attention over everything."""

    last = None
    valid_len = None

    def __init__(self, window=None, state=None, kv=None):
        self.window, self.state, self.kv = window, state, kv

    def update_and_attend(self, q, k, v):
        return self._attend(_arr(q), _arr(k), _arr(v)), \
            _SequenceView(kv=(_arr(k), _arr(v)))

    def reader(self):
        return self

    def attend(self, q):
        return self._attend(_arr(q), *self.kv)

    def _attend(self, qa, ka, va):
        t = jnp.arange(qa.shape[1])
        mask = t[None, :] <= t[:, None]
        if self.window is not None:
            mask &= t[:, None] - t[None, :] < self.window
        return masked_attention(qa, ka, va, mask[None, None])

    def read(self):
        return self.state

    def write(self, new):
        return None


class Phi4FlashMLP(nn.Layer):
    def __init__(self, cfg: Phi4FlashConfig):
        super().__init__()
        self.inter = int(cfg.intermediate_size)
        self.gate_up_proj = _linear(cfg.hidden_size, 2 * self.inter)
        self.down_proj = _linear(self.inter, cfg.hidden_size)

    def forward(self, x):
        gu = serving_linear(self.gate_up_proj, x)
        return serving_linear(
            self.down_proj,
            F.silu(gu[..., :self.inter]) * gu[..., self.inter:])


def _diff_queries(q, pairs: int, hd: int):
    """``[b, s, 2 pairs hd]`` -> ``[b, s, 2 pairs, 2 hd]``: query ``(j,
    s)`` in half ``s`` of a zero row, times sqrt(2) (the kernels divide by
    the square root of the padded width)."""
    b, s = q.shape[:2]
    q = q.reshape(b, s, pairs, 2, hd) * jnp.asarray(math.sqrt(2.0), q.dtype)
    zero = jnp.zeros_like(q[..., 0, :])
    return jnp.stack([jnp.concatenate([q[..., 0, :], zero], -1),
                      jnp.concatenate([zero, q[..., 1, :]], -1)],
                     axis=3).reshape(b, s, 2 * pairs, 2 * hd)


def _diff_combine(o, lq1, lk1, lq2, lk2, gain, *, lam0: float, eps: float):
    """The two softmaxes' outputs ``[b, s, 2 pairs, 2 hd]`` -> ``(1 -
    lam0) RMSNorm(a_1 - lam a_2)`` ``[b, s, pairs 2 hd]``, float32
    inside."""
    f32 = jnp.float32
    b, s, h2, w = o.shape
    a = o.astype(f32).reshape(b, s, h2 // 2, 2, w)
    lam = (jnp.exp(jnp.sum(lq1.astype(f32) * lk1.astype(f32)))
           - jnp.exp(jnp.sum(lq2.astype(f32) * lk2.astype(f32))) + lam0)
    d = a[..., 0, :] - lam * a[..., 1, :]
    d = d * jax.lax.rsqrt(jnp.mean(d * d, -1, keepdims=True) + eps) \
        * gain.astype(f32) * (1.0 - lam0)
    return d.reshape(b, s, (h2 // 2) * w).astype(o.dtype)


class Phi4FlashAttention(nn.Layer):
    """Differential attention; ``kind`` says over what: a window of the
    layer's own K/V, all of its own K/V, or (cross) the K/V of another
    layer, in which case it has no K/V projection."""

    def __init__(self, cfg: Phi4FlashConfig, index: int, kind: str):
        super().__init__()
        h, hd = int(cfg.hidden_size), cfg.head_dim
        self.kind, self.hd = kind, hd
        self.pairs = int(cfg.num_attention_heads) // 2
        self.kv_groups = int(cfg.num_key_value_heads) // 2
        self.lam0 = 0.8 - 0.6 * math.exp(-0.3 * index)
        self.eps = float(cfg.layer_norm_eps)
        self.q_proj = _linear(h, h, bias=True)
        if kind != CROSS:
            self.kv_proj = _linear(h, 4 * self.kv_groups * hd, bias=True)
        self.o_proj = _linear(h, h, bias=True)
        small = I.Normal(0.0, 0.1)
        for name in ("lambda_q1", "lambda_k1", "lambda_q2", "lambda_k2"):
            setattr(self, name, self.create_parameter(
                [hd], default_initializer=small))
        self.subln = self.create_parameter(
            [2 * hd], default_initializer=I.Constant(1.0))

    def linears(self):
        kv = () if self.kind == CROSS else (("kv_proj", self.kv_proj),)
        return (("q_proj", self.q_proj),) + kv + (("o_proj", self.o_proj),)

    def kv(self, x):
        """K and V of ``x`` as they are stored: ``[b, s, kv/2, 2 hd]``."""
        b, s = x.shape[:2]
        kv = serving_linear(self.kv_proj, x)
        shape = [b, s, self.kv_groups, 2 * self.hd]
        half = 2 * self.kv_groups * self.hd
        return (M.reshape(kv[..., :half], shape),
                M.reshape(kv[..., half:], shape))

    def forward(self, x, cache, kv=None):
        """``x`` is the rows that attend; ``kv`` their (or, where a prefill
        narrowed ``x`` to its last row, every row's) K and V."""
        q = apply(_diff_queries, (serving_linear(self.q_proj, x),),
                  dict(pairs=self.pairs, hd=self.hd), name="diff_queries")
        scope = {SWA: "swa", FULL: "kv_attend", CROSS: "cross_attn"}
        with jax.named_scope(scope[self.kind]):
            if self.kind == CROSS:
                o, new_cache = cache.attend(q), None
            else:
                k, v = kv if kv is not None else self.kv(x)
                o, new_cache = cache.update_and_attend(q, k, v)
        o = apply(_diff_combine,
                  (Tensor(_arr(o)), self.lambda_q1, self.lambda_k1,
                   self.lambda_q2, self.lambda_k2, self.subln),
                  dict(lam0=self.lam0, eps=self.eps), name="diff_combine")
        return serving_linear(self.o_proj, o), new_cache


def _mamba_conv(u, conv_w, conv_b, tail, valid_len=None):
    y, new_tail = causal_conv(u, conv_w, tail, valid_len)
    c = jax.nn.silu(y + conv_b.astype(jnp.float32))
    return c.astype(u.dtype), new_tail.astype(tail.dtype)


def _mamba_scan(c, dt, bc, z, a_log, skip, state, valid_len=None, *,
                n_state: int):
    """``c`` [b, s, di] the convolved input, ``dt`` [b, s, di] before its
    softplus, ``bc`` [b, s, 2 N], ``z`` the gate. Returns the scan output
    (the memory), the gated output, and the new state."""
    d = jax.nn.softplus(dt.astype(jnp.float32))
    a = -jnp.exp(a_log.astype(jnp.float32))
    bm, cm = bc[..., :n_state], bc[..., n_state:]
    if c.shape[1] == 1:
        y, new = ssm.selective_step(c[:, 0], d[:, 0], bm[:, 0], cm[:, 0],
                                    a, skip, state)
        y = y[:, None]
    else:
        y, new = ssm.selective_scan(c, d, bm, cm, a, skip, state, valid_len)
    gated = y * jax.nn.silu(z.astype(jnp.float32))
    return y.astype(c.dtype), gated.astype(c.dtype), new


class Phi4FlashMamba(nn.Layer):
    """Mamba-1 mixer; with ``publishes`` its scan output (before the gate)
    goes into the step carry as ``"m"``."""

    def __init__(self, cfg: Phi4FlashConfig, publishes: bool):
        super().__init__()
        h, di = int(cfg.hidden_size), cfg.d_inner
        self.di, self.n = di, int(cfg.mamba_d_state)
        self.rank, self.kernel = int(cfg.mamba_dt_rank), int(cfg.mamba_d_conv)
        self.publishes = publishes
        self.in_proj = _linear(h, 2 * di)
        self.x_proj = _linear(di, self.rank + 2 * self.n)
        self.dt_proj = _linear(self.rank, di, bias=True)
        self.out_proj = _linear(di, h)
        self.conv_w = self.create_parameter([self.kernel, di])
        self.conv_b = self.create_parameter([di], is_bias=True)
        self.A_log = self.create_parameter(
            [di, self.n], default_initializer=I.Constant(0.0))
        self.D = self.create_parameter(
            [di], default_initializer=I.Constant(1.0))

    def linears(self):
        return (("in_proj", self.in_proj), ("x_proj", self.x_proj),
                ("dt_proj", self.dt_proj), ("out_proj", self.out_proj))

    def state_arrays(self, dtype: str):
        """Per-lane state: the scan's state (float32, the channels minor)
        and the last ``d_conv - 1`` rows that went into the convolution."""
        return (("S", (self.n, self.di), "float32"),
                ("conv", (self.kernel - 1, self.di), dtype))

    def forward(self, x, cache, carry):
        state, tail = cache.read()
        tail_args = () if cache.valid_len is None else (cache.valid_len,)
        xz = serving_linear(self.in_proj, x)
        c, new_tail = apply(
            _mamba_conv,
            (xz[..., :self.di], self.conv_w, self.conv_b, tail) + tail_args,
            {}, name="mamba_conv")
        proj = serving_linear(self.x_proj, c)
        dt = serving_linear(self.dt_proj, proj[..., :self.rank])
        y, gated, new_state = apply(
            _mamba_scan,
            (c, dt, proj[..., self.rank:], xz[..., self.di:], self.A_log,
             self.D, state) + tail_args,
            dict(n_state=self.n), name="mamba_scan")
        if self.publishes:
            carry["m"] = y._data
        return serving_linear(self.out_proj, gated), \
            cache.write((new_state._data, new_tail._data))


class Phi4FlashGMU(nn.Layer):
    """Gated memory unit: ``W_2 (silu(W_1 x) * m)``."""

    def __init__(self, cfg: Phi4FlashConfig):
        super().__init__()
        self.in_proj = _linear(cfg.hidden_size, cfg.d_inner)
        self.out_proj = _linear(cfg.d_inner, cfg.hidden_size)

    def linears(self):
        return (("in_proj", self.in_proj), ("out_proj", self.out_proj))

    def forward(self, x, carry):
        with jax.named_scope("gmu"):
            return serving_linear(
                self.out_proj,
                F.silu(serving_linear(self.in_proj, x)) * Tensor(carry["m"]))


class Phi4FlashDecoderLayer(nn.Layer):
    uses_step_carry = True  # the seam hands ``carry=`` to such a layer

    def __init__(self, cfg: Phi4FlashConfig, index: int):
        super().__init__()
        self.kind = kind = cfg.kind_of(index)
        if kind == MAMBA:
            self.mixer = Phi4FlashMamba(cfg, publishes=index == cfg.half)
        elif kind == GMU:
            self.mixer = Phi4FlashGMU(cfg)
        else:
            self.mixer = Phi4FlashAttention(cfg, index, kind)
        self.input_layernorm = nn.LayerNorm(cfg.hidden_size,
                                            cfg.layer_norm_eps)
        self.post_attention_layernorm = nn.LayerNorm(cfg.hidden_size,
                                                     cfg.layer_norm_eps)
        self.mlp = Phi4FlashMLP(cfg)

    def forward(self, x, cache=None, start_pos=0, carry=None):
        """``start_pos`` is part of the seam's layer call; no layer of this
        model has positions to apply."""
        with jax.named_scope("attention"):
            xn = self.input_layernorm(x)
            if self.kind == MAMBA:
                y, new_cache = self.mixer(xn, cache, carry)
            elif self.kind == GMU:
                y, new_cache = self.mixer(xn, carry), None
            else:
                kv = None
                last = getattr(cache, "last", None)
                if self.kind == FULL and last is not None:
                    # a prefill whose later layers see the last row alone:
                    # K and V of every row, the rest of this layer of one
                    kv = self.mixer.kv(xn)
                    x, xn = (Tensor(last_row(t._data, last)) for t in (x, xn))
                y, new_cache = self.mixer(xn, cache, kv)
            x = x + y
        with jax.named_scope("mlp"):
            x = x + self.mlp(self.post_attention_layernorm(x))
        return x, new_cache


class Phi4FlashModel(nn.Layer):
    def __init__(self, cfg: Phi4FlashConfig):
        super().__init__()
        self.cfg = cfg
        self.embed_tokens = nn.Embedding(cfg.vocab_size, cfg.hidden_size)
        self.layers = nn.LayerList(
            [Phi4FlashDecoderLayer(cfg, i)
             for i in range(int(cfg.num_hidden_layers))])
        self.final_layernorm = nn.LayerNorm(cfg.hidden_size,
                                            cfg.layer_norm_eps)


class Phi4FlashForCausalLM(nn.Layer):
    def __init__(self, cfg: Phi4FlashConfig):
        super().__init__()
        self.cfg = cfg
        self.model = Phi4FlashModel(cfg)

    def forward(self, input_ids):
        """Logits ``[b, s, vocab]`` of a whole sequence from position 0:
        the served layers, over views that keep no cache."""
        c, b = self.cfg, input_ids.shape[0]
        x = self.model.embed_tokens(input_ids)
        dtype = x._data.dtype
        views, carry = [], {}
        for i, layer in enumerate(self.model.layers):
            kind = layer.kind
            if kind == MAMBA:
                view = _SequenceView(state=tuple(
                    jnp.zeros((b,) + shape, dt) for _, shape, dt
                    in layer.mixer.state_arrays(str(dtype))))
            elif kind == CROSS:
                view = views[c.half + 1]
            else:
                view = _SequenceView(
                    window=int(c.sliding_window) if kind == SWA else None)
            x, nv = layer(x, cache=view, carry=carry)
            views.append(nv)
        h = self.model.final_layernorm(x)._data
        return Tensor(jnp.einsum("bsh,vh->bsv", h,
                                 self.model.embed_tokens.weight._data))

    # ---- the engine<->model seam (models/serving_seam.py)

    def serving_spec(self) -> ServingSpec:
        c = self.cfg
        dtype = str(self.model.embed_tokens.weight._data.dtype)
        heads, kv = int(c.num_attention_heads), int(c.num_key_value_heads)
        width = 2 * c.head_dim  # a stored K/V head: both halves of a pair

        def state(layer):
            if layer.kind == MAMBA:
                return RecurrentLayerState(layer.mixer.state_arrays(dtype))
            if layer.kind == GMU:
                return StatelessLayerState()
            if layer.kind == SWA:
                return WindowLayerState(heads, width, int(c.sliding_window),
                                        num_kv_heads=kv // 2)
            if layer.kind == FULL:
                return KVLayerState(heads, width, num_kv_heads=kv // 2)
            return SharedKVLayerState(c.half + 1, heads, width)

        return ServingSpec(vocab_size=int(c.vocab_size),
                           max_positions=int(c.max_position_embeddings),
                           layers=tuple(state(la)
                                        for la in self.model.layers),
                           prefill_tail=c.half + 2)

    def serving_embed(self, ids, positions):
        return self.model.embed_tokens(ids)  # no positions to add

    def serving_layers(self):
        return self.model.layers

    def serving_final(self, x):
        return self.model.final_layernorm(x)

    def serving_head(self, h_last):
        return jnp.einsum("bh,vh->bv", h_last,
                          self.model.embed_tokens.weight._data)

    def serving_linears(self):
        out = []
        for li, layer in enumerate(self.model.layers):
            out += [(f"{li}.mixer.{n}", lin)
                    for n, lin in layer.mixer.linears()]
            out += [(f"{li}.mlp.{n}", getattr(layer.mlp, n))
                    for n in ("gate_up_proj", "down_proj")]
        return out

    def serving_embedding(self):
        return self.model.embed_tokens
