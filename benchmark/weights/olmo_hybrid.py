"""Olmo-Hybrid weights from a seed, made on the device, for the program AND
the reference. Pure jax: imports nothing of paddle_tpu.

One jitted call per group (the token table, one decoder layer, the final norm
with the head), keyed by (seed, group, layer index), so the program's model
can be filled layer by layer and the reference can make the same layer again
without holding the rest. Values are drawn in float32 and rounded once to
``dtype`` (the type they are served in); the reference upcasts those same
rounded values. The kind of layer ``index`` is ``cfg["layer_types"][index]``.

Distribution (each choice is in the configuration file's ``assumed``):
matrices N(0, 0.02), with one entry in a thousand of each layer's large
matrices ten times its draw (trained models have such outliers, and they are
what makes a per-channel int8 grid coarse: ``weights/gpt.py``); the token
table N(0, 1), so that a token's identity weighs as much in the residual
stream as one normalized sub-block's output (the model has no positions to
tell rows apart: what does is the recurrent state, the convolution and the
causal mask); norm gains 1 + N(0, 0.1); the convolutions U(-1/sqrt(K),
1/sqrt(K)); the decay gate as the public Gated DeltaNet implementations
initialise it: ``A`` uniform in [1, 16] (``A_log = log A``), ``dt``
log-uniform in [1e-3, 1e-1] inverted through softplus into ``dt_bias``, and
its input projection ``a_w`` N(0, 0.002) so that ``dt_bias`` sets the scale
of the decay and the token modulates it.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from benchmark.weights.gpt import OUTLIER_FACTOR, OUTLIER_SHARE, root_key

LINEAR, FULL = "linear_attention", "full_attention"


def widths(cfg: dict) -> dict:
    """The sizes the weights depend on (depth is not among them: no
    layer's distribution changes with a depth cut)."""
    return {k: int(cfg[k]) for k in (
        "vocab_size", "hidden_size", "intermediate_size",
        "linear_num_key_heads", "linear_key_head_dim",
        "linear_value_head_dim", "linear_conv_kernel_dim")}


def kind_of(cfg: dict, index: int) -> str:
    return cfg["layer_types"][int(index)]


def _normal(key, shape, std, dtype, mean=0.0, outliers=False):
    x = std * jax.random.normal(key, shape, jnp.float32)
    if outliers:
        hit = jax.random.bernoulli(jax.random.fold_in(key, 1),
                                   OUTLIER_SHARE, shape)
        x = jnp.where(hit, OUTLIER_FACTOR * x, x)
    return (mean + x).astype(dtype)


def _leaves(c: dict, kind: str):
    """name -> (shape, how it is drawn), in a fixed order."""
    h, inter = c["hidden_size"], c["intermediate_size"]
    big = lambda i, o: ((i, o), ("matrix", 0.02))
    gain = lambda n: ((n,), ("gain",))
    if kind == LINEAR:
        heads, dk, dv = (c["linear_num_key_heads"], c["linear_key_head_dim"],
                         c["linear_value_head_dim"])
        k = c["linear_conv_kernel_dim"]
        conv = lambda n: ((k, n), ("uniform", 1.0 / math.sqrt(k)))
        mixer = {
            "q_w": big(h, heads * dk), "k_w": big(h, heads * dk),
            "v_w": big(h, heads * dv), "g_w": big(h, heads * dv),
            "a_w": ((h, heads), ("plain", 0.002)),
            "b_w": ((h, heads), ("plain", 0.02)),
            "o_w": big(heads * dv, h),
            "q_conv": conv(heads * dk), "k_conv": conv(heads * dk),
            "v_conv": conv(heads * dv),
            "A_log": ((heads,), ("A_log",)),
            "dt_bias": ((heads,), ("dt_bias",)),
            "o_norm": gain(dv)}
    else:
        mixer = {"q_w": big(h, h), "k_w": big(h, h), "v_w": big(h, h),
                 "o_w": big(h, h), "q_norm": gain(h), "k_norm": gain(h)}
    mixer.update({"post_attn_norm": gain(h), "gate_w": big(h, inter),
                  "up_w": big(h, inter), "down_w": big(inter, h),
                  "post_ffn_norm": gain(h)})
    return mixer


def _draw(key, shape, how, dtype):
    what = how[0]
    if what == "matrix":
        return _normal(key, shape, how[1], dtype, outliers=True)
    if what == "plain":
        return _normal(key, shape, how[1], dtype)
    if what == "gain":
        return _normal(key, shape, 0.1, dtype, mean=1.0)
    if what == "uniform":
        return jax.random.uniform(key, shape, jnp.float32, -how[1],
                                  how[1]).astype(dtype)
    if what == "A_log":
        return jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0,
                                          16.0)).astype(dtype)
    # dt log-uniform in [1e-3, 1e-1], inverted through softplus
    dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32,
                                    math.log(1e-3), math.log(1e-1)))
    return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)


@functools.partial(jax.jit, static_argnames=("w", "dtype", "kind"))
def _layer(key, index, w, dtype, kind):
    key = jax.random.fold_in(jax.random.fold_in(key, 1), index)
    return {name: _draw(jax.random.fold_in(key, j), shape, how, dtype)
            for j, (name, (shape, how)) in enumerate(
                _leaves(dict(w), kind).items())}


@functools.partial(jax.jit, static_argnames=("w", "dtype"))
def _embed(key, w, dtype):
    c = dict(w)
    return {"embed": _normal(jax.random.fold_in(key, 0),
                             (c["vocab_size"], c["hidden_size"]), 1.0,
                             dtype)}


@functools.partial(jax.jit, static_argnames=("w", "dtype"))
def _final(key, w, dtype):
    c = dict(w)
    key = jax.random.fold_in(key, 2)
    return {"norm": _normal(jax.random.fold_in(key, 0), (c["hidden_size"],),
                            0.1, dtype, mean=1.0),
            "head": _normal(jax.random.fold_in(key, 1),
                            (c["hidden_size"], c["vocab_size"]), 0.02,
                            dtype)}


def _static(cfg):
    return tuple(sorted(widths(cfg).items()))


def layer(seed: int, index: int, cfg: dict, dtype) -> dict:
    """Leaves of decoder layer ``index``, of the kind ``layer_types``
    gives it."""
    return _layer(root_key(seed), jnp.asarray(index, jnp.int32),
                  _static(cfg), jnp.dtype(dtype), kind_of(cfg, index))


def embed(seed: int, cfg: dict, dtype) -> dict:
    return _embed(root_key(seed), _static(cfg), jnp.dtype(dtype))


def final(seed: int, cfg: dict, dtype) -> dict:
    return _final(root_key(seed), _static(cfg), jnp.dtype(dtype))


def all_weights(seed: int, cfg: dict, dtype) -> dict:
    """The whole model as the references' ``logits`` take it (small sizes:
    the tests)."""
    return {"embed": embed(seed, cfg, dtype),
            "layers": [layer(seed, i, cfg, dtype)
                       for i in range(int(cfg["num_hidden_layers"]))],
            "final": final(seed, cfg, dtype)}
