"""GPT weights from a seed, made on the device, for the program AND the
reference. Pure jax: imports nothing of paddle_tpu.

One jitted call per group (embeddings, one decoder layer, the final norm),
keyed by (seed, group, layer index), so the program's model can be filled
layer by layer and the reference can make the same layer again without
holding the rest. Values are drawn in float32 and rounded once to ``dtype``
(the type they are served or trained in); the reference upcasts those same
rounded values.

Distribution (GPT-2 style, with non-trivial norms and biases so that a
dropped bias or gain shows in the output): matrices N(0, 0.02), the two
residual-writing matrices N(0, 0.02 / sqrt(2 L)), biases N(0, 0.02), norm
gains 1 + N(0, 0.1), norm biases N(0, 0.1), and the position table
N(0, 0.3). The wide position table is there for the output check: with a
narrow one (0.01) a random tied-head model decoded greedily falls into one
repeated token whose logit leads by two standard deviations, so that no
precision, however low, ever changes a served token (measured on the chip,
PR 23: 0 of 2,156 tokens off the reference's first choice under int8
weights and int8 KV). With 0.3 every position's state differs, the best
two logits lie as close as a Gaussian's (a tenth of the tokens within 2% of
a standard deviation), and what a lower precision does to them shows.

One entry in a thousand of each layer's four matrices is ten times its draw.
Trained models have such outliers, and they are what makes a coarse grid
coarse: a per-channel int8 scale is set by the channel's largest entry.
With plain Gaussian matrices the engine's int8 weights and int8 KV moved the
check's number only threefold against bf16's own rounding (chip readings,
PR 23: 8.4e-4 to 8.8e-4 against 2.8e-4); with the outliers the logits' error
under int8 is some fifteen times bf16's (float32 emulation at the served
widths, CPU, PR 23), so the control stands clear of every sound run.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

#: leaves of one decoder layer, in a fixed order: name -> (shape fn, std fn, mean)
_LAYER = (
    ("ln1_w", lambda c: (c["hidden_size"],), lambda c: 0.1, 1.0),
    ("ln1_b", lambda c: (c["hidden_size"],), lambda c: 0.1, 0.0),
    ("qkv_w", lambda c: (c["hidden_size"], 3 * c["hidden_size"]),
     lambda c: 0.02, 0.0),
    ("qkv_b", lambda c: (3 * c["hidden_size"],), lambda c: 0.02, 0.0),
    ("proj_w", lambda c: (c["hidden_size"], c["hidden_size"]),
     lambda c: 0.02 / math.sqrt(2 * c["num_layers_published"]), 0.0),
    ("proj_b", lambda c: (c["hidden_size"],), lambda c: 0.02, 0.0),
    ("ln2_w", lambda c: (c["hidden_size"],), lambda c: 0.1, 1.0),
    ("ln2_b", lambda c: (c["hidden_size"],), lambda c: 0.1, 0.0),
    ("up_w", lambda c: (c["hidden_size"], c["intermediate_size"]),
     lambda c: 0.02, 0.0),
    ("up_b", lambda c: (c["intermediate_size"],), lambda c: 0.02, 0.0),
    ("down_w", lambda c: (c["intermediate_size"], c["hidden_size"]),
     lambda c: 0.02 / math.sqrt(2 * c["num_layers_published"]), 0.0),
    ("down_b", lambda c: (c["hidden_size"],), lambda c: 0.02, 0.0),
)
LAYER_LEAVES = tuple(n for n, *_ in _LAYER)
EMBED_LEAVES = ("wte", "wpe")
FINAL_LEAVES = ("lnf_w", "lnf_b")


def widths(cfg: dict) -> dict:
    """The sizes the weights depend on, as a hashable-by-items dict.
    ``num_layers_published`` fixes the residual scaling, so a depth cut
    does not change any layer's distribution."""
    return {"vocab_size": int(cfg["vocab_size"]),
            "hidden_size": int(cfg["hidden_size"]),
            "intermediate_size": int(cfg["intermediate_size"]),
            "max_position_embeddings": int(cfg["max_position_embeddings"]),
            "num_layers_published": int(cfg.get("num_layers_published",
                                                cfg["num_layers"]))}


def root_key(seed: int):
    """``--seed`` may pass 2**31: fold the high bits in instead of handing
    jax an integer its 32-bit default cannot hold."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.key(seed & 0x7FFFFFFF), seed >> 31)


#: share of a layer matrix's entries that are outliers, and their factor
OUTLIER_SHARE, OUTLIER_FACTOR = 1e-3, 10.0


def _draw(key, shape, std, mean, dtype, outliers=False):
    x = std * jax.random.normal(key, shape, jnp.float32)
    if outliers:
        hit = jax.random.bernoulli(jax.random.fold_in(key, 1),
                                   OUTLIER_SHARE, shape)
        x = jnp.where(hit, OUTLIER_FACTOR * x, x)
    return (mean + x).astype(dtype)


@functools.partial(jax.jit, static_argnames=("w", "dtype"))
def _layer(key, index, w, dtype):
    c = dict(w)
    key = jax.random.fold_in(jax.random.fold_in(key, 1), index)
    return {name: _draw(jax.random.fold_in(key, j), shape(c), std(c), mean,
                        dtype, outliers=len(shape(c)) == 2)
            for j, (name, shape, std, mean) in enumerate(_LAYER)}


@functools.partial(jax.jit, static_argnames=("w", "dtype"))
def _embed(key, w, dtype):
    c = dict(w)
    key = jax.random.fold_in(key, 0)
    return {"wte": _draw(jax.random.fold_in(key, 0),
                         (c["vocab_size"], c["hidden_size"]), 0.02, 0.0,
                         dtype),
            "wpe": _draw(jax.random.fold_in(key, 1),
                         (c["max_position_embeddings"], c["hidden_size"]),
                         0.3, 0.0, dtype)}


@functools.partial(jax.jit, static_argnames=("w", "dtype"))
def _final(key, w, dtype):
    c = dict(w)
    key = jax.random.fold_in(key, 2)
    return {"lnf_w": _draw(jax.random.fold_in(key, 0), (c["hidden_size"],),
                           0.1, 1.0, dtype),
            "lnf_b": _draw(jax.random.fold_in(key, 1), (c["hidden_size"],),
                           0.1, 0.0, dtype)}


def _static(cfg):
    return tuple(sorted(widths(cfg).items()))


def layer(seed: int, index, cfg: dict, dtype) -> dict:
    """Leaves of decoder layer ``index`` (an int or a traced int32)."""
    return _layer(root_key(seed), jnp.asarray(index, jnp.int32),
                  _static(cfg), jnp.dtype(dtype))


def embed(seed: int, cfg: dict, dtype) -> dict:
    return _embed(root_key(seed), _static(cfg), jnp.dtype(dtype))


def final(seed: int, cfg: dict, dtype) -> dict:
    return _final(root_key(seed), _static(cfg), jnp.dtype(dtype))


@functools.partial(jax.jit, static_argnames=("w", "dtype", "n"))
def _stacked(key, w, dtype, n):
    return jax.vmap(lambda i: _layer(key, i, w, dtype))(
        jnp.arange(n, dtype=jnp.int32))


def stacked_layers(seed: int, cfg: dict, dtype) -> dict:
    """All ``cfg['num_layers']`` layers, each leaf stacked on a leading
    axis: the same values as ``layer(seed, i, ...)`` for each i."""
    return _stacked(root_key(seed), _static(cfg), jnp.dtype(dtype),
                    int(cfg["num_layers"]))
