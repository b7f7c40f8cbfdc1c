"""Phi-4-mini-flash weights from a seed, made on the device, for the program
AND the reference. Pure jax: imports nothing of paddle_tpu.

One jitted call per group (the token table, one decoder layer, the final
norm), keyed by (seed, group, layer index), as ``weights/olmo_hybrid.py``:
the program's model is filled layer by layer and the reference makes the same
layer again without holding the rest. Values are drawn in float32 and rounded
once to ``dtype``; the reference upcasts those same rounded values. The kind
of layer ``index`` is :func:`kind_of` (the table at the head of
``benchmark/reference/phi4flash.py``).

Distribution (each choice is in the configuration file's ``assumed``):
matrices N(0, 1/fan_in) (at the published widths a standard deviation of
0.020 from the hidden size, 0.014 from ``d_inner``, 0.0099 from the MLP
width; by the fan-in so that the tests' tiny widths keep every path as
strong as the published ones do: at a flat 0.02 a 128-wide ``x_proj`` gives
``B`` and ``C`` so small that the scan's state hardly reaches the logits),
one entry in a thousand of each large matrix ten times its draw
(``weights/gpt.py`` says why); their biases N(0, 0.02); LayerNorm gains 1 +
N(0, 0.1) and biases N(0, 0.1); the token table N(0, 1/hidden) (0.020): the
head is tied to it, and a wider table makes the token just read its own
successor (its logit is ``|e|^2`` over the residual's scale). The Mamba
leaves as the public Mamba code initialises them: ``A_log = log(1..N)`` in
every channel, ``D`` = 1, ``dt`` log-uniform in [1e-3, 1e-1] inverted
through softplus into the ``dt`` bias, the ``dt`` matrix U(-r^-1/2, r^-1/2)
for rank ``r``, convolution and its bias U(-1/2, 1/2) (``1/sqrt(K)``, K = 4).
The differential attention's four ``lambda`` vectors N(0, 0.1), its
sub-layer gain 1 + N(0, 0.1).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from benchmark.weights.gpt import root_key
from benchmark.weights.olmo_hybrid import _normal

MAMBA, SWA, FULL, GMU, CROSS = ("mamba", "window_attention",
                                "full_attention", "gmu", "cross_attention")


def sizes(cfg: dict) -> dict:
    """Every size the layers depend on: the configuration's keys, and the
    Mamba sizes it does not state at the family's defaults."""
    h = int(cfg["hidden_size"])
    out = {k: int(cfg[k]) for k in (
        "vocab_size", "hidden_size", "intermediate_size",
        "num_hidden_layers", "num_attention_heads", "num_key_value_heads",
        "sliding_window")}
    out["d_state"] = int(cfg.get("mamba_d_state", 16))
    out["d_conv"] = int(cfg.get("mamba_d_conv", 4))
    out["d_inner"] = int(cfg.get("mamba_expand", 2)) * h
    out["dt_rank"] = int(cfg.get("mamba_dt_rank") or -(-h // 16))
    out["head_dim"] = h // out["num_attention_heads"]
    return out


def kind_of(cfg: dict, index: int) -> str:
    half = int(cfg["num_hidden_layers"]) // 2
    if index % 2 == 0:
        return MAMBA if index <= half else GMU
    if index <= half:
        return SWA
    return FULL if index == half + 1 else CROSS


def _leaves(c: dict, kind: str):
    """name -> (shape, how it is drawn), in a fixed order."""
    h, inter, di = c["hidden_size"], c["intermediate_size"], c["d_inner"]
    hd, kv = c["head_dim"], c["num_key_value_heads"]
    n, k, r = c["d_state"], c["d_conv"], c["dt_rank"]
    big = lambda i, o: ((i, o), ("matrix", i ** -0.5))
    bias = lambda o: ((o,), ("plain", 0.02))
    out = {"ln1_w": ((h,), ("gain",)), "ln1_b": ((h,), ("plain", 0.1))}
    if kind == MAMBA:
        out.update({
            "in_w": big(h, 2 * di),
            "conv_w": ((k, di), ("uniform", 1.0 / math.sqrt(k))),
            "conv_b": ((di,), ("uniform", 1.0 / math.sqrt(k))),
            "x_w": big(di, r + 2 * n),
            "dt_w": ((r, di), ("uniform", r ** -0.5)),
            "dt_b": ((di,), ("dt_bias",)),
            "A_log": ((di, n), ("A_log",)), "D": ((di,), ("ones",)),
            "out_w": big(di, h)})
    elif kind == GMU:
        out.update({"in_w": big(h, di), "out_w": big(di, h)})
    else:
        out.update({"q_w": big(h, h), "q_b": bias(h)})
        if kind != CROSS:
            out.update({"kv_w": big(h, 2 * kv * hd), "kv_b": bias(2 * kv * hd)})
        out.update({"o_w": big(h, h), "o_b": bias(h),
                    "lq1": ((hd,), ("plain", 0.1)),
                    "lk1": ((hd,), ("plain", 0.1)),
                    "lq2": ((hd,), ("plain", 0.1)),
                    "lk2": ((hd,), ("plain", 0.1)),
                    "subln": ((2 * hd,), ("gain",))})
    out.update({"ln2_w": ((h,), ("gain",)), "ln2_b": ((h,), ("plain", 0.1)),
                "up_w": big(h, 2 * inter), "down_w": big(inter, h)})
    return out


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
    if what == "ones":
        return jnp.ones(shape, dtype)
    if what == "A_log":
        return jnp.broadcast_to(
            jnp.log(jnp.arange(1, shape[1] + 1, dtype=jnp.float32)),
            shape).astype(dtype)
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
                             (c["vocab_size"], c["hidden_size"]),
                             c["hidden_size"] ** -0.5, dtype)}


@functools.partial(jax.jit, static_argnames=("w", "dtype"))
def _final(key, w, dtype):
    c = dict(w)
    key = jax.random.fold_in(key, 2)
    return {"norm_w": _normal(jax.random.fold_in(key, 0),
                              (c["hidden_size"],), 0.1, dtype, mean=1.0),
            "norm_b": _normal(jax.random.fold_in(key, 1),
                              (c["hidden_size"],), 0.1, dtype)}


def _static(cfg):
    return tuple(sorted(sizes(cfg).items()))


def layer(seed: int, index: int, cfg: dict, dtype) -> dict:
    """Leaves of decoder layer ``index``, of the kind its index gives it."""
    return _layer(root_key(seed), jnp.asarray(index, jnp.int32),
                  _static(cfg), jnp.dtype(dtype), kind_of(cfg, index))


def embed(seed: int, cfg: dict, dtype) -> dict:
    return _embed(root_key(seed), _static(cfg), jnp.dtype(dtype))


def final(seed: int, cfg: dict, dtype) -> dict:
    return _final(root_key(seed), _static(cfg), jnp.dtype(dtype))


def all_weights(seed: int, cfg: dict, dtype) -> dict:
    """The whole model as the reference's ``logits`` takes it (small sizes:
    the tests)."""
    return {"embed": embed(seed, cfg, dtype),
            "layers": [layer(seed, i, cfg, dtype)
                       for i in range(int(cfg["num_hidden_layers"]))],
            "final": final(seed, cfg, dtype)}
