"""Keye-VL-2.0 language-model weights from a seed, made on the device, for
the program AND the reference. Pure jax: imports nothing of paddle_tpu.

One jitted call per group (the token table, one decoder layer, the final
norm and head), keyed by (seed, group, layer index), as
``weights/trinity.py``: the program's model is filled layer by layer and the
reference makes the same layer again without holding the rest. Values are
drawn in float32 and rounded once to ``dtype``; the reference upcasts those
same rounded values.

**The share.** The configuration's ``num_experts`` counts the routed
experts HELD here, from ``expert_first`` on; ``published.num_experts``
(where the file has it) is how many the router routes over. Each routed
expert is drawn from a key of its own index among all of them, so a share
holds the very experts the whole layer would hold there. ``e_up`` ``[held,
hidden, 2 width]`` (``[gate | up]``), ``e_down`` ``[held, width, hidden]``.

A layer's leaves: ``input_norm``, ``q_proj``, ``k_proj``, ``v_proj``,
``o_proj``, ``q_norm``, ``k_norm`` (one gain a value of the head); the
indexer's ``iq_proj`` ``[hidden, heads_I d_I]``, ``ik_proj`` ``[hidden,
d_I]``, ``iw`` ``[hidden, heads_I]``, ``ik_norm`` and ``ik_bias`` (the
LayerNorm on the index key); ``post_attn_norm``; ``router`` ``[hidden,
routed]``, ``e_up``, ``e_down``. Matrices lie ``[in, out]``. Every layer has
the same leaves.

Distribution (each choice is in the configuration file's ``assumed``):
matrices N(0, 1/fan_in), one entry in a thousand of each ten times its draw
(``weights/gpt.py`` says why); RMSNorm and LayerNorm gains 1 + N(0, 0.1),
the LayerNorm's bias N(0, 0.1); the token table N(0, 1) (unit mean square,
the size a sublayer's output adds to the stream); the head N(0, 1/hidden).
Queries and keys leave their norms at unit mean square, so the attention's
scores spread by 1. **The indexer**: ``iq_proj``, ``ik_proj`` and ``iw``
N(0, 1/hidden) without outliers: an index query and the normed index key
have unit entries, a head's ``relu(qI . kI)`` spreads by 4.7 over the keys,
the head weights ``w`` by 1, and ``I = (heads_I d_I)^-1/2 sum_j w_j
relu(.)`` by 0.6 over a query's keys: the kept ``topk`` are no tie and no
single head decides them. **The router** N(0, ``ROUTER_SCALE``^2 / hidden),
as ``weights/longcat_flash.py`` argues: its 128 logits spread by 3 and the
8 chosen hold most of the softmax's mass (flat, the renormalized weights
would all be 1/8 and the choice would move nothing). No selection bias:
the family's routing has none.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from benchmark.weights.gpt import root_key
from benchmark.weights.olmo_hybrid import _normal

#: the router's logits spread by this (standard deviation)
ROUTER_SCALE = 3.0


def sizes(cfg: dict) -> dict:
    """Every size the layers depend on, from the configuration's keys:
    ``held`` routed experts from ``first`` on of ``routed``."""
    out = {k: int(cfg[k]) for k in (
        "vocab_size", "hidden_size", "moe_intermediate_size",
        "num_hidden_layers", "num_attention_heads", "num_key_value_heads",
        "head_dim", "num_experts_per_tok")}
    sa = cfg["sa_config"]
    out.update(index_dim=int(sa["indexer_head_dim"]),
               index_heads=int(sa["indexer_num_heads"]),
               topk=int(sa["topk"]))
    if int(sa.get("indexer_num_kv_heads", 1)) != 1:
        raise ValueError("one index key head is what the layer keeps")
    out["held"] = int(cfg["num_experts"])
    out["routed"] = int(cfg.get("published", {}).get("num_experts",
                                                     out["held"]))
    out["first"] = int(cfg.get("expert_first", 0))
    if not 0 <= out["first"] <= out["first"] + out["held"] <= out["routed"]:
        raise ValueError("the experts held are a range of those routed")
    return out


def _leaves(c: dict) -> dict:
    """name -> (shape, how it is drawn), in a fixed order; the stacked
    experts are drawn apart (:func:`_layer`)."""
    h, d = c["hidden_size"], c["head_dim"]
    heads, kv = c["num_attention_heads"], c["num_key_value_heads"]
    hi, di = c["index_heads"], c["index_dim"]
    big = lambda *shape: (shape, ("matrix", shape[-2] ** -0.5))
    plain = lambda *shape: (shape, ("plain", shape[-2] ** -0.5))
    gain = lambda n: ((n,), ("gain",))
    return {"input_norm": gain(h),
            "q_proj": big(h, heads * d), "k_proj": big(h, kv * d),
            "v_proj": big(h, kv * d), "o_proj": big(heads * d, h),
            "q_norm": gain(d), "k_norm": gain(d),
            "iq_proj": plain(h, hi * di), "ik_proj": plain(h, di),
            "iw": plain(h, hi),
            "ik_norm": gain(di), "ik_bias": ((di,), ("bias",)),
            "post_attn_norm": gain(h),
            "router": ((h, c["routed"]), ("plain", ROUTER_SCALE * h ** -0.5))}


def _draw(key, shape, how, dtype):
    if how[0] == "matrix":
        return _normal(key, shape, how[1], dtype, outliers=True)
    if how[0] == "plain":
        return _normal(key, shape, how[1], dtype)
    if how[0] == "bias":
        return _normal(key, shape, 0.1, dtype)
    return _normal(key, shape, 0.1, dtype, mean=1.0)      # a gain


@functools.partial(jax.jit, static_argnames=("w", "dtype"))
def _layer(key, index, w, dtype):
    c = dict(w)
    key = jax.random.fold_in(jax.random.fold_in(key, 1), index)
    out = {name: _draw(jax.random.fold_in(key, j), shape, how, dtype)
           for j, (name, (shape, how)) in enumerate(_leaves(c).items())}
    h, ew = c["hidden_size"], c["moe_intermediate_size"]
    mkey = jax.random.fold_in(key, 1000)

    def expert(e):  # by its index among ALL the routed experts
        ekey = jax.random.fold_in(mkey, e)
        return (_normal(jax.random.fold_in(ekey, 0), (h, 2 * ew), h ** -0.5,
                        dtype, outliers=True),
                _normal(jax.random.fold_in(ekey, 1), (ew, h), ew ** -0.5,
                        dtype, outliers=True))

    out["e_up"], out["e_down"] = jax.lax.map(
        expert, c["first"] + jnp.arange(c["held"]))
    return out


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
    return {"norm": _normal(jax.random.fold_in(key, 0),
                            (c["hidden_size"],), 0.1, dtype, mean=1.0),
            "head": _normal(jax.random.fold_in(key, 1),
                            (c["hidden_size"], c["vocab_size"]),
                            c["hidden_size"] ** -0.5, dtype)}


def _static(cfg):
    return tuple(sorted(sizes(cfg).items()))


def layer(seed: int, index: int, cfg: dict, dtype) -> dict:
    """Leaves of decoder layer ``index``."""
    return _layer(root_key(seed), jnp.asarray(index, jnp.int32),
                  _static(cfg), jnp.dtype(dtype))


def embed(seed: int, cfg: dict, dtype) -> dict:
    return _embed(root_key(seed), _static(cfg), jnp.dtype(dtype))


def final(seed: int, cfg: dict, dtype) -> dict:
    """The final norm's gain and the (untied) head ``[hidden, vocab]``."""
    return _final(root_key(seed), _static(cfg), jnp.dtype(dtype))


def all_weights(seed: int, cfg: dict, dtype) -> dict:
    """The whole model as the reference's ``logits`` takes it (small sizes:
    the tests)."""
    return {"embed": embed(seed, cfg, dtype),
            "layers": [layer(seed, i, cfg, dtype)
                       for i in range(int(cfg["num_hidden_layers"]))],
            "final": final(seed, cfg, dtype)}
