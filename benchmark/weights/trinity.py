"""Trinity (``afmoe``) weights from a seed, made on the device, for the
program AND the reference. Pure jax: imports nothing of paddle_tpu.

One jitted call per group (the token table, one decoder layer, the final
norm and head), keyed by (seed, group, layer index), as
``weights/longcat_flash.py``: the program's model is filled layer by layer
and the reference makes the same layer again without holding the rest (an
expert layer with its 32 held experts is 2.0 GB in bf16). Values are drawn
in float32 and rounded once to ``dtype``; the reference upcasts those same
rounded values. The router's selection bias stays float32 whatever
``dtype`` is.

**The share.** The configuration's ``num_experts`` counts the routed
experts HELD here, from ``expert_first`` on; ``published.num_experts``
(where the file has it) is how many the router routes over. Each routed
expert is drawn from a key of its own index among all of them, so a share
holds the very experts the whole layer would hold there. ``e_up`` ``[held,
hidden, 2 width]`` (``[gate | up]``), ``e_down`` ``[held, width, hidden]``.

A layer's leaves: ``input_norm``, ``q_proj``, ``k_proj``, ``v_proj``,
``gate_proj``, ``o_proj``, ``q_norm``, ``k_norm`` (one gain a value of the
head), ``post_attn_norm``, ``pre_mlp_norm``, ``post_mlp_norm``; then ``up``
= ``[gate | up]`` and ``down`` (a dense layer: index under
``num_dense_layers``) or ``router`` ``[hidden, routed]``, ``e_bias``,
``e_up``, ``e_down``, ``s_up``, ``s_down`` (the shared expert). Matrices lie
``[in, out]``. A sliding layer and a full one have the same leaves.

Distribution (each choice is in the configuration file's ``assumed``):
matrices N(0, 1/fan_in), one entry in a thousand of each ten times its draw
(``weights/gpt.py`` says why); RMSNorm gains 1 + N(0, 0.1), the four a
layer and the two a head alike ("depth-scaled" in the family's description
is how a trained model starts its post-norm gains: with seeded weights a
draw); the token table N(0, 1/hidden), which ``mup_enabled``'s
``sqrt(hidden)`` brings to unit mean square, the size every sublayer's
normed output adds to the stream; the head and the router N(0, 1/hidden)
(the router's input has unit mean square, so its 256 scores spread over the
sigmoid). Queries and keys leave their norms at unit mean square, so the
attention's scores spread by 1. The selection bias is FIT
(:func:`selection_biases`), as ``weights/xing4.py`` fits its: to where the
4-of-256 choice loads every expert alike.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from benchmark.weights.gpt import root_key
from benchmark.weights.olmo_hybrid import _normal
from benchmark.weights.xing4 import fit_selection_bias  # noqa: F401 (the
# reference's calibration pass calls it as ``W.fit_selection_bias``)

DENSE, EXPERT = "dense", "expert"


def sizes(cfg: dict) -> dict:
    """Every size the layers depend on, from the configuration's keys:
    ``held`` routed experts from ``first`` on of ``routed``."""
    out = {k: int(cfg[k]) for k in (
        "vocab_size", "hidden_size", "intermediate_size",
        "moe_intermediate_size", "num_hidden_layers", "num_attention_heads",
        "num_key_value_heads", "head_dim", "num_dense_layers",
        "num_experts_per_tok", "sliding_window")}
    out["held"] = int(cfg["num_experts"])
    out["routed"] = int(cfg.get("published", {}).get("num_experts",
                                                     out["held"]))
    out["first"] = int(cfg.get("expert_first", 0))
    if not 0 <= out["first"] <= out["first"] + out["held"] <= out["routed"]:
        raise ValueError("the experts held are a range of those routed")
    if int(cfg.get("num_shared_experts", 1)) != 1:
        raise ValueError("one shared expert is what the layer computes")
    return out


def kind_of(cfg: dict, index: int) -> str:
    return DENSE if index < int(cfg["num_dense_layers"]) else EXPERT


def _leaves(c: dict, kind: str) -> dict:
    """name -> (shape, how it is drawn), in a fixed order; the stacked
    experts are drawn apart (:func:`_layer`)."""
    h, d = c["hidden_size"], c["head_dim"]
    heads, kv = c["num_attention_heads"], c["num_key_value_heads"]
    big = lambda *shape: (shape, ("matrix", shape[-2] ** -0.5))
    gain = lambda n: ((n,), ("gain",))
    out = {"input_norm": gain(h),
           "q_proj": big(h, heads * d), "k_proj": big(h, kv * d),
           "v_proj": big(h, kv * d), "gate_proj": big(h, heads * d),
           "o_proj": big(heads * d, h),
           "q_norm": gain(d), "k_norm": gain(d),
           "post_attn_norm": gain(h), "pre_mlp_norm": gain(h),
           "post_mlp_norm": gain(h)}
    if kind == DENSE:
        w = c["intermediate_size"]
        out.update({"up": big(h, 2 * w), "down": big(w, h)})
    else:
        w = c["moe_intermediate_size"]
        out.update({"router": ((h, c["routed"]), ("plain", h ** -0.5)),
                    "s_up": big(h, 2 * w), "s_down": big(w, h)})
    return out


def _draw(key, shape, how, dtype):
    if how[0] == "matrix":
        return _normal(key, shape, how[1], dtype, outliers=True)
    if how[0] == "plain":
        return _normal(key, shape, how[1], dtype)
    return _normal(key, shape, 0.1, dtype, mean=1.0)      # a gain


@functools.partial(jax.jit, static_argnames=("w", "dtype", "kind"))
def _layer(key, index, w, dtype, kind):
    c = dict(w)
    key = jax.random.fold_in(jax.random.fold_in(key, 1), index)
    out = {name: _draw(jax.random.fold_in(key, j), shape, how, dtype)
           for j, (name, (shape, how)) in enumerate(_leaves(c, kind).items())}
    if kind == DENSE:
        return out
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
    out["e_bias"] = jnp.zeros((c["routed"],), jnp.float32)  # see `layer`
    return out


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
    return {"norm": _normal(jax.random.fold_in(key, 0),
                            (c["hidden_size"],), 0.1, dtype, mean=1.0),
            "head": _normal(jax.random.fold_in(key, 1),
                            (c["hidden_size"], c["vocab_size"]),
                            c["hidden_size"] ** -0.5, dtype)}


def _static(cfg):
    return tuple(sorted(sizes(cfg).items()))


def _drawn_layer(seed, index, cfg, dtype):
    return _layer(root_key(seed), jnp.asarray(index, jnp.int32),
                  _static(cfg), jnp.dtype(dtype), kind_of(cfg, index))


def layer(seed: int, index: int, cfg: dict, dtype) -> dict:
    """Leaves of decoder layer ``index``, of the kind its index gives it;
    an expert layer's selection bias is the fitted one."""
    p = _drawn_layer(seed, index, cfg, dtype)
    if "e_bias" in p:
        p["e_bias"] = selection_biases(seed, cfg, dtype)[index]
    return p


# ------------------------------------------------- the selection bias, fit

_FITTED = {}

#: the fit's tokens: this many sequences of :func:`calibration_length`
CALIBRATION_SEQUENCES = 16


def calibration_length(cfg: dict) -> int:
    """Tokens a calibration sequence holds: enough that every expert's even
    share is some 128 picks over the 16 sequences."""
    s = sizes(cfg)
    return max(32, -(-128 * s["routed"] // (CALIBRATION_SEQUENCES
                                           * s["num_experts_per_tok"])))


def selection_biases(seed: int, cfg: dict, dtype) -> dict:
    """layer index -> the fitted selection bias ``[routed]`` float32 of
    every expert layer. A pure function of the seed and the configuration
    (kept for the last one asked for: the program's layers and the
    reference's ask in turn): 16 sequences of seeded token ids go through
    the layers in order, in the plain reference's float32 arithmetic and
    with this share's experts, and each expert layer's bias is fit on the
    scores its router gives those tokens before they go on through it
    (``weights/xing4.py`` says why the model's own hidden states and why
    several sequences). The program and the reference get the same
    numbers; neither's verdict leans on how good the fit is."""
    from benchmark.reference import trinity as ref  # it imports this module

    frozen = ref._frozen(cfg)
    key = (int(seed), str(jnp.dtype(dtype)), frozen)
    if key not in _FITTED:
        _FITTED.clear()
        shape = (CALIBRATION_SEQUENCES, calibration_length(cfg))
        ids = jax.random.randint(jax.random.fold_in(root_key(seed), 4),
                                 shape, 0, int(cfg["vocab_size"]))
        X = ref.embedded(embed(seed, cfg, dtype)["embed"], ids, cfg)
        out = {}
        for i in range(int(cfg["num_hidden_layers"])):
            X, bias = ref.calibration_layer(
                X, _drawn_layer(seed, i, cfg, dtype), i, frozen)
            if bias is not None:
                out[i] = bias
        _FITTED[key] = out
    return _FITTED[key]


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
