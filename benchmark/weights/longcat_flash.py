"""LongCat-Flash weights from a seed, made on the device, for the program
AND the reference. Pure jax: imports nothing of paddle_tpu.

One jitted call per group (the token table, one decoder layer with both its
half-layers, the final norm and head), keyed by (seed, group, layer index),
as ``weights/xing4.py``: the program's model is filled layer by layer and
the reference makes the same layer again without holding the rest (a layer
with its 16 held experts is 2.5 GB in bf16). Values are drawn in float32 and
rounded once to ``dtype``; the reference upcasts those same rounded values.
The router's selection bias stays float32 whatever ``dtype`` is.

**The share.** The configuration's ``n_routed_experts`` counts the routed
experts HELD here, from ``expert_first`` on; ``published.n_routed_experts``
(where the file has it) is how many the router routes over, beside its
``zero_expert_num`` zero-compute columns. Each routed expert is drawn from
a key of its own index among all of them, so a share holds the very
experts the whole layer would hold there, and 512 of them are never made
to keep 16. ``e_up`` ``[held, hidden, 2 width]`` (``[gate | up]``),
``e_down`` ``[held, width, hidden]``.

A layer's leaves: ``a_*`` and ``b_*`` for its two half-layers (``attn_norm``,
``q_a``, ``q_a_norm``, ``q_b``, ``kv_a``, ``kv_a_norm``, ``kv_b``, ``o``,
``mlp_norm``, ``up`` = ``[gate | up]``, ``down``), then ``router`` ``[hidden,
routed + zero]``, ``e_bias``, ``e_up``, ``e_down``. Matrices lie ``[in,
out]``.

Distribution (each choice is in the configuration file's ``assumed``):
matrices N(0, 1/fan_in), one entry in a thousand of each ten times its draw
(``weights/gpt.py`` says why), but the two up-projections behind the latents,
``q_b`` and ``kv_b``, N(0, 1/hidden): the family draws every matrix with ONE
spread, and ``mla_scale_q_lora`` / ``mla_scale_kv_lora`` (``sqrt(hidden /
rank)``) exist to give the low-rank paths the variance a full-width
projection has under it, so queries, keys and values come out at unit
variance and the attention's scores spread by 1. Drawn N(0, 1/rank) under
those factors the scores spread by 5.7, the softmax stands on one key, and a
sound bf16 program's rounding of a score (0.05 at 17) moves an attention
weight by 5% (my chip runs, PR 39: 60% of served tokens not the float32
reference's first choice, at every routing margin); RMSNorm gains 1 + N(0, 0.1); the token table
N(0, 1); the head N(0, 1/hidden). The ROUTER is N(0, ``ROUTER_SCALE``^2 /
hidden): its input has unit mean square, so its 768 logits spread by 3 and
the 12 chosen hold some 0.7 of the softmax's mass (at a spread of 1 the
softmax is nearly flat, a chosen weight is 0.05 after the factor 6, and the
expert layer vanishes from the logits). The selection bias is FIT
(:func:`selection_biases`), as the family's training fits it: to the point
where every column, routed or zero-compute, is chosen equally often, which
gives the 256 zero-compute experts a third of the picks together and each
routed expert an even share of the rest: 8 real experts a token on average.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from benchmark.weights.gpt import root_key
from benchmark.weights.olmo_hybrid import _normal

#: the router's logits spread by this (standard deviation)
ROUTER_SCALE = 3.0
HALVES = ("a", "b")


def sizes(cfg: dict) -> dict:
    """Every size the layers depend on, from the configuration's keys:
    ``held`` routed experts from ``first`` on of ``routed``, beside
    ``zero`` zero-compute columns."""
    out = {k: int(cfg[k]) for k in (
        "vocab_size", "hidden_size", "ffn_hidden_size",
        "expert_ffn_hidden_size", "num_layers", "num_attention_heads",
        "q_lora_rank", "kv_lora_rank", "qk_nope_head_dim",
        "qk_rope_head_dim", "v_head_dim", "moe_topk", "zero_expert_num")}
    out["held"] = int(cfg["n_routed_experts"])
    out["routed"] = int(cfg.get("published", {}).get("n_routed_experts",
                                                     out["held"]))
    out["first"] = int(cfg.get("expert_first", 0))
    if not 0 <= out["first"] <= out["first"] + out["held"] <= out["routed"]:
        raise ValueError("the experts held are a range of those routed")
    if out["zero_expert_num"] and cfg.get("zero_expert_type",
                                          "identity") != "identity":
        raise ValueError("zero_expert_type 'identity' is the one kind")
    return out


def _half_leaves(c: dict) -> dict:
    h, heads = c["hidden_size"], c["num_attention_heads"]
    qr, kvr = c["q_lora_rank"], c["kv_lora_rank"]
    nope, rope, vd = (c["qk_nope_head_dim"], c["qk_rope_head_dim"],
                      c["v_head_dim"])
    w = c["ffn_hidden_size"]
    big = lambda *shape: (shape, ("matrix", shape[-2] ** -0.5))
    # the up-projections behind the two latents: N(0, 1/hidden), the spread
    # `mla_scale_q_lora` / `mla_scale_kv_lora` make up for (see the head)
    low_rank = lambda *shape: (shape, ("matrix", h ** -0.5))
    gain = lambda n: ((n,), ("gain",))
    return {"attn_norm": gain(h),
            "q_a": big(h, qr), "q_a_norm": gain(qr),
            "q_b": low_rank(qr, heads * (nope + rope)),
            "kv_a": big(h, kvr + rope), "kv_a_norm": gain(kvr),
            "kv_b": low_rank(kvr, heads * (nope + vd)),
            "o": big(heads * vd, h),
            "mlp_norm": gain(h), "up": big(h, 2 * w), "down": big(w, h)}


def _draw(key, shape, how, dtype):
    if how[0] == "matrix":
        return _normal(key, shape, how[1], dtype, outliers=True)
    return _normal(key, shape, 0.1, dtype, mean=1.0)      # a gain


@functools.partial(jax.jit, static_argnames=("w", "dtype"))
def _layer(key, index, w, dtype):
    c = dict(w)
    key = jax.random.fold_in(jax.random.fold_in(key, 1), index)
    out = {}
    for n, half in enumerate(HALVES):
        hkey = jax.random.fold_in(key, n)
        for j, (name, (shape, how)) in enumerate(_half_leaves(c).items()):
            out[f"{half}_{name}"] = _draw(jax.random.fold_in(hkey, j), shape,
                                          how, dtype)
    h, ew = c["hidden_size"], c["expert_ffn_hidden_size"]
    columns = c["routed"] + c["zero_expert_num"]
    mkey = jax.random.fold_in(key, len(HALVES))
    out["router"] = _normal(jax.random.fold_in(mkey, 0), (h, columns),
                            ROUTER_SCALE * h ** -0.5, dtype)

    def expert(e):  # by its index among ALL the routed experts
        ekey = jax.random.fold_in(jax.random.fold_in(mkey, 1), e)
        return (_normal(jax.random.fold_in(ekey, 0), (h, 2 * ew), h ** -0.5,
                        dtype, outliers=True),
                _normal(jax.random.fold_in(ekey, 1), (ew, h), ew ** -0.5,
                        dtype, outliers=True))

    out["e_up"], out["e_down"] = jax.lax.map(
        expert, c["first"] + jnp.arange(c["held"]))
    out["e_bias"] = jnp.zeros((columns,), jnp.float32)    # see `layer`
    return out


@functools.partial(jax.jit, static_argnames=("w", "dtype"))
def _embed(key, w, dtype):
    c = dict(w)
    return {"embed": _normal(jax.random.fold_in(key, 0),
                             (c["vocab_size"], c["hidden_size"]), 1.0, dtype)}


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
                  _static(cfg), jnp.dtype(dtype))


def layer(seed: int, index: int, cfg: dict, dtype) -> dict:
    """Leaves of decoder layer ``index`` (both half-layers, the router,
    this share's experts); the selection bias is the fitted one."""
    p = _drawn_layer(seed, index, cfg, dtype)
    p["e_bias"] = selection_biases(seed, cfg, dtype)[index]
    return p


# ------------------------------------------------- the selection bias, fit

FIT_ROUNDS = 400


@functools.partial(jax.jit, static_argnames=("k",))
def fit_selection_bias(scores, k: int):
    """The bias ``[columns]`` at which the ``k`` largest of ``scores +
    bias`` choose every column equally often over the tokens of ``scores``
    ``[T, columns]`` (softmax probabilities): a column over its share has
    its bias lowered, one under it raised, the step (in probability units:
    a column's even share of the mass) shrinking as it goes; the scores and
    so the mixing weights are untouched. An even choice over ``routed +
    zero`` columns IS the family's target: the zero-compute experts get
    ``zero / (routed + zero)`` of the picks together (a third), and every
    routed expert an even share of the rest."""
    t, e = scores.shape
    share = t * k / e

    def one(r, bias):
        _, top = jax.lax.top_k(scores + bias, k)
        load = jnp.zeros((e,), jnp.float32).at[top.reshape(-1)].add(1.0)
        return bias - 0.985 ** r / e * (load / share - 1.0)

    return jax.lax.fori_loop(0, FIT_ROUNDS, one,
                             jnp.zeros((e,), jnp.float32))


_FITTED = {}

#: the fit's tokens: this many sequences of :func:`calibration_length`
CALIBRATION_SEQUENCES = 16


def calibration_length(cfg: dict) -> int:
    """Tokens a calibration sequence holds: enough that every column's
    even share is some 128 picks over the 16 sequences."""
    s = sizes(cfg)
    columns = s["routed"] + s["zero_expert_num"]
    return max(32, -(-128 * columns
                     // (CALIBRATION_SEQUENCES * s["moe_topk"])))


def selection_biases(seed: int, cfg: dict, dtype) -> dict:
    """layer index -> the fitted selection bias ``[columns]`` float32. A
    pure function of the seed and the configuration (kept for the last one
    asked for: the program's layers and the reference's ask in turn): 16
    sequences of seeded token ids go through the layers in order, in the
    plain reference's float32 arithmetic and with this share's experts,
    and each layer's bias is fit on the probabilities its router gives
    those tokens before they go on through it (``weights/xing4.py`` says
    why the model's own hidden states and why several sequences). The
    program and the reference get the same numbers; neither's verdict
    leans on how good the fit is."""
    from benchmark.reference import longcat_flash as ref  # imports this

    frozen = ref._frozen(cfg)
    key = (int(seed), str(jnp.dtype(dtype)), frozen)
    if key not in _FITTED:
        _FITTED.clear()
        shape = (CALIBRATION_SEQUENCES, calibration_length(cfg))
        ids = jax.random.randint(jax.random.fold_in(root_key(seed), 4),
                                 shape, 0, int(cfg["vocab_size"]))
        X = embed(seed, cfg, dtype)["embed"][ids].astype(jnp.float32)
        out = {}
        for i in range(int(cfg["num_layers"])):
            X, out[i] = ref.calibration_layer(
                X, _drawn_layer(seed, i, cfg, dtype), frozen)
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
                       for i in range(int(cfg["num_layers"]))],
            "final": final(seed, cfg, dtype)}
