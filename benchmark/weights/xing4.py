"""Xing4.0 weights from a seed, made on the device, for the program AND the
reference. Pure jax: imports nothing of paddle_tpu.

One jitted call per group (the token table, the head, one decoder layer, the
final norm, the multi-token-prediction module), keyed by (seed, group, layer
index), as ``weights/phi4flash.py``: the program's model is filled layer by
layer and the reference makes the same layer again without holding the rest
(an expert layer is 1.49 GB in bf16). Values are drawn in float32 and rounded
once to ``dtype``; the reference upcasts those same rounded values. The
router's selection bias and the hyper-connections' scalars and biases stay
float32 whatever ``dtype`` is (they are a few hundred numbers, and the mixers
are computed in float32).

Matrices lie ``[in, out]``; the experts are stacked: ``e_up`` ``[E, hidden,
2 width]`` (``[gate | up]``) and ``e_down`` ``[E, width, hidden]``. A layer
is dense (``kind_of`` ``"dense"``) while its index is under
``first_k_dense_replace``, an expert layer after.

Distribution (each choice is in the configuration file's ``assumed``):
matrices N(0, 1/fan_in), one entry in a thousand of each ten times its draw
(``weights/gpt.py`` says why); RMSNorm gains 1 + N(0, 0.1); the token table
N(0, 1), so that the embedding and six layers' contributions to the streams
are of one size; the head N(0, 1/hidden); the router N(0, 1/hidden) (its
input has unit mean square, so the 64 scores spread over the sigmoid). A
decoder layer's selection bias is FIT, as ``noaux_tc`` fits it in training
(:func:`selection_biases`): a bias drawn N(0, 0.1) beside scores that spread
by 0.21 gives the busiest expert five times its share and a quarter of the
experts nothing in a step, which no deployment routes like. The
multi-token-prediction block's bias stays that draw (no cell loads the
block, and its parity test wants a bias that changes the choice). A
hyper-connection mixer: one matrix ``[4 hidden,
24]`` N(0, 1/fan_in) whose columns are ``pre`` (4), ``post`` (4) and ``res``
(16, row-major 4 x 4), the biases N(0, 0.25) with 1 added on the diagonal of
``res`` (the papers start near the identity), the three scalars 0.5 each
(large enough that the token-dependent part moves every mixer). At this
spread 20 Sinkhorn rounds leave every row within 2e-5 of 1 (100,000 drawn
matrices); at biases N(0, 0.5) with 2 on the diagonal some rows are still
4e-3 off, which no trained model would ship with ``hc_sinkhorn_iters`` 20.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from benchmark.weights.gpt import root_key
from benchmark.weights.olmo_hybrid import _normal

DENSE, EXPERT = "dense", "expert"
HC_COLS = lambda n: 2 * n + n * n  # pre, post, res


def sizes(cfg: dict) -> dict:
    """Every size the layers depend on, from the configuration's keys."""
    out = {k: int(cfg[k]) for k in (
        "vocab_size", "hidden_size", "intermediate_size",
        "moe_intermediate_size", "num_hidden_layers", "num_attention_heads",
        "q_lora_rank", "kv_lora_rank", "qk_nope_head_dim",
        "qk_rope_head_dim", "v_head_dim", "n_routed_experts",
        "n_shared_experts", "num_experts_per_tok", "first_k_dense_replace",
        "hc_mult")}
    if out["n_shared_experts"] != 1:
        raise ValueError("one shared expert is what the layer computes")
    return out


def kind_of(cfg: dict, index: int) -> str:
    return DENSE if index < int(cfg["first_k_dense_replace"]) else EXPERT


def _hc(c: dict, prefix: str) -> dict:
    n, h = c["hc_mult"], c["hidden_size"]
    return {prefix + "_norm": ((n * h,), ("gain",)),
            prefix + "_w": ((n * h, HC_COLS(n)), ("plain32", (n * h) ** -0.5)),
            prefix + "_b": ((HC_COLS(n),), ("hc_bias", n)),
            prefix + "_a": ((3,), ("const32", 0.5))}


def _leaves(c: dict, kind: str):
    """name -> (shape, how it is drawn), in a fixed order."""
    h, heads = c["hidden_size"], c["num_attention_heads"]
    qr, kvr = c["q_lora_rank"], c["kv_lora_rank"]
    nope, rope, vd = (c["qk_nope_head_dim"], c["qk_rope_head_dim"],
                      c["v_head_dim"])
    big = lambda *shape: (shape, ("matrix", shape[-2] ** -0.5))
    gain = lambda n: ((n,), ("gain",))
    out = dict(_hc(c, "hca"))
    out.update({
        "attn_norm": gain(h),
        "q_a": big(h, qr), "q_a_norm": gain(qr),
        "q_b": big(qr, heads * (nope + rope)),
        "kv_a": big(h, kvr + rope), "kv_a_norm": gain(kvr),
        "kv_b": big(kvr, heads * (nope + vd)),
        "o": big(heads * vd, h)})
    out.update(_hc(c, "hcm"))
    out["mlp_norm"] = gain(h)
    if kind == DENSE:
        w = c["intermediate_size"]
        out.update({"up": big(h, 2 * w), "down": big(w, h)})
    else:
        w, e = c["moe_intermediate_size"], c["n_routed_experts"]
        out.update({"router": big(h, e),
                    "e_bias": ((e,), ("plain32", 0.1)),   # see `layer`
                    "e_up": big(e, h, 2 * w), "e_down": big(e, w, h),
                    "s_up": big(h, 2 * w), "s_down": big(w, h)})
    return out


def _draw(key, shape, how, dtype):
    what = how[0]
    if what == "matrix":
        return _normal(key, shape, how[1], dtype, outliers=True)
    if what == "gain":
        return _normal(key, shape, 0.1, dtype, mean=1.0)
    if what == "plain32":
        return _normal(key, shape, how[1], jnp.float32)
    if what == "const32":
        return jnp.full(shape, how[1], jnp.float32)
    # a mixer's biases: N(0, 0.25), the diagonal of `res` 1 higher
    n = how[1]
    eye = jnp.concatenate([jnp.zeros((2 * n,)), jnp.eye(n).reshape(-1)])
    return _normal(key, shape, 0.25, jnp.float32) + eye


def _draw_all(key, leaves, dtype):
    return {name: _draw(jax.random.fold_in(key, j), shape, how, dtype)
            for j, (name, (shape, how)) in enumerate(leaves.items())}


@functools.partial(jax.jit, static_argnames=("w", "dtype", "kind"))
def _layer(key, index, w, dtype, kind):
    key = jax.random.fold_in(jax.random.fold_in(key, 1), index)
    return _draw_all(key, _leaves(dict(w), kind), dtype)


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


@functools.partial(jax.jit, static_argnames=("w", "dtype"))
def _mtp(key, w, dtype):
    c = dict(w)
    h = c["hidden_size"]
    key = jax.random.fold_in(key, 3)
    leaves = {"hnorm": ((h,), ("gain",)), "enorm": ((h,), ("gain",)),
              "proj": ((2 * h, h), ("matrix", (2 * h) ** -0.5))}
    leaves.update(_leaves(c, EXPERT))
    return _draw_all(key, leaves, dtype)


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

FIT_ROUNDS = 400


@functools.partial(jax.jit, static_argnames=("k",))
def fit_selection_bias(scores, k: int):
    """The bias ``[E]`` at which the ``k`` largest of ``scores + bias``
    load every expert alike over the tokens of ``scores`` ``[T, E]``:
    ``noaux_tc``'s rule (an expert over its share has its bias lowered, one
    under it raised; the scores and so the mixing weights are untouched)
    run to its fixed point on one batch, the step shrinking as it goes."""
    t, e = scores.shape
    share = t * k / e

    def one(r, bias):
        _, top = jax.lax.top_k(scores + bias, k)
        load = jnp.sum(jax.nn.one_hot(top, e, dtype=jnp.float32), (0, 1))
        return bias - 0.05 * 0.985 ** r * (load / share - 1.0)

    return jax.lax.fori_loop(0, FIT_ROUNDS, one,
                             jnp.zeros((e,), jnp.float32))


_FITTED = {}


CALIBRATION_SEQUENCES = 16


def selection_biases(seed: int, cfg: dict, dtype) -> dict:
    """layer index -> the fitted selection bias ``[E]`` float32 of every
    expert layer. A pure function of the seed and the configuration (kept
    for the last one asked for: the program's layers and the reference's
    ask in turn): 16 sequences of ``4 x n_routed_experts`` seeded token
    ids go through the layers in order, in the plain reference's float32
    arithmetic, and each expert layer's bias is fit on the scores its
    router gives those tokens before they go on through it. So the fit
    sees what the router sees in the model, not synthetic rows; and it
    sees several sequences, because the tokens of one share a direction
    (a tenth of their spread, growing with depth) that favours that
    sequence's own few experts, which a bias fit on it would only
    memorize: a decode step's lanes are as many sequences. The program and
    the reference get the same numbers; neither's verdict leans on how
    good the fit is."""
    from benchmark.reference import xing4 as ref  # it imports this module

    frozen = ref._frozen(cfg)
    key = (int(seed), str(jnp.dtype(dtype)), frozen)
    if key not in _FITTED:
        _FITTED.clear()
        shape = (CALIBRATION_SEQUENCES, 4 * int(cfg["n_routed_experts"]))
        ids = jax.random.randint(jax.random.fold_in(root_key(seed), 4),
                                 shape, 0, int(cfg["vocab_size"]))
        X = jax.vmap(lambda row: ref._streams(
            embed(seed, cfg, dtype)["embed"], row, int(cfg["hc_mult"])))(ids)
        out = {}
        for i in range(int(cfg["num_hidden_layers"])):
            X, bias = ref.calibration_layer(
                X, _drawn_layer(seed, i, cfg, dtype), kind_of(cfg, i), frozen)
            if bias is not None:
                out[i] = bias
        _FITTED[key] = out
    return _FITTED[key]


def embed(seed: int, cfg: dict, dtype) -> dict:
    return _embed(root_key(seed), _static(cfg), jnp.dtype(dtype))


def final(seed: int, cfg: dict, dtype) -> dict:
    """The final norm's gain and the (untied) head ``[hidden, vocab]``."""
    return _final(root_key(seed), _static(cfg), jnp.dtype(dtype))


def mtp(seed: int, cfg: dict, dtype) -> dict:
    """The multi-token-prediction module: its two norms, its projection
    and one expert decoder block's leaves."""
    return _mtp(root_key(seed), _static(cfg), jnp.dtype(dtype))


def all_weights(seed: int, cfg: dict, dtype, with_mtp: bool = False) -> dict:
    """The whole model as the reference's ``logits`` takes it (small sizes:
    the tests)."""
    out = {"embed": embed(seed, cfg, dtype),
           "layers": [layer(seed, i, cfg, dtype)
                      for i in range(int(cfg["num_hidden_layers"]))],
           "final": final(seed, cfg, dtype)}
    if with_mtp:
        out["mtp"] = mtp(seed, cfg, dtype)
    return out
