"""Plain GPT reference: forward, loss, gradients and AdamW in ``jax.numpy``.

Written from the equations (GPT-2/GPT-3 decoder: learned positions,
pre-LayerNorm blocks, fused qkv, causal softmax attention, tanh-GELU MLP of
width 4h, final LayerNorm, head tied to the token table; AdamW with
decoupled decay). float32 everywhere, matmuls at
``jax.default_matmul_precision("highest")``, no kernel, no cache, no
batching trick. It imports nothing of ``paddle_tpu`` and takes nothing the
program made: its weights come from ``benchmark/weights/gpt.py`` and the
seed, the same values the program was filled with, upcast.

``mode`` is the precision of every matrix product:

* ``"f32"``: the reference.
* ``"bf16"`` / ``"fp8"``: the CONTROL, the reference put in the program's
  place one precision lower than the configuration states. Both operands
  of every product (weights, activations, scores, probabilities) are
  rounded to that type on the way in, with a straight-through gradient.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from benchmark.weights import gpt as W

_ROUND = {"bf16": jnp.bfloat16, "fp8": jnp.float8_e4m3fn}


def _q(x, mode):
    if mode == "f32":
        return x
    r = x.astype(_ROUND[mode]).astype(jnp.float32)
    return x + jax.lax.stop_gradient(r - x)


def _mm(a, b, mode):
    return jnp.matmul(_q(a, mode), _q(b, mode),
                      precision=jax.lax.Precision.HIGHEST)


def layer_norm(x, w, b, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * w + b


def gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def block(x, p, heads: int, eps: float, mode: str = "f32"):
    """One decoder layer over one sequence ``x`` [s, h]; ``p`` are that
    layer's leaves (float32)."""
    s, h = x.shape
    d = h // heads
    y = layer_norm(x, p["ln1_w"], p["ln1_b"], eps)
    qkv = _mm(y, p["qkv_w"], mode) + p["qkv_b"]
    q, k, v = (qkv[:, i * h:(i + 1) * h].reshape(s, heads, d)
               .transpose(1, 0, 2) for i in range(3))       # [heads, s, d]
    scores = _mm(q, k.transpose(0, 2, 1), mode) / math.sqrt(d)
    causal = jnp.tril(jnp.ones((s, s), bool))
    scores = jnp.where(causal, scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    ctx = _mm(probs, v, mode).transpose(1, 0, 2).reshape(s, h)
    x = x + _mm(ctx, p["proj_w"], mode) + p["proj_b"]
    y = layer_norm(x, p["ln2_w"], p["ln2_b"], eps)
    y = gelu_tanh(_mm(y, p["up_w"], mode) + p["up_b"])
    return x + _mm(y, p["down_w"], mode) + p["down_b"]


def embed_tokens(ids, e):
    return e["wte"][ids] + e["wpe"][jnp.arange(ids.shape[0])]


def head_logits(x, e, f, eps, mode="f32"):
    return _mm(layer_norm(x, f["lnf_w"], f["lnf_b"], eps), e["wte"].T, mode)


def _f32(tree):
    return jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), tree)


# ------------------------------------------------------------------ serving


@functools.partial(jax.jit, static_argnames=("heads", "eps", "mode"))
def _block_jit(x, p, heads, eps, mode):
    return block(x, _f32(p), heads, eps, mode)


@functools.partial(jax.jit, static_argnames=("eps", "mode", "cap"))
def _rows_logits(x, e, f, start, eps, mode, cap):
    rows = jax.lax.dynamic_slice_in_dim(x, start, cap, axis=0)
    return head_logits(rows, _f32(e), _f32(f), eps, mode)


def teacher_forced_logits(seed, cfg, dtype, prompt, served, pad_to=256,
                          cap=512, mode="f32"):
    """One pass over ``prompt + served`` (token lists): the float32 logits
    [len(served), vocab] of the positions that predict each served token,
    on the device. Layer by layer, so only one layer's float32 weights
    exist at a time; the sequence is padded to a multiple of ``pad_to``
    (causal attention keeps the padding out of every row that is read)
    and at most ``cap`` rows are read, so few programs are compiled."""
    import numpy as np

    plen, n = len(prompt), len(served)
    if not 0 < n <= cap:
        raise ValueError(f"{n} served tokens; the check holds 1..{cap}")
    padded = -(-max(plen + n, cap + 1) // pad_to) * pad_to
    ids = np.zeros((padded,), np.int32)
    ids[:plen] = prompt
    ids[plen:plen + n] = served
    heads, eps = int(cfg["num_heads"]), float(cfg["layer_norm_epsilon"])
    e = W.embed(seed, cfg, dtype)
    x = embed_tokens(jnp.asarray(ids), _f32(e))
    for i in range(int(cfg["num_layers"])):
        x = _block_jit(x, W.layer(seed, i, cfg, dtype), heads, eps, mode)
    start = min(plen - 1, padded - cap)
    off = plen - 1 - start
    logits = _rows_logits(x, e, W.final(seed, cfg, dtype), start, eps, mode,
                          cap)
    return logits[off:off + n]


def _gap_of(ref_logits, tokens):
    picked = jnp.take_along_axis(ref_logits, tokens[:, None], -1)[:, 0]
    return jnp.max(ref_logits, -1) - picked


def served_token_gaps(seed, cfg, dtype, prompt, served, **kw):
    """For every served token: the reference's best logit at its position
    minus the reference's logit of the token that was served, in logit
    units (0 where the served token is the reference's first choice)."""
    import numpy as np

    ref = teacher_forced_logits(seed, cfg, dtype, prompt, served, **kw)
    return np.asarray(_gap_of(ref, jnp.asarray(served, jnp.int32))).tolist()


def control_gaps(seed, cfg, dtype, prompt, served, mode, **kw):
    """The serving check's control, without decoding: at each position of
    the same prompt and served tokens, the gap (under the float32
    reference) of the token that the ``mode`` pass puts first."""
    import numpy as np

    ref = teacher_forced_logits(seed, cfg, dtype, prompt, served, **kw)
    low = teacher_forced_logits(seed, cfg, dtype, prompt, served, mode=mode,
                                **kw)
    return np.asarray(_gap_of(ref, jnp.argmax(low, -1))).tolist()


# ----------------------------------------------------------------- training


def init_params(seed, cfg):
    """The float32 training state's parameters, as the weights module
    makes them: ``{"embed", "layers" (stacked), "final"}``."""
    return {"embed": W.embed(seed, cfg, jnp.float32),
            "layers": W.stacked_layers(seed, cfg, jnp.float32),
            "final": W.final(seed, cfg, jnp.float32)}


def batch_loss(params, ids, labels, heads, eps, mode):
    """Mean next-token cross-entropy over the batch, in nats. The layers
    run over the whole batch, each recomputed in the backward pass
    (``jax.checkpoint``: memory, not arithmetic); the head and the loss
    run one sequence at a time, so that one sequence's [s, vocab] float32
    logits are alive, not the batch's."""
    x = jax.vmap(embed_tokens, in_axes=(0, None))(ids, params["embed"])
    layer = jax.vmap(functools.partial(block, heads=heads, eps=eps,
                                       mode=mode), in_axes=(0, None))

    @jax.checkpoint
    def body(x, p):
        return layer(x, p), None

    x, _ = jax.lax.scan(body, x, params["layers"])

    @jax.checkpoint
    def sequence(x_labels):
        x1, y1 = x_labels
        logits = head_logits(x1, params["embed"], params["final"], eps, mode)
        picked = jnp.take_along_axis(logits, y1[:, None], -1)[:, 0]
        return jnp.mean(jax.nn.logsumexp(logits, -1) - picked)

    return jnp.mean(jax.lax.map(sequence, (x, labels)))


def leaf_norms(tree):
    """L2 norm of every leaf; a stacked leaf gives one norm per layer."""
    def norm(path, a):
        a = a.astype(jnp.float32)
        if path[0].key == "layers":
            return jnp.sqrt(jnp.sum(jnp.square(a),
                                    axis=tuple(range(1, a.ndim))))
        return jnp.sqrt(jnp.sum(jnp.square(a)))
    return jax.tree_util.tree_map_with_path(norm, tree)


@functools.partial(jax.jit, static_argnames=("heads", "eps", "mode"))
def loss_and_grads(params, ids, labels, heads, eps, mode):
    loss, grads = jax.value_and_grad(batch_loss)(
        params, ids, labels, heads, eps, mode)
    return loss, grads, leaf_norms(grads)


@functools.partial(jax.jit, static_argnames=("opt",), donate_argnums=(0,))
def adamw(params, history, opt):
    """The AdamW step number ``len(history)``. The moments are worked out
    from the gradients so far (``history``, oldest first) instead of being
    carried: m_t = b1 m_(t-1) + (1 - b1) g_t from m_0 = 0, likewise v_t.
    For the two or three steps the check follows, that holds fewer arrays
    than the parameters, both moments and the gradient together would."""
    o = dict(opt)
    b1, b2, t = o["beta1"], o["beta2"], float(len(history))

    def upd(p, *gs):
        m = v = jnp.zeros_like(p)
        for g in gs:
            m = b1 * m + (1 - b1) * g
            v = b2 * v + (1 - b2) * jnp.square(g)
        m_hat, v_hat = m / (1 - b1 ** t), v / (1 - b2 ** t)
        return p - o["learning_rate"] * (
            m_hat / (jnp.sqrt(v_hat) + o["epsilon"]) + o["weight_decay"] * p)

    return jax.tree_util.tree_map(upd, params, *history)


@jax.jit
def change_norms(params, start):
    return leaf_norms(jax.tree_util.tree_map(lambda a, b: a - b, params,
                                             start))


def train_trajectory(seed, cfg, opt: dict, batches, mode: str = "f32"):
    """Follow ``len(batches)`` steps from the seeded weights. ``batches``
    are (ids, labels) int32 arrays [b, s]. Returns the per-step losses,
    the first step's gradient norms per leaf, and the norms of the
    parameters' change after the last step."""
    import numpy as np

    heads, eps = int(cfg["num_heads"]), float(cfg["layer_norm_epsilon"])
    opt_t = tuple(sorted((k, float(opt[k])) for k in (
        "learning_rate", "beta1", "beta2", "epsilon", "weight_decay")))
    params = init_params(seed, cfg)
    losses, first, history = [], None, ()
    for ids, labels in batches:
        loss, grads, norms = loss_and_grads(
            params, jnp.asarray(ids), jnp.asarray(labels), heads, eps, mode)
        losses.append(float(loss))
        if first is None:
            first = jax.tree_util.tree_map(np.asarray, norms)
        history += (grads,)
        params = adamw(params, history, opt_t)
    del history, grads
    change = jax.tree_util.tree_map(
        np.asarray, change_norms(params, init_params(seed, cfg)))
    return {"losses": losses, "grad_norms": first, "change_norms": change}
