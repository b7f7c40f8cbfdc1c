"""The gated delta rule (Gated DeltaNet, arXiv:2412.06464) on raw arrays.

Per head, with a matrix state ``S`` in R^{dv x dk}, a decay ``a_t = exp(g_t)``
in (0, 1] and a write strength ``b_t``:

    S_t = a_t S_{t-1} - b_t (a_t S_{t-1} k_t - v_t) k_t^T
    o_t = S_t q_t

Three forms of the same recurrence:

* :func:`gated_delta_serial` - one token at a time (``lax.scan`` over ``t``):
  what the other two are tested against;
* :func:`gated_delta_chunked` - the prefill form. Inside a chunk of ``C``
  tokens write ``S_t = G_t S_0 + sum_{i<=t} (G_t / G_i) u_i k_i^T`` with
  ``G_t`` the chunk's cumulative decay and ``u_t = b_t (v_t - a_t S_{t-1}
  k_t)``; then ``(I + A) U = b V - (b G K) S_0^T`` with
  ``A = tril(b (K K^T) * decay, -1)`` and ``decay[t, i] = G_t / G_i``, so
  ``U = U' - W S_0^T`` after ONE unit-lower-triangular solve a chunk (the WY
  form). Every chunk and head is solved at once as batched matmuls; one pass
  over the chunks carries ``S``. Everything is kept in LOG decay
  (``G_t - G_i <= 0`` wherever it is exponentiated), so nothing is divided by
  a decay and no ``1 / G`` can overflow;
* :func:`gated_delta_step` - the decode form, one token for every lane.

State and accumulation are float32. ``mm_dtype`` is the type of the chunked
form's matmul OPERANDS (bfloat16 under a bfloat16 model); the triangular
solve, the decays and the single-token update stay float32.

:func:`causal_conv` is the short depthwise convolution in front of the rule,
with the ``K - 1`` rows it has to carry from one call to the next.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

CHUNK = 64


def l2norm(x, eps: float = 1e-6):
    """``x / sqrt(sum(x^2) + eps)`` over the last axis, float32."""
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + eps)


@jax.named_scope("gdn_conv")
def causal_conv(x, w, tail, valid_len=None):
    """Causal depthwise convolution ``y_t = sum_j w[j] * x_{t-(K-1)+j}``.

    ``x`` [B, T, C] (this call's rows), ``w`` [K, C], ``tail`` [B, K-1, C]
    (the rows before ``x``; zeros at a sequence's start). Returns ``y``
    [B, T, C] float32 (before any activation) and the new tail: the last
    ``K - 1`` rows of ``tail ++ x`` or, with ``valid_len`` (a traced
    scalar: ``x`` is padded past it), the ``K - 1`` rows that end at
    ``valid_len``."""
    k, t = w.shape[0], x.shape[1]
    xp = jnp.concatenate([tail.astype(x.dtype), x], axis=1)
    wf = w.astype(jnp.float32)
    y = sum(wf[j] * xp[:, j:j + t].astype(jnp.float32) for j in range(k))
    start = t if valid_len is None else valid_len
    new_tail = jax.lax.dynamic_slice_in_dim(xp, start, k - 1, axis=1)
    return y, new_tail


def _mask_padding(g, beta, valid_len):
    """Positions at or past ``valid_len`` become no-ops: decay 1, write 0."""
    if valid_len is None:
        return g, beta
    keep = (jnp.arange(g.shape[1]) < valid_len)[None, :, None]
    return jnp.where(keep, g, 0.0), jnp.where(keep, beta, 0.0)


@jax.named_scope("gdn_update")
def gated_delta_step(q, k, v, g, beta, state):
    """One token for every row. ``q``, ``k`` [B, H, dk]; ``v`` [B, H, dv];
    ``g`` (log decay), ``beta`` [B, H]; ``state`` [B, H, dv, dk] float32.
    Returns ``(o [B, H, dv], new state)``, float32. Products are taken
    elementwise and summed, so no matmul precision enters."""
    f32 = jnp.float32
    q, k, v = q.astype(f32), k.astype(f32), v.astype(f32)
    sd = jnp.exp(g.astype(f32))[..., None, None] * state
    sk = jnp.sum(sd * k[..., None, :], -1)
    u = beta.astype(f32)[..., None] * (v - sk)
    new = sd + u[..., :, None] * k[..., None, :]
    return jnp.sum(new * q[..., None, :], -1), new


def gated_delta_serial(q, k, v, g, beta, state, valid_len=None):
    """The recurrence token by token. ``q``, ``k`` [B, T, H, dk]; ``v``
    [B, T, H, dv]; ``g``, ``beta`` [B, T, H]; ``state`` [B, H, dv, dk].
    Returns ``(o [B, T, H, dv], final state)``, float32."""
    g, beta = _mask_padding(g.astype(jnp.float32),
                            beta.astype(jnp.float32), valid_len)

    def body(s, xs):
        o, s = gated_delta_step(*xs, s)
        return s, o

    xs = tuple(jnp.moveaxis(a, 1, 0) for a in (q, k, v, g, beta))
    final, o = jax.lax.scan(body, state.astype(jnp.float32), xs)
    return jnp.moveaxis(o, 0, 1), final


@jax.named_scope("gdn_chunk")
def gated_delta_chunked(q, k, v, g, beta, state, valid_len=None,
                        chunk: int = CHUNK, mm_dtype=jnp.float32):
    """The chunkwise (WY) form; same arguments and results as
    :func:`gated_delta_serial`. ``T`` is padded to a multiple of ``chunk``
    with no-op positions; ``mm_dtype`` is the matmul operands' type."""
    f32 = jnp.float32
    b, t, h, dk = k.shape
    dv = v.shape[-1]
    g, beta = _mask_padding(g.astype(f32), beta.astype(f32), valid_len)
    pad = (-t) % chunk
    if pad:
        q, k, v, g, beta = (
            jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
            for a in (q, k, v, g, beta))
    n = (t + pad) // chunk

    def chunks(a):  # [B, n*C, H, ...] -> [B, H, n, C, ...]
        a = a.reshape((b, n, chunk) + a.shape[2:])
        return jnp.moveaxis(a, 3, 1)

    def mm(eq, x, y):
        return jnp.einsum(eq, x.astype(mm_dtype), y.astype(mm_dtype),
                          preferred_element_type=f32)

    qc, kc, vc = (chunks(a.astype(f32)) for a in (q, k, v))
    gc, bc = chunks(g), chunks(beta)                    # [B, H, n, C]
    cum = jnp.cumsum(gc, -1)                            # log G_t
    lower = jnp.tril(jnp.ones((chunk, chunk), bool))
    strict = jnp.tril(jnp.ones((chunk, chunk), bool), -1)
    diff = cum[..., :, None] - cum[..., None, :]        # log(G_t / G_i)
    decay = jnp.where(lower, jnp.exp(jnp.where(lower, diff, 0.0)), 0.0)
    a_mat = jnp.where(strict, bc[..., None] * decay
                      * mm("...ck,...dk->...cd", kc, kc), 0.0)
    rhs = jnp.concatenate(
        [bc[..., None] * vc, (bc * jnp.exp(cum))[..., None] * kc], -1)
    solved = jax.scipy.linalg.solve_triangular(
        a_mat + jnp.eye(chunk, dtype=f32), rhs, lower=True,
        unit_diagonal=True)
    u0, w = solved[..., :dv], solved[..., dv:]          # U', W
    qk = jnp.where(lower, decay * mm("...ck,...dk->...cd", qc, kc), 0.0)
    gq = jnp.exp(cum)[..., None] * qc                   # G_t q_t
    g_end = cum[..., -1]                                # [B, H, n]
    kd = jnp.exp(g_end[..., None] - cum)[..., None] * kc

    def body(s, xs):
        u0_i, w_i, qk_i, gq_i, kd_i, ge_i = xs
        u = u0_i - mm("bhck,bhvk->bhcv", w_i, s)
        o = mm("bhck,bhvk->bhcv", gq_i, s) + mm("bhcd,bhdv->bhcv", qk_i, u)
        s = jnp.exp(ge_i)[..., None, None] * s \
            + mm("bhcv,bhck->bhvk", u, kd_i)
        return s, o

    xs = tuple(jnp.moveaxis(a, 2, 0) for a in (u0, w, qk, gq, kd, g_end))
    final, o = jax.lax.scan(body, state.astype(f32), xs)
    o = jnp.moveaxis(o, 0, 2)                           # [B, H, n, C, dv]
    o = jnp.moveaxis(o, 1, 3).reshape(b, n * chunk, h, dv)
    return o[:, :t], final
