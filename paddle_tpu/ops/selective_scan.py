"""The selective state-space scan (Mamba-1, arXiv:2312.00752) on raw arrays.

Per channel ``c`` of ``D`` and state index ``n`` of ``N``, with a step size
``d_t`` in R^D, an input ``x_t`` in R^D, and the token's own ``B_t``, ``C_t``
in R^N (``A`` in R^{D x N} negative, ``skip`` in R^D)::

    S_t = exp(d_t * A) * S_{t-1} + (d_t * x_t) B_t^T        # elementwise in (c, n)
    y_t = S_t C_t + skip * x_t

The decay differs per channel AND per state index, so the recurrence has no
matmul (chunkwise "SSD") form: every ``S_t`` is an elementwise update of
``D * N`` numbers. Two forms of it:

* :func:`selective_scan` - the prefill form: ONE pass over the tokens in
  chunks of :data:`CHUNK` (``lax.scan`` over the chunks, a chunk's tokens
  unrolled inside the body so that the compiler fuses their updates and the
  loop's own cost is paid once a chunk). Only ``[B, N, D]`` of state is live:
  the ``[T, D, N]`` tensor of a naive scan (2.7 GB of float32 at T = 8192,
  D = 5120) never exists;
* :func:`selective_step` - the decode form, one token for every lane.

State and accumulation are float32, whatever the inputs' type. The state is
kept ``[B, N, D]``, the channels minor: on the chip the minor axis fills the
128 lanes of a register, and ``N`` = 16 there would leave seven eighths of
each empty.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

CHUNK = 16


def _update(state, d, x, b, c, a_t, skip):
    """One token: ``state`` [B, N, D]; ``d``, ``x`` [B, D]; ``b``, ``c``
    [B, N]; ``a_t`` [N, D] (A transposed); ``skip`` [D]. float32."""
    state = jnp.exp(d[:, None, :] * a_t) * state \
        + (d * x)[:, None, :] * b[:, :, None]
    y = jnp.sum(state * c[:, :, None], axis=1) + skip * x
    return state, y


@jax.named_scope("ssm_step")
def selective_step(x, d, b, c, a, skip, state):
    """One token for every row. ``x``, ``d`` [B, D]; ``b``, ``c`` [B, N];
    ``a`` [D, N]; ``skip`` [D]; ``state`` [B, N, D] float32. Returns
    ``(y [B, D], new state)``, float32."""
    f32 = jnp.float32
    new, y = _update(state.astype(f32), d.astype(f32), x.astype(f32),
                     b.astype(f32), c.astype(f32), a.astype(f32).T,
                     skip.astype(f32))
    return y, new


@jax.named_scope("ssm_scan")
def selective_scan(x, d, b, c, a, skip, state, valid_len=None,
                   chunk: int = CHUNK):
    """The recurrence over ``T`` tokens. ``x``, ``d`` [B, T, D]; ``b``,
    ``c`` [B, T, N]; ``a`` [D, N]; ``skip`` [D]; ``state`` [B, N, D] (the
    state before the first token). Returns ``(y [B, T, D], final state)``,
    float32. Positions at or past ``valid_len`` (a traced scalar: the true
    length under a padded bucket) leave the state as it is: their step size
    is zero, so they decay by 1 and write nothing."""
    f32 = jnp.float32
    bsz, t, _ = x.shape
    x, d, b, c = (v.astype(f32) for v in (x, d, b, c))
    if valid_len is not None:
        d = jnp.where((jnp.arange(t) < valid_len)[None, :, None], d, 0.0)
    pad = (-t) % chunk
    if pad:
        x, d, b, c = (jnp.pad(v, ((0, 0), (0, pad), (0, 0)))
                      for v in (x, d, b, c))
    n = (t + pad) // chunk
    a_t, skip = a.astype(f32).T, skip.astype(f32)

    def chunks(v):  # [B, n*C, W] -> [n, C, B, W]
        return jnp.transpose(v.reshape(bsz, n, chunk, v.shape[-1]),
                             (1, 2, 0, 3))

    def body(s, xs):
        xc, dc, bc, cc = xs
        ys = []
        for i in range(chunk):
            s, y = _update(s, dc[i], xc[i], bc[i], cc[i], a_t, skip)
            ys.append(y)
        return s, jnp.stack(ys)

    final, y = jax.lax.scan(body, state.astype(f32),
                            tuple(chunks(v) for v in (x, d, b, c)))
    y = jnp.transpose(y, (2, 0, 1, 3)).reshape(bsz, n * chunk, -1)
    return y[:, :t], final
