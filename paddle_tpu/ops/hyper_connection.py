"""The hyper-connection between two sublayers as ONE Pallas kernel.

A model with ``n`` residual streams (Xing4.0's ``hc_mult``) reads each
sublayer's input off the streams and writes its output back into them::

    H_pre, H_post, H_res = mixers(X)            # per position, float32
    u  = RMSNorm(sum_j H_pre[j] X[j])           # the sublayer's input
    X' = H_res X + outer(H_post, y)             # y = sublayer(u)

Written with ``jax.numpy`` (``models/xing4.py`` ``HyperConnection``, the
parity reference of ``tests/test_hyper_connection.py``) that is a hundred
XLA kernels and twelve passes over ``X`` a sublayer. Here the streams lie
``[positions, n * h]`` (stream ``j`` the lane slice ``[j h, (j + 1) h)``)
and ONE kernel over tiles of positions does, per position and in float32:

1. ``X' = H_res X + outer(H_post, y)`` from the PREVIOUS sublayer's mixers
   and output (:func:`hyper_connection` with ``prev``);
2. the mean square of ``flatten(X')`` and ``z = flatten(X') (W * norm)``:
   on the MXU, float32-accurate. ``X'`` and the matrix are each cut into
   three bfloat16 parts (high, middle, low: 24 bits together) and every
   product of two parts is taken, the matrix's parts side by side in the
   lanes of one weight tile, so three passes give all nine (``Precision.
   HIGHEST`` means the six largest);
3. sigmoid, 2 sigmoid and ``sinkhorn(exp(clip(.)))`` on the tile's mixers
   TRANSPOSED, positions in the lanes (``[n, positions]`` a column of
   ``H_res``): a few whole vector registers a normalisation;
4. ``u`` and the sublayer's RMSNorm, out in the weights' dtype and, where
   asked, unrounded as well (an expert layer's router scores that);
5. writes ``X'``, ``u`` and the mixers ``[positions, 128]`` float32 that
   step 1 of the next call reads (columns: ``H_pre`` at ``0 .. n``,
   ``H_post`` at ``n .. 2n``, ``H_res[i, j]`` at ``2n + j n + i``).

So a sublayer costs one read and one write of the streams.
:func:`hyper_connection_update` is step 1 alone (behind a layer's last
sublayer). On a TPU the kernel; elsewhere its body in the Pallas
interpreter (:func:`~paddle_tpu.ops.pallas_ops._use_interpret`).
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .pallas_ops import _use_interpret

__all__ = ["MixerParams", "pack_mixer_params", "hyper_connection",
           "hyper_connection_update"]

F32, BF16 = jnp.float32, jnp.bfloat16
_LANES = 128
_ROWS = 16    # positions a turn of the inner loops: a whole bf16 tile
_TILE = 128   # positions a grid step, at most
_CHUNK = 512  # lanes of one stream an inner step works on
# (The loops over a tile's chunks and over Sinkhorn's rounds are
# `lax.fori_loop`s, not Python loops: every serving program lowers its
# kernels again at each process start, compile cache or not, and the
# unrolled body, 1,300 equations, cost nine programs 12 s of set-up.)
_VMEM_LIMIT = 100 * 1024 * 1024


class MixerParams(NamedTuple):
    """One sublayer's mixer weights as the kernel reads them."""

    #: ``(W * norm)`` ``[n h, 128]`` bfloat16, columns in the kernel's
    #: order, its high, middle and low parts at lanes 0, ``c``, ``2 c``
    w: jax.Array
    #: ``[2 c, 1]`` float32: each column's scale, then each column's bias
    ab: jax.Array


def _columns(n: int):
    """(columns, the kernel's column -> the model's column). The model's
    ``H_res`` columns are row-major (``i n + j``); the kernel wants one
    COLUMN of the matrix in consecutive rows (``j n + i``)."""
    order = list(range(2 * n)) + [2 * n + i * n + j
                                  for j in range(n) for i in range(n)]
    return len(order), order


def _padded(cols: int) -> int:
    c = -(-cols // 8) * 8
    if 3 * c >= _LANES:
        raise ValueError(f"{cols} mixer columns do not fit one weight tile")
    return c


def _split3(x):
    """``x`` float32 -> three bfloat16 parts whose sum is ``x`` to 24
    bits."""
    hi = x.astype(BF16)
    rest = x - hi.astype(F32)
    mid = rest.astype(BF16)
    return hi, mid, (rest - mid.astype(F32)).astype(BF16)


def pack_mixer_params(w, norm, a, b, n: int) -> MixerParams:
    """``w`` ``[n h, 2n + n n]``, ``norm`` ``[n h]`` (the RMSNorm's gain,
    folded into the matrix), ``a`` ``[3]`` and ``b`` ``[2n + n n]`` as the
    model keeps them."""
    cols, order = _columns(n)
    c = _padded(cols)
    order = jnp.asarray(order)
    wn = (w.astype(F32) * norm.astype(F32)[:, None])[:, order]
    wn = jnp.pad(wn, ((0, 0), (0, c - cols)))
    packed = jnp.concatenate(_split3(wn), axis=1)
    a = a.astype(F32)
    scale = jnp.concatenate([jnp.repeat(a[:2], n), jnp.repeat(a[2:], n * n)])
    ab = jnp.concatenate([jnp.pad(scale, (0, c - cols)),
                          jnp.pad(b.astype(F32)[order], (0, c - cols))])
    return MixerParams(jnp.pad(packed, ((0, 0), (0, _LANES - 3 * c))),
                       ab[:, None])


# --------------------------------------------------- the body's arithmetic


def _sinkhorn(cols, iters: int, eps: float):
    """``cols[j]`` ``[n, positions]``: column ``j`` of each position's
    matrix. Rows, then columns, divided by their sums plus ``eps``."""
    def one_round(_, cols):
        rows = cols[0]
        for c in cols[1:]:
            rows = rows + c
        cols = [c / (rows + eps) for c in cols]
        return tuple(c / (jnp.sum(c, axis=0, keepdims=True) + eps)
                     for c in cols)
    return jax.lax.fori_loop(0, iters, one_round, tuple(cols))


def _read_out(pre, streams):
    """``sum_j H_pre[j] X[j]`` (``pre[j]`` ``[rows, 1]``)."""
    u = pre[0] * streams[0]
    for p, x in zip(pre[1:], streams[1:]):
        u = u + p * x
    return u


def _update(res, post, streams, y):
    """Stream ``i`` of ``H_res X + outer(H_post, y)`` for every ``i``."""
    out = []
    for r, p in zip(res, post):
        acc = r[0] * streams[0]
        for rj, x in zip(r[1:], streams[1:]):
            acc = acc + rj * x
        out.append(acc + p * y)
    return out


def _kernel(*refs, n, h, c, iters, rms_eps, hc_eps, clamp, has_prev,
            want_mix, want_f32, tp, lt):
    refs = list(refs)
    x_ref = refs.pop(0)
    if has_prev:
        y_ref, mp_ref = refs.pop(0), refs.pop(0)
    if want_mix:
        w_ref, ab_ref, g_ref = refs.pop(0), refs.pop(0), refs.pop(0)
    src = xo_ref = refs.pop(0) if has_prev else x_ref
    if want_mix:
        u_ref = refs.pop(0)
        u32_ref = refs.pop(0) if want_f32 else None
        mix_ref = refs.pop(0)
        parts = [refs.pop(0) for _ in range(3)]
        ms_ref, s_ref, zt_ref, mt_ref, us_ref = refs
    k = n * h
    ch = _CHUNK if h % _CHUNK == 0 else (_LANES if h % _LANES == 0 else h)

    def rows_of(r):
        return pl.ds(pl.multiple_of(r * _ROWS, _ROWS), _ROWS)

    def lanes_of(ci, base=0):
        """Chunk ``ci`` of the stream that starts at lane ``base`` (a
        multiple of ``h``, and so of ``ch``)."""
        return pl.ds(pl.multiple_of(base + ci * ch, ch), ch)

    def column(m, i):
        return m[:, i:i + 1]

    # 1 and the first half of 2: the streams' update, its squares' sum and
    # its three bfloat16 parts, sixteen positions and one chunk a turn
    def update(r, carry):
        sl = rows_of(r)
        if has_prev:
            m = mp_ref[sl, :]
            post = [column(m, n + i) for i in range(n)]
            res = [[column(m, 2 * n + j * n + i) for j in range(n)]
                   for i in range(n)]

        def chunk(ci, sq):
            xs = [x_ref[sl, lanes_of(ci, j * h)] for j in range(n)]
            if has_prev:
                xs = _update(res, post, xs,
                             y_ref[sl, lanes_of(ci)].astype(F32))
                for i, v in enumerate(xs):
                    xo_ref[sl, lanes_of(ci, i * h)] = v
            if want_mix:
                for i, v in enumerate(xs):
                    sq = sq + v * v
                    for part, ref in zip(_split3(v), parts):
                        ref[sl, lanes_of(ci, i * h)] = part
            return sq

        sq = jax.lax.fori_loop(0, h // ch, chunk, jnp.zeros((_ROWS, ch), F32))
        if want_mix:
            ms_ref[sl, :] = jnp.broadcast_to(
                jnp.sum(sq, axis=1, keepdims=True), (_ROWS, _LANES))
        return carry

    jax.lax.fori_loop(0, tp // _ROWS, update, 0)
    if not want_mix:
        return

    # 2: z on the MXU, smallest parts first; the squares' sum rides in
    # lane 127 through the transposition
    w = w_ref[...]
    s = None
    for ref in reversed(parts):
        d = jnp.dot(ref[...], w, preferred_element_type=F32)
        s = d if s is None else s + d
    lane = jax.lax.broadcasted_iota(jnp.int32, (tp, _LANES), 1)
    s = jnp.where(lane == _LANES - 1, ms_ref[...], s)
    if tp < lt:  # a short tile: whole lane tiles of positions all the same
        s_ref[...] = jnp.zeros_like(s_ref)
        s_ref[0:tp, :] = s
        s = s_ref[...]
    zt_ref[...] = s.T

    # 3: the mixers, positions in the lanes
    inv = jax.lax.rsqrt(zt_ref[_LANES - 1:_LANES, :] / k + rms_eps)
    z = (zt_ref[2 * c:3 * c, :] + zt_ref[c:2 * c, :] + zt_ref[0:c, :]) * inv
    zt_ref[0:c, :] = ab_ref[0:c, :] * z + ab_ref[c:2 * c, :]
    mt_ref[...] = jnp.zeros_like(mt_ref)
    mt_ref[0:n, :] = jax.nn.sigmoid(zt_ref[0:n, :])
    mt_ref[n:2 * n, :] = 2.0 * jax.nn.sigmoid(zt_ref[n:2 * n, :])
    cols = _sinkhorn(
        [jnp.exp(jnp.clip(zt_ref[2 * n + j * n:2 * n + (j + 1) * n, :],
                          clamp[0], clamp[1])) for j in range(n)],
        iters, hc_eps)
    for j, col in enumerate(cols):
        mt_ref[2 * n + j * n:2 * n + (j + 1) * n, :] = col
    mix_ref[...] = mt_ref[...].T[0:tp, :]

    # 4: the sublayer's input off the updated streams (kept unscaled in
    # the scratch while its squares are summed), and its norm
    def read_out(r, carry):
        sl = rows_of(r)
        m = mix_ref[sl, :]
        pre = [column(m, j) for j in range(n)]

        def chunk(ci, sq):
            u = _read_out(pre, [src[sl, lanes_of(ci, j * h)]
                                for j in range(n)])
            us_ref[:, lanes_of(ci)] = u
            return sq + u * u

        sq = jax.lax.fori_loop(0, h // ch, chunk, jnp.zeros((_ROWS, ch), F32))
        inv = jax.lax.rsqrt(jnp.sum(sq, axis=1, keepdims=True) / h + rms_eps)

        def scale(ci, carry):
            o = us_ref[:, lanes_of(ci)] * inv * g_ref[:, lanes_of(ci)]
            if want_f32:
                u32_ref[sl, lanes_of(ci)] = o
            u_ref[sl, lanes_of(ci)] = o.astype(u_ref.dtype)
            return carry

        jax.lax.fori_loop(0, h // ch, scale, 0)
        return carry

    jax.lax.fori_loop(0, tp // _ROWS, read_out, 0)


@functools.partial(jax.jit, static_argnames=(
    "n", "iters", "rms_eps", "hc_eps", "clamp", "want_f32", "out_dtype"))
def _call(x, y, mix, w, ab, gain, *, n, iters=0, rms_eps=0.0, hc_eps=0.0,
          clamp=(0.0, 0.0), want_f32=False, out_dtype=None):
    """The launch on ``x`` ``[positions, n h]``; jitted so that a model's
    sublayers, which call it with the same shapes, share one traced and
    lowered kernel inside a step program. ``y`` and ``mix`` None: no
    update (a stack's first sublayer); ``w`` None: the update alone."""
    p, k = x.shape
    h = k // n
    has_prev, want_mix = y is not None, w is not None
    tp = min(_TILE, -(-p // _ROWS) * _ROWS)  # a decode step: one short tile
    lt = -(-tp // _LANES) * _LANES
    c = _padded(_columns(n)[0])
    row = lambda width: pl.BlockSpec((tp, width), lambda i: (i, 0))
    whole = lambda a: pl.BlockSpec(a.shape, lambda i: (0, 0))
    args, in_specs, out_shape, out_specs, scratch = [x], [row(k)], [], [], []
    if has_prev:
        args += [y, mix]
        in_specs += [row(h), row(_LANES)]
        out_shape.append(jax.ShapeDtypeStruct((p, k), F32))
        out_specs.append(row(k))
    if want_mix:
        ab = jnp.broadcast_to(ab, (2 * c, lt))
        gain = gain.astype(F32).reshape(1, h)
        args += [w, ab, gain]
        in_specs += [whole(w), whole(ab), whole(gain)]
        for dtype in [out_dtype] + [F32] * want_f32:
            out_shape.append(jax.ShapeDtypeStruct((p, h), dtype))
            out_specs.append(row(h))
        out_shape.append(jax.ShapeDtypeStruct((p, _LANES), F32))
        out_specs.append(row(_LANES))
        scratch = [pltpu.VMEM((tp, k), BF16)] * 3 + [
            pltpu.VMEM((tp, _LANES), F32), pltpu.VMEM((lt, _LANES), F32),
            pltpu.VMEM((_LANES, lt), F32), pltpu.VMEM((_LANES, lt), F32),
            pltpu.VMEM((_ROWS, h), F32)]
    # what the call moves and computes, for the compiler's own count (its
    # scheduler's, and `cost_analysis()`'s, which sees no inside of a
    # kernel): every array once; the update, the three MXU passes, the
    # squares, the read-out and its norm
    nbytes = sum(a.size * a.dtype.itemsize for a in args) + sum(
        o.size * jnp.dtype(o.dtype).itemsize for o in out_shape)
    flops = p * k * (2 * (n + 1) * has_prev
                     + (2 + 6 * _LANES + 2) * want_mix) + 4 * p * h * want_mix
    cost = pl.CostEstimate(
        flops=flops, bytes_accessed=nbytes,
        transcendentals=p * (_columns(n)[0] + 2) * want_mix)
    return pl.pallas_call(
        functools.partial(
            _kernel, n=n, h=h, c=c, iters=iters, rms_eps=rms_eps,
            hc_eps=hc_eps, clamp=clamp, has_prev=has_prev, want_mix=want_mix,
            want_f32=want_f32, tp=tp, lt=lt),
        grid=(-(-p // tp),),
        in_specs=in_specs, out_specs=out_specs, out_shape=out_shape,
        scratch_shapes=scratch,
        input_output_aliases={0: 0} if has_prev else {},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=_VMEM_LIMIT),
        cost_estimate=cost,
        interpret=_use_interpret(),
        name="hyper_connection",
    )(*args)


def hyper_connection(x, params: MixerParams, gain, *, n: int, iters: int,
                     rms_eps: float, hc_eps: float, clamp, out_dtype,
                     prev=None, want_f32: bool = False):
    """One sublayer's read-out, behind the previous sublayer's update.

    ``x`` ``[..., n h]`` float32, the streams; ``prev``: None, or ``(y
    [..., h], mix)`` of the sublayer before (its output and the mixers this
    function returned for it): the streams are updated first. ``gain``
    ``[h]``: the sublayer's own RMSNorm (``rms_eps`` is that norm's and the
    mixers' alike). Returns ``(X' (``x`` itself
    without ``prev``), u [..., h] in out_dtype, u unrounded (None unless
    want_f32), mix [..., 128] float32)``."""
    lead, k = x.shape[:-1], x.shape[-1]
    flat = lambda a: a.reshape(-1, a.shape[-1])
    y, mix = (None, None) if prev is None else map(flat, prev)
    out = _call(flat(x), y, mix, params.w, params.ab, gain, n=n, iters=iters,
                rms_eps=float(rms_eps), hc_eps=float(hc_eps),
                clamp=(float(clamp[0]), float(clamp[1])),
                want_f32=want_f32, out_dtype=jnp.dtype(out_dtype))
    out = [o.reshape(lead + o.shape[-1:]) for o in out]
    new_x = out.pop(0) if prev is not None else x
    u = out.pop(0)
    u32 = out.pop(0) if want_f32 else None
    return new_x, u, u32, out.pop(0)


def hyper_connection_update(x, y, mix, *, n: int):
    """``H_res X + outer(H_post, y)`` alone: ``x`` ``[..., n h]`` float32,
    ``y`` ``[..., h]``, ``mix`` as :func:`hyper_connection` returned it."""
    flat = lambda a: a.reshape(-1, a.shape[-1])
    out, = _call(flat(x), flat(y), flat(mix), None, None, None, n=n)
    return out.reshape(x.shape)
