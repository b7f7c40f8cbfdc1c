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
  ``U = U' - W S_0^T`` after ONE unit-lower-triangular system a chunk (the
  WY form), and one pass over the chunks carries ``S``. Everything is kept
  in LOG decay (``G_t - G_i <= 0`` wherever it is exponentiated), so
  nothing is divided by a decay and no ``1 / G`` can overflow. It runs in
  one of two forms, chosen by the device as the paged kernels and
  ``grouped_matmul`` are (:func:`~paddle_tpu.ops.pallas_ops._use_interpret`):

  - on a TPU ONE Pallas kernel, ``gdn_chunk`` (:func:`_gdn_kernel`): a grid
    step takes a few chunks of a few heads, builds their decays, ``A`` and
    ``Q K^T`` in VMEM, inverts ``I + A`` in float32 (the 8 x 8 diagonal
    blocks by substitution, the blocks under them by matmuls) and walks
    each head's chunks with its state resident in VMEM: nothing between
    the head-major ``q``, ``k``, ``v`` and ``o`` goes through HBM;
  - elsewhere (the CPU tests) the ``jax.numpy`` form (:func:`_chunked_xla`:
    ``solve_triangular`` and a ``lax.scan`` over the chunks), which is also
    the kernel's parity reference; ``interpret=True`` runs the kernel's
    body in the Pallas interpreter whatever the device;

* :func:`gated_delta_step` - the decode form, one token for every lane.

State and accumulation are float32. ``mm_dtype`` is the type of the chunked
form's matmul OPERANDS (bfloat16 under a bfloat16 model), in both of its
forms at the same places; the triangular system, the decays and the
single-token update stay float32.

:func:`causal_conv` is the short depthwise convolution in front of the rule,
with the ``K - 1`` rows it has to carry from one call to the next.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .pallas_ops import _use_interpret

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


def _pad_chunks(q, k, v, g, beta, valid_len, multiple: int):
    """The rule's arguments with padded positions made no-ops and ``T``
    padded to a multiple of ``multiple`` with more of them."""
    f32 = jnp.float32
    g, beta = _mask_padding(g.astype(f32), beta.astype(f32), valid_len)
    pad = (-k.shape[1]) % multiple
    if pad:
        q, k, v, g, beta = (
            jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
            for a in (q, k, v, g, beta))
    return q, k, v, g, beta


@jax.named_scope("gdn_chunk")
def gated_delta_chunked(q, k, v, g, beta, state, valid_len=None,
                        chunk: int = CHUNK, mm_dtype=jnp.float32,
                        interpret: bool = False):
    """The chunkwise (WY) form; same arguments and results as
    :func:`gated_delta_serial`. ``T`` is padded to whole chunks with no-op
    positions; ``mm_dtype`` is the matmul operands' type. On a TPU the
    ``gdn_chunk`` kernel, elsewhere the ``jax.numpy`` form; ``interpret``
    runs the kernel's body in the Pallas interpreter whatever the
    device."""
    if not interpret and _use_interpret():
        return _chunked_xla(q, k, v, g, beta, state, valid_len, chunk,
                            mm_dtype)
    b, t, h, dk = k.shape
    nc = min(_CHUNKS_A_STEP, -(-t // chunk))
    q, k, v, g, beta = _pad_chunks(q, k, v, g, beta, valid_len, nc * chunk)
    n = q.shape[1] // chunk

    def heads_first(a):  # [B, n C, H, d] -> [B H, n, C, d]
        return jnp.moveaxis(a, 2, 1).reshape(b * h, n, chunk, a.shape[-1])

    gb = jnp.stack([g, beta], -1)                       # [B, n C, H, 2]
    gb = jnp.moveaxis(gb.reshape(b, n, chunk, h, 2), (3, 4), (1, 3))
    o, final = _gdn_call(
        heads_first(q), heads_first(k), heads_first(v),
        gb.reshape(b * h, n, 2, chunk),
        jnp.swapaxes(state.astype(jnp.float32), 2, 3).reshape(b * h, dk, -1),
        nc=nc, mm_dtype=jnp.dtype(mm_dtype), interpret=interpret)
    o = jnp.moveaxis(o.reshape(b, h, n * chunk, -1), 1, 2)
    final = jnp.swapaxes(final.reshape(b, h, dk, -1), 2, 3)
    return o[:, :t], final


# ------------------------------------------------------ the Pallas kernel

#: heads (the largest divisor of their number up to it) and chunks of each
#: that a grid step takes: their triangular systems are inverted side by
#: side (the substitution's steps and the matmuls of one chunk wait on each
#: other; twelve chunks' fill the gaps), then each head's state walks its
#: chunks, the heads' walks filling each other's waits. By my chip runs, PR
#: 42, at `[1, 3072, 30, 96 / 192]`, the launch alone: 1 x 4: 2.11 ms, 2 x
#: 4: 1.83, 5 x 2: 1.64, 6 x 2: 1.63
_HEADS_A_STEP = 6
_CHUNKS_A_STEP = 2
#: the diagonal blocks inverted by substitution; the blocks under them are
#: merged by matmuls, doubling the block each time. A substitution step
#: costs a fifth of a merge (two float32 products at six MXU passes each):
#: 16 -> 8 took 0.10 ms of 1.83 off the launch, 1 (merges alone) added 0.3
_BLOCK = 8


def _dot(x, y, dims, mm_dtype):
    """``x . y`` over ``dims`` with operands in ``mm_dtype``, accumulated
    in float32; float32 operands are multiplied as float32 (the MXU's
    default would round them to bfloat16)."""
    f32 = jnp.float32
    precision = (jax.lax.Precision.HIGHEST if mm_dtype == f32
                 else jax.lax.Precision.DEFAULT)
    return jax.lax.dot_general(x.astype(mm_dtype), y.astype(mm_dtype), dims,
                               precision=precision,
                               preferred_element_type=f32)


_NN = (((1,), (0,)), ((), ()))       # [m, k] [k, n]
_TN = (((0,), (0,)), ((), ()))       # [k, m] [k, n]
_BNN = (((2,), (1,)), ((0,), (0,)))  # the same with a leading batch axis
_BNT = (((2,), (2,)), ((0,), (0,)))


def _unit_lower_inverse(a, at, at_ref, row, lane):
    """``(I + a)^-1`` for ``a`` ``[chunks, C, C]`` strictly lower
    triangular, float32 throughout; ``at`` is ``a`` TRANSPOSED and
    ``at_ref`` VMEM of the same shape (the substitution reads single rows
    of it). The ``_BLOCK``-wide diagonal blocks by substitution on the
    columns, last to first, every block of every chunk at once: with ``T (I
    + a) = I`` column ``c`` of a block is ``e_c - sum_{j > c} T[:, j] a[j,
    c]``, and the coefficients ``a[j, c]`` of all the blocks are the rows
    ``c``, ``_BLOCK + c``, ... of the transpose, which do not overlap. The
    blocks under the diagonal by ``T <- T - T (a in the new blocks) T``,
    the block doubling each time. Both are substitution, not a Neumann
    product: ``beta`` reaches 2 here, and the powers of ``a`` then grow to
    1e6 beside an inverse of order 1."""
    f32 = jnp.float32
    c_ = a.shape[-1]
    blk = min(_BLOCK, c_)
    same = (row // blk) == (lane // blk)
    t0 = jnp.broadcast_to((row == lane).astype(f32), a.shape)
    at_ref[...] = jnp.where(same, at, 0.0)

    def column(i, t):
        c = blk - 2 - i
        coef = at_ref[:, pl.ds(c, 1), :]
        for b in range(1, c_ // blk):
            coef = coef + at_ref[:, pl.ds(b * blk + c, 1), :]
        col = ((row % blk) == c).astype(f32) - jnp.sum(
            t * coef, axis=2, keepdims=True)
        return jnp.where(same & ((lane % blk) == c), col, t)

    t = jax.lax.fori_loop(0, blk - 1, column, t0)
    while blk < c_:
        under = ((row // (2 * blk)) == (lane // (2 * blk))) & (
            (row // blk) > (lane // blk))
        ta = _dot(t, jnp.where(under, a, 0.0), _BNN, f32)
        t = t - _dot(ta, t, _BNN, f32)
        blk *= 2
    return t


def _gdn_kernel(q_ref, k_ref, v_ref, gb_ref, s0_ref, o_ref, s_ref, at_ref,
                *, mm_dtype):
    """The next ``nc`` chunks of ``hb`` heads. ``q_ref``, ``k_ref`` ``[hb,
    nc, C, dk]``, ``v_ref`` ``[hb, nc, C, dv]``, ``gb_ref`` ``[hb, nc, 2,
    C]`` (log decay, write strength), ``s0_ref`` ``[hb, dk, dv]`` the
    heads' states before their first chunk, TRANSPOSED (so is ``s_ref``:
    every product of the walk then has its operands as they lie, and the
    one transpose left, of ``decay K``, does not wait for the state);
    ``o_ref`` ``[hb, nc, C, dv]``; ``s_ref`` stays in VMEM over the heads'
    grid steps and goes out after the last. The names are the
    ``jax.numpy`` form's."""
    f32 = jnp.float32
    hb, nc, chunk, _ = q_ref.shape

    @pl.when(pl.program_id(1) == 0)
    def _():
        s_ref[...] = s0_ref[...]

    def chunks(ref):  # [hb, nc, ...] -> [hb nc, ...]
        return ref[...].reshape((hb * nc,) + ref.shape[2:])

    row = jax.lax.broadcasted_iota(jnp.int32, (1, chunk, chunk), 1)
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, chunk, chunk), 2)
    lower, eye = row >= lane, row == lane
    q, k, v = (chunks(r).astype(f32) for r in (q_ref, k_ref, v_ref))
    gb = chunks(gb_ref)
    g, beta = gb[:, 0:1, :], gb[:, 1:2, :]              # [., 1, C]: rows
    # a row of the chunk's positions -> a column: through the diagonal
    cum = jnp.sum(jnp.where(lower, g, 0.0), axis=2, keepdims=True)
    cum_row = jnp.sum(jnp.where(eye, cum, 0.0), axis=1, keepdims=True)
    bcol = jnp.sum(jnp.where(eye, beta, 0.0), axis=2, keepdims=True)
    # log(G_t / G_i) under the diagonal, log(G_i / G_t) above it: one exp
    # gives the decays and their transpose, and no exponent is positive
    diff = cum - cum_row
    e = jnp.exp(jnp.where(lower, diff, -diff))
    decay = jnp.where(lower, e, 0.0)
    kk = _dot(k, k, _BNT, mm_dtype)
    a_mat = jnp.where(row > lane, bcol * decay * kk, 0.0)
    at_mat = jnp.where(row < lane, beta * e * kk, 0.0)
    t = _unit_lower_inverse(a_mat, at_mat, at_ref, row, lane)
    gcol = jnp.exp(cum)                                 # G_t
    u0 = _dot(t, bcol * v, _BNN, f32)                   # U'
    w = _dot(t, (bcol * gcol) * k, _BNN, f32)           # W
    qk = jnp.where(lower, decay * _dot(q, k, _BNT, mm_dtype), 0.0)
    # W and G q meet the state in one product: W's rows, then G q's
    wq = jnp.concatenate([w.astype(mm_dtype), (gcol * q).astype(mm_dtype)],
                         axis=1)
    g_end = cum[:, chunk - 1:chunk, :]                  # [., 1, 1]
    kd = jnp.exp(g_end - cum) * k
    for head in range(hb):
        s = s_ref[head]
        for c in range(nc):
            j = head * nc + c
            ws = _dot(wq[j], s, _NN, mm_dtype)          # [2 C, dv]
            u = u0[j] - ws[:chunk]
            o_ref[head, c] = ws[chunk:] + _dot(qk[j], u, _NN, mm_dtype)
            s = jnp.exp(g_end[j]) * s + _dot(kd[j], u, _TN, mm_dtype)
        s_ref[head] = s


@functools.partial(jax.jit, static_argnames=("nc", "mm_dtype", "interpret"))
def _gdn_call(q, k, v, gb, state, *, nc, mm_dtype, interpret):
    """The launch on head-major arrays: ``q``, ``k`` ``[B H, n, C, dk]``,
    ``v`` ``[B H, n, C, dv]``, ``gb`` ``[B H, n, 2, C]``, ``state`` ``[B H,
    dk, dv]`` (transposed). Jitted so that a model's layers, which call it
    with the same shapes, share one traced and lowered kernel inside a
    program."""
    f32 = jnp.float32
    bh, n, chunk, dk = k.shape
    dv = v.shape[-1]
    hb = next(d for d in range(_HEADS_A_STEP, 0, -1) if bh % d == 0)
    step = lambda *last: pl.BlockSpec((hb, nc) + last,
                                      lambda h, i: (h, i, 0, 0))
    whole = pl.BlockSpec((hb, dk, dv), lambda h, i: (h, 0, 0))
    # what the rule needs a token a head, as the benchmark counts it
    # (benchmark/roofline/hybrid_prefill.py; the float32 products' extra
    # MXU passes are not operations of the rule); every array once
    flops = bh * n * chunk * (4 * chunk * dk + chunk * (dk + dv)
                              + 6 * dk * dv + 2 * chunk * dv)
    nbytes = sum(a.size * a.dtype.itemsize for a in (q, k, v, gb, state)) \
        + 4 * (bh * n * chunk * dv + state.size)
    return pl.pallas_call(
        functools.partial(_gdn_kernel, mm_dtype=mm_dtype),
        grid=(bh // hb, n // nc),
        in_specs=[step(chunk, dk), step(chunk, dk), step(chunk, dv),
                  step(2, chunk), whole],
        out_specs=[step(chunk, dv), whole],
        out_shape=[jax.ShapeDtypeStruct((bh, n, chunk, dv), f32),
                   jax.ShapeDtypeStruct((bh, dk, dv), f32)],
        scratch_shapes=[pltpu.VMEM((hb * nc, chunk, chunk), f32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        cost_estimate=pl.CostEstimate(
            flops=flops, bytes_accessed=nbytes,
            transcendentals=bh * n * chunk * (chunk + 3)),
        interpret=interpret,
        name="gdn_chunk",
    )(q, k, v, gb, state)


def _chunked_xla(q, k, v, g, beta, state, valid_len, chunk, mm_dtype):
    """:func:`gated_delta_chunked` in ``jax.numpy``: every chunk and head
    solved at once (``solve_triangular``), a ``lax.scan`` over the
    chunks."""
    f32 = jnp.float32
    b, t, h, dk = k.shape
    dv = v.shape[-1]
    q, k, v, g, beta = _pad_chunks(q, k, v, g, beta, valid_len, chunk)
    n = q.shape[1] // chunk

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
