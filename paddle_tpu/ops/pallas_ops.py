"""Pallas TPU kernels — the hot-op fusion zoo.

Replaces the reference's CUDA fusion layer (flash_attn integration
ref:paddle/phi/kernels/gpu/flash_attn_kernel.cu:213, fused_attention/
fused_feedforward ref:paddle/phi/kernels/fusion/) with TPU-native Pallas:
blockwise flash attention with online softmax streaming K/V through VMEM,
grid over (batch*heads, q-blocks, k-blocks), fp32 accumulation on the MXU.

Backward is fused Pallas too (≈ ref:paddle/phi/kernels/gpu/flash_attn_grad_kernel.cu):
the forward emits a lane-broadcast log-sum-exp residual; ONE kernel
(``flash_bwd``) gridded over k-blocks builds a tile pair's scores once and
reduces dK/dV across q-blocks into VMEM scratch and dQ across k-blocks into
a float32 accumulator of the whole sequence — O(S) memory, the S×S matrix is
never materialized. Beyond ``_FUSED_BWD_VMEM`` two reduction kernels
(``flash_bwd_dkv``, ``flash_bwd_dq``) run instead. Under a causal mask every
kernel visits a tile by ``_causal_tile``: unmasked under the diagonal,
masked across it (by rows that stop at the diagonal where the tile lies on
it), not at all above it.

Falls back to a pure-XLA reference path for awkward shapes; on CPU the
kernels run in the Pallas interpreter, so the same code path is exercised by
the CPU test mesh.
"""
from __future__ import annotations

import functools
import math
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
_LANES = 128  # residuals (lse, delta) are stored lane-broadcast [.., s, 128]


#: memoized _use_interpret() answers, keyed on (backend, device_count):
#: the default backend is fixed for a process's lifetime (JAX_PLATFORMS),
#: and the probe (jax.default_backend() resolves the backend registry)
#: used to re-run inside every pallas_call trace — three call sites here
#: alone, plus every paged kernel. The device count is PART of the key
#: (ISSUE 16): a forced ``xla_force_host_platform_device_count`` mesh is
#: a different runtime than the single-device probe that may have
#: resolved first — the blind "one entry, reuse it" fast path reused the
#: single-device answer there. Clear it (tests only) after swapping
#: platforms mid-process.
_INTERPRET_MEMO: Dict[tuple, bool] = {}


def _use_interpret() -> bool:
    """Run kernels in the Pallas interpreter off-TPU (the CPU test mesh:
    the CPU backend has no Mosaic lowering). On a TPU backend the answer is
    always False — the chip path never interprets — and a backend that
    fails to initialize raises here instead of quietly interpreting.
    Memoized per (backend, device_count) at module level
    (``_INTERPRET_MEMO``); both probes are answered from jax's own cached
    backend object, so a memo hit never re-resolves the backend
    registry."""
    key = (jax.default_backend(), jax.device_count())
    hit = _INTERPRET_MEMO.get(key)
    if hit is None:
        hit = _INTERPRET_MEMO[key] = key[0] != "tpu"
    return hit


def _attention_reference(q, k, v, scale, causal):
    """XLA fallback, [b, s, h, d] layout, fp32 softmax."""
    qt, kt, vt = (jnp.swapaxes(t, 1, 2) for t in (q, k, v))
    logits = jnp.einsum("bhqd,bhkd->bhqk", qt, kt) * scale
    if causal:
        sq, sk = logits.shape[-2], logits.shape[-1]
        mask = jnp.tril(jnp.ones((sq, sk), jnp.bool_), k=sk - sq)
        logits = jnp.where(mask, logits, NEG_INF)
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1).astype(q.dtype)
    return jnp.swapaxes(jnp.einsum("bhqk,bhkd->bhqd", probs, vt), 1, 2)


def _causal_mask(s, lead):
    """Scores of a block whose row ``i`` sees its columns ``j <= i +
    lead``; the others become NEG_INF."""
    rows = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    return jnp.where(rows - cols >= -lead, s, NEG_INF)


#: rows of a sub-block row of a tile that lies on the causal diagonal
#: (measured beside the tiles, ``_TUNED_BLOCKS``: at [64, 1024, 128] one
#: 1,024 x 1,024 tile a batch-head in four rows of 256 runs the forward in
#: 0.378 ms where three 512 x 512 tiles take 0.506 and the parent's 0.539)
_DIAG_SUB = 256


def _sub_rows(blk):
    """Rows of a sub-block row of a ``blk``-row diagonal tile:
    ``_DIAG_SUB`` where the tile holds at least four such rows, else the
    tile itself (at two a tile the steps that replace one masked 512 x 512
    step measured slower than it: 0.606–0.678 ms against 0.505)."""
    sub = _DIAG_SUB
    return sub if sub % _LANES == 0 and blk % sub == 0 and blk >= 4 * sub \
        else blk


def _causal_tile(step, qi, ki, blk_q, blk_k, offset):
    """Visit what tile (``qi``, ``ki``) holds of the causal half (query
    ``i`` sees keys ``j <= i + offset``): ``step(r, nr, nc, lead)`` runs
    over rows ``r : r + nr`` and the first ``nc`` columns of the tile,
    ``lead`` None where every pair of them is visible (no mask needed),
    else what :func:`_causal_mask` takes. A tile wholly under the
    diagonal is ONE unmasked step; a tile wholly above it visits nothing.
    A tile the diagonal crosses is masked, and where it lies ON the
    diagonal (square tiles, ``offset`` a multiple of them: where its rows
    meet the diagonal is known at trace time) it goes by :func:`_sub_rows`:
    each one step over the columns up to its own diagonal sub-block, so
    nothing right of that is multiplied. Which of the three a tile is, is
    decided on the device from its indices (``pl.when``): a kernel holds
    one unmasked body and one masked body a sub-block row."""
    lead = qi * blk_q + offset - ki * blk_k  # row i sees columns <= i + lead
    whole = lead >= blk_k - 1
    pl.when(whole)(functools.partial(step, 0, blk_q, blk_k, None))
    n = _sub_rows(blk_q)
    if blk_q == blk_k and offset % blk_q == 0 and n < blk_q:
        @pl.when(lead == 0)
        def _on_the_diagonal():
            for r in range(0, blk_q, n):
                step(r, n, r + n, r)
    else:
        pl.when(jnp.logical_not(whole) & (lead + blk_q - 1 >= 0))(
            functools.partial(step, 0, blk_q, blk_k, lead))


def _last_key_tile(i, blk_q, blk_k, offset):
    """The last key tile query tile ``i`` sees anything of (0 where it
    sees nothing). An index map that stops there copies no tile the
    kernel skips: the pipeline copies a block only when its index
    changes."""
    return jnp.maximum((i * blk_q + blk_q - 1 + offset) // blk_k, 0)


def _first_query_tile(j, blk_q, blk_k, offset, nq):
    """The first query tile that sees anything of key tile ``j``."""
    return jnp.clip((j * blk_k - offset) // blk_q, 0, nq - 1)


# --------------------------------------------------------------- forward


def _flash_fwd_kernel(q_ref, k_ref, v_ref, o_ref, *rest, scale, causal,
                      blk_q, blk_k, offset, with_lse):
    """One (bh, qi, ki) step of blockwise attention with online softmax.
    ``offset = sk - sq`` aligns the causal diagonal when kv is longer than q
    (decode): query i attends keys j <= i + offset. Under ``causal`` the
    tile goes through :func:`_causal_tile`: no mask under the diagonal, no
    product above it. (The latent prefill of ``ops/paged_attention.py``
    keeps a body of its own.)"""
    if with_lse:
        lse_ref, m_scr, l_scr, acc_scr = rest
    else:
        lse_ref, (m_scr, l_scr, acc_scr) = None, rest
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    def _step(r, nr, nc, lead):
        q = q_ref[0, r:r + nr]  # [nr, d]
        k = k_ref[0, :nc]  # [nc, d]
        v = v_ref[0, :nc]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale  # [nr, nc]
        if lead is not None:
            s = _causal_mask(s, lead)
        m_prev = m_scr[r:r + nr, 0:1]  # [nr, 1]
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)  # [nr, nc] f32
        correction = jnp.exp(m_prev - m_new)  # [nr, 1]
        l_new = (correction * l_scr[r:r + nr, 0:1]
                 + jnp.sum(p, axis=1, keepdims=True))
        pv = jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )  # [nr, d]
        acc_scr[r:r + nr] = acc_scr[r:r + nr] * correction + pv
        m_scr[r:r + nr] = jnp.broadcast_to(m_new, (nr, _LANES))
        l_scr[r:r + nr] = jnp.broadcast_to(l_new, (nr, _LANES))

    if causal:
        _causal_tile(_step, qi, ki, blk_q, blk_k, offset)
    else:
        _step(0, blk_q, blk_k, None)

    @pl.when(ki == nk - 1)
    def _finish():
        denom = l_scr[:, 0:1]
        denom = jnp.where(denom == 0.0, 1.0, denom)
        o_ref[0] = (acc_scr[:] / denom).astype(o_ref.dtype)
        if lse_ref is not None:
            safe_l = jnp.where(l_scr[:] > 0.0, l_scr[:], 1.0)
            lse_ref[0] = jnp.where(l_scr[:] > 0.0,
                                   m_scr[:] + jnp.log(safe_l), NEG_INF)


def _flash_forward(q, k, v, scale, causal, blk_q=128, blk_k=128,
                   with_lse=False):
    """q,k,v: [bh, s, d] (batch*heads flattened). Returns o, or (o, lse)
    where lse is the lane-broadcast [bh, sq, 128] log-sum-exp residual."""
    return _forward_call(q, k, v, float(scale), bool(causal),
                         min(blk_q, q.shape[1]), min(blk_k, k.shape[1]),
                         with_lse, _use_interpret())


@functools.partial(jax.jit, static_argnames=(
    "scale", "causal", "blk_q", "blk_k", "with_lse", "interpret"))
def _forward_call(q, k, v, scale, causal, blk_q, blk_k, with_lse, interpret):
    """The launch; jitted so that a model's layers share one traced and
    lowered kernel inside a program (what a layer costs a process's start,
    compile cache or none). Everything a trace depends on is an argument."""
    bh, sq, d = q.shape
    sk = k.shape[1]
    offset = sk - sq
    grid = (bh, sq // blk_q, sk // blk_k)
    kernel = functools.partial(
        _flash_fwd_kernel, scale=scale, causal=causal, blk_q=blk_q,
        blk_k=blk_k, offset=offset, with_lse=with_lse,
    )
    if causal:  # the key tiles above the diagonal are not copied either
        kv_spec = pl.BlockSpec((1, blk_k, d), lambda b, i, j: (
            b, jnp.minimum(j, _last_key_tile(i, blk_q, blk_k, offset)), 0))
    else:
        kv_spec = pl.BlockSpec((1, blk_k, d), lambda b, i, j: (b, j, 0))
    out_shape = [jax.ShapeDtypeStruct((bh, sq, d), q.dtype)]
    out_specs = [pl.BlockSpec((1, blk_q, d), lambda b, i, j: (b, i, 0))]
    if with_lse:
        out_shape.append(jax.ShapeDtypeStruct((bh, sq, _LANES), jnp.float32))
        out_specs.append(
            pl.BlockSpec((1, blk_q, _LANES), lambda b, i, j: (b, i, 0)))
    res = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, blk_q, d), lambda b, i, j: (b, i, 0)),
            kv_spec,
            kv_spec,
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((blk_q, _LANES), jnp.float32),  # running max (lane-bcast)
            pltpu.VMEM((blk_q, _LANES), jnp.float32),  # running denom
            pltpu.VMEM((blk_q, d), jnp.float32),  # output accumulator
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT,
        ),
        interpret=interpret,
        name="flash_fwd",
    )(q, k, v)
    return (res[0], res[1]) if with_lse else res[0]


# --------------------------------------------------------------- backward
#
# Standard flash-attention backward:
#   delta_i = rowsum(dO_i * O_i)
#   P_ij    = exp(S_ij - lse_i)
#   dV_j    = sum_i P_ij^T dO_i
#   dS_ij   = P_ij * (dO_i V_j^T - delta_i)            (x scale at the end)
#   dK_j    = sum_i dS_ij^T Q_i
#   dQ_i    = sum_j dS_ij K_j
# ONE kernel, ``flash_bwd`` (counter ``flash.bwd_fused``), builds S, P, dP
# and dS of a tile pair once and feeds all three sums from them (five
# products, one exp, one mask and one copy of each operand tile; delta from
# the output tile, in the kernel): grid (bh, kj, qi), dK/dV reduce over the
# inner query axis into [blk_k, d] scratch, dQ over the OUTER key axis into a
# float32 accumulator of the batch-head's whole sequence, which stays in
# VMEM and is written at the key axis's last turn. Where that accumulator
# and its output block pass ``_FUSED_BWD_VMEM`` (long sequences under ring
# attention) the two reduction kernels are the route (``flash.bwd_split``):
# ``flash_bwd_dkv`` gridded (bh, kj, qi) and ``flash_bwd_dq`` gridded (bh,
# qi, ki), each rebuilding S, P and dS, delta made by XLA. All three go by
# ``_causal_tile`` and copy no tile they skip.

#: bytes of VMEM the fused backward may hold for dQ: the float32
#: accumulator [sq, d] and the two buffers of its [sq, d] output block
#: (8 B a value in bf16: 8,192 x 128 is 8 MiB). The route is chosen from
#: ``sq`` and ``d`` against it when the backward is traced.
_FUSED_BWD_VMEM = 8 * 2 ** 20

#: the scoped VMEM a flash kernel may take: beside dQ the fused backward
#: holds its tiles twice and four score-sized float32 values (16 MiB at
#: 1,024 x 1,024), more than the chip's default 16 MiB in all
_VMEM_LIMIT = 48 * 2 ** 20


def _bwd_common(q, k, v, do, lse, di, lead, scale, guard):
    """P and the unscaled dS of a block; ``lead`` None: no mask. ``di``
    [nr, 1], or lane-broadcast [nr, 128] as ``lse`` is."""
    nc = k.shape[0]
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale  # [nr, nc]
    if lead is not None:
        s = _causal_mask(s, lead)
    reps = nc // _LANES
    spread = lambda x: jnp.tile(x, (1, reps)) if reps > 1 else x[:, :nc]
    lse_b = spread(lse)
    p = jnp.exp(s - lse_b)  # [nr, nc] f32
    if guard:
        # fully-masked query rows (sq > sk) store lse = NEG_INF;
        # exp(NEG_INF - NEG_INF) would be 1, so force their probabilities
        # (and thus grads) to zero
        p = jnp.where(lse_b > NEG_INF * 0.5, p, 0.0)
    dp = jax.lax.dot_general(
        do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )  # [nr, nc]
    return p, p * (dp - (di if di.shape[1] == 1 else spread(di)))


def _dkv_sums(p, ds, q, do):
    """(P^T dO, dS^T Q): what a block adds to dV and to dK, [nc, d]."""
    tn = (((0,), (0,)), ((), ()))
    return (jax.lax.dot_general(p.astype(do.dtype), do, tn,
                                preferred_element_type=jnp.float32),
            jax.lax.dot_general(ds.astype(q.dtype), q, tn,
                                preferred_element_type=jnp.float32))


def _dq_sum(ds, k):
    """dS K: what a block adds to dQ, [nr, d]."""
    return jax.lax.dot_general(ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _flash_bwd_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, o_ref,
                      dq_ref, dk_ref, dv_ref, dq_acc, dk_scr, dv_scr, *,
                      scale, causal, blk_q, blk_k, offset):
    """One (bh, kj, qi) step of the fused backward: five products a block."""
    kj = pl.program_id(1)
    qi = pl.program_id(2)
    nk = pl.num_programs(1)
    nq = pl.num_programs(2)
    row0 = qi * blk_q
    tile_rows = pl.ds(pl.multiple_of(row0, blk_q), blk_q)

    @pl.when(qi == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    @pl.when(kj == 0)
    def _init_dq():
        dq_acc[tile_rows] = jnp.zeros((blk_q, dq_acc.shape[1]), jnp.float32)

    def _step(r, nr, nc, lead):
        q, do = q_ref[0, r:r + nr], do_ref[0, r:r + nr]
        k = k_ref[0, :nc]
        delta = jnp.sum(do.astype(jnp.float32)
                        * o_ref[0, r:r + nr].astype(jnp.float32),
                        axis=1, keepdims=True)
        p, ds = _bwd_common(q, k, v_ref[0, :nc], do, lse_ref[0, r:r + nr],
                            delta, lead, scale, causal and offset < 0)
        dv, dk = _dkv_sums(p, ds, q, do)
        dv_scr[:nc] += dv
        dk_scr[:nc] += dk
        dq_acc[pl.ds(pl.multiple_of(row0 + r, nr), nr)] += _dq_sum(ds, k)

    if causal:
        _causal_tile(_step, qi, kj, blk_q, blk_k, offset)
    else:
        _step(0, blk_q, blk_k, None)

    @pl.when(qi == nq - 1)
    def _finish():
        dk_ref[0] = (dk_scr[:] * scale).astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)

    @pl.when(kj == nk - 1)
    def _finish_dq():
        dq_ref[0, tile_rows] = (dq_acc[tile_rows] * scale).astype(dq_ref.dtype)


def _flash_bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, di_ref,
                          dk_ref, dv_ref, dk_scr, dv_scr, *, scale, causal,
                          blk_q, blk_k, offset):
    kj = pl.program_id(1)
    qi = pl.program_id(2)
    nq = pl.num_programs(2)

    @pl.when(qi == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    def _step(r, nr, nc, lead):
        q, do = q_ref[0, r:r + nr], do_ref[0, r:r + nr]
        p, ds = _bwd_common(q, k_ref[0, :nc], v_ref[0, :nc], do,
                            lse_ref[0, r:r + nr], di_ref[0, r:r + nr], lead,
                            scale, causal and offset < 0)
        dv, dk = _dkv_sums(p, ds, q, do)
        dv_scr[:nc] += dv
        dk_scr[:nc] += dk

    if causal:
        _causal_tile(_step, qi, kj, blk_q, blk_k, offset)
    else:
        _step(0, blk_q, blk_k, None)

    @pl.when(qi == nq - 1)
    def _finish():
        dk_ref[0] = (dk_scr[:] * scale).astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


def _flash_bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, di_ref,
                         dq_ref, dq_scr, *, scale, causal, blk_q, blk_k,
                         offset):
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    def _step(r, nr, nc, lead):
        k = k_ref[0, :nc]
        _, ds = _bwd_common(q_ref[0, r:r + nr], k, v_ref[0, :nc],
                            do_ref[0, r:r + nr], lse_ref[0, r:r + nr],
                            di_ref[0, r:r + nr], lead, scale,
                            causal and offset < 0)
        dq_scr[r:r + nr] += _dq_sum(ds, k)

    if causal:
        _causal_tile(_step, qi, ki, blk_q, blk_k, offset)
    else:
        _step(0, blk_q, blk_k, None)

    @pl.when(ki == nk - 1)
    def _finish():
        dq_ref[0] = (dq_scr[:] * scale).astype(dq_ref.dtype)


def _flash_backward(q, k, v, o, lse, do, scale, causal, blk_q=128, blk_k=128):
    """All operands [bh, s, d] except lse [bh, sq, 128]. Returns (dq, dk,
    dv); counts the route it traces (``flash.bwd_fused`` /
    ``flash.bwd_split``), which follows dQ's bytes: a shape, not a flag."""
    from ..core import compile_cache

    _, sq, d = q.shape
    fused = sq * d * (4 + 2 * q.dtype.itemsize) <= _FUSED_BWD_VMEM
    compile_cache.bump("flash.bwd_fused" if fused else "flash.bwd_split")
    return _backward_call(q, k, v, o, lse, do, float(scale), bool(causal),
                          min(blk_q, sq), min(blk_k, k.shape[1]), fused,
                          _use_interpret())


@functools.partial(jax.jit, static_argnames=(
    "scale", "causal", "blk_q", "blk_k", "fused", "interpret"))
def _backward_call(q, k, v, o, lse, do, scale, causal, blk_q, blk_k, fused,
                   interpret):
    """The launch, jitted as :func:`_forward_call` is."""
    bh, sq, d = q.shape
    sk = k.shape[1]
    offset = sk - sq
    nq, nk = sq // blk_q, sk // blk_k

    # grid (bh, kj, qi): the query tiles above a key tile's diagonal are
    # not copied
    if causal:
        q_of = lambda b, j, i: (
            b, jnp.maximum(i, _first_query_tile(j, blk_q, blk_k, offset, nq)),
            0)
    else:
        q_of = lambda b, j, i: (b, i, 0)
    q_spec_i = pl.BlockSpec((1, blk_q, d), q_of)
    kv_spec_j = pl.BlockSpec((1, blk_k, d), lambda b, j, i: (b, j, 0))
    lm_spec_i = pl.BlockSpec((1, blk_q, _LANES), q_of)
    kernel_args = dict(scale=scale, causal=causal, blk_q=blk_q, blk_k=blk_k,
                       offset=offset)
    dkv_shape = [jax.ShapeDtypeStruct((bh, sk, d), k.dtype),
                 jax.ShapeDtypeStruct((bh, sk, d), v.dtype)]
    dkv_scratch = [pltpu.VMEM((blk_k, d), jnp.float32),
                   pltpu.VMEM((blk_k, d), jnp.float32)]

    if fused:
        return pl.pallas_call(
            functools.partial(_flash_bwd_kernel, **kernel_args),
            grid=(bh, nk, nq),
            in_specs=[q_spec_i, kv_spec_j, kv_spec_j, q_spec_i, lm_spec_i,
                      q_spec_i],
            out_specs=[pl.BlockSpec((1, sq, d), lambda b, j, i: (b, 0, 0)),
                       kv_spec_j, kv_spec_j],
            out_shape=[jax.ShapeDtypeStruct((bh, sq, d), q.dtype)] + dkv_shape,
            scratch_shapes=[pltpu.VMEM((sq, d), jnp.float32)] + dkv_scratch,
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "arbitrary", "arbitrary"),
                vmem_limit_bytes=_VMEM_LIMIT,
            ),
            interpret=interpret,
            name="flash_bwd",
        )(q, k, v, do, lse, o)

    di = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)
    di = jnp.broadcast_to(di[:, :, None], (bh, sq, _LANES))
    dk, dv = pl.pallas_call(
        functools.partial(_flash_bwd_dkv_kernel, **kernel_args),
        grid=(bh, nk, nq),
        in_specs=[q_spec_i, kv_spec_j, kv_spec_j, q_spec_i, lm_spec_i,
                  lm_spec_i],
        out_specs=[kv_spec_j, kv_spec_j],
        out_shape=dkv_shape,
        scratch_shapes=dkv_scratch,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT,
        ),
        interpret=interpret,
        name="flash_bwd_dkv",
    )(q, k, v, do, lse, di)

    # grid (bh, qi, ki): nor the key tiles above a query tile's diagonal
    if causal:
        k_of = lambda b, i, j: (
            b, jnp.minimum(j, _last_key_tile(i, blk_q, blk_k, offset)), 0)
    else:
        k_of = lambda b, i, j: (b, j, 0)
    q_spec_q = pl.BlockSpec((1, blk_q, d), lambda b, i, j: (b, i, 0))
    kv_spec_k = pl.BlockSpec((1, blk_k, d), k_of)
    lm_spec_q = pl.BlockSpec((1, blk_q, _LANES), lambda b, i, j: (b, i, 0))
    dq = pl.pallas_call(
        functools.partial(_flash_bwd_dq_kernel, **kernel_args),
        grid=(bh, nq, nk),
        in_specs=[q_spec_q, kv_spec_k, kv_spec_k, q_spec_q, lm_spec_q,
                  lm_spec_q],
        out_specs=q_spec_q,
        out_shape=jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((blk_q, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT,
        ),
        interpret=interpret,
        name="flash_bwd_dq",
    )(q, k, v, do, lse, di)
    return dq, dk, dv


# ------------------------------------------------------------- public op


def _shapes_ok(q, k, blk=128):
    b, sq, h, d = q.shape
    sk = k.shape[1]
    return (
        sq % min(blk, sq) == 0
        and sk % min(blk, sk) == 0
        and sq >= 8
        and sk >= 8
        and d in (64, 128, 256)
    )


def _flatten_heads(x):
    b, s, h, d = x.shape
    return jnp.swapaxes(x, 1, 2).reshape(b * h, s, d)


def _unflatten_heads(x, b, h):
    bh, s, d = x.shape
    return jnp.swapaxes(x.reshape(b, h, s, d), 1, 2)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash_attention(q, k, v, scale, causal, blk_q=128, blk_k=128):
    b, sq, h, d = q.shape
    of = _flash_forward(_flatten_heads(q), _flatten_heads(k),
                        _flatten_heads(v), scale, causal,
                        blk_q=blk_q, blk_k=blk_k)
    return _unflatten_heads(of, b, h)


def _flash_fwd_rule(q, k, v, scale, causal, blk_q=128, blk_k=128):
    b, sq, h, d = q.shape
    qf, kf, vf = _flatten_heads(q), _flatten_heads(k), _flatten_heads(v)
    of, lse = _flash_forward(qf, kf, vf, scale, causal, blk_q=blk_q,
                             blk_k=blk_k, with_lse=True)
    return _unflatten_heads(of, b, h), (qf, kf, vf, of, lse)


def _flash_bwd_rule(scale, causal, blk_q, blk_k, res, do):
    qf, kf, vf, of, lse = res
    b, sq, h, d = do.shape
    dq, dk, dv = _flash_backward(qf, kf, vf, of, lse, _flatten_heads(do),
                                 scale, causal, blk_q=blk_q, blk_k=blk_k)
    return (_unflatten_heads(dq, b, h), _unflatten_heads(dk, b, h),
            _unflatten_heads(dv, b, h))


_flash_attention.defvjp(_flash_fwd_rule, _flash_bwd_rule)


#: Flash tiles by device kind and kv sequence length: (blk_q, blk_k).
#: Measured on a v5e in bf16 at a head of 128, the forward (``flash_fwd``)
#: and the one backward kernel (``flash_bwd``; ``flash_bwd_dkv`` and
#: ``flash_bwd_dq`` beyond ``_FUSED_BWD_VMEM``) each alone, each candidate
#: checked against the kernels it replaced before it was timed (PERF.md
#: section 6, PR 46; the 512 x 512 of PR 1 were measured for two backward
#: kernels and a mask on every tile). 1,024 x 1,024 with ``_DIAG_SUB`` rows
#: of 256: at 1,024 positions one grid step a batch-head, where a step's
#: fixed cost (some 0.7 us of copies in and out) is paid once and not four
#: times. The 8192 row, which every longer sequence takes too (the split
#: pair beyond the budget), holds 1,024 x 512: 1,024 x 1,024 is faster
#: there (forward + fused backward 3.47 ms against 4.87) but the compiler
#: gives its backward 54.4 MB of VMEM, more than ``_VMEM_LIMIT`` states.
#: The benchmark's `train-1chip` cell runs the 1024 row; its ledger
#: lines read it as `flash_time_share_pct`. A kind that is not here keeps
#: the 128s: tiles verified on one TPU generation are not adopted on
#: another (VMEM limits differ; Mosaic may reject them).
_TUNED_BLOCKS = {
    "TPU v5 lite": {1024: (1024, 1024), 2048: (1024, 1024),
                    4096: (1024, 1024), 8192: (1024, 512)},
}


def _tuned_blocks(seq):
    """Measured tiles for this chip at the nearest measured seqlen
    (``_TUNED_BLOCKS``), or None."""
    table = _TUNED_BLOCKS.get(jax.devices()[0].device_kind)
    # only adopt within the measured range: a tiling verified at 8192 was
    # never lowered at 512 (different VMEM footprint; Mosaic may reject
    # it), and short seqs route through XLA attention anyway
    if not table or seq < min(table):
        return None
    return table[min(table, key=lambda s: abs(s - seq))]


def _default_blocks(seq=None):
    """Kernel tiling: FLAGS_flash_block_q/_k. 128 matches the MXU/lane
    width and is the safe default; larger k-blocks amortize grid overhead
    at long context. With the flags at their defaults the tiles measured
    for this chip (``_tuned_blocks``) are taken; any other flag value
    wins."""
    from ..core import flags

    bq = int(flags.flag("flash_block_q"))
    bk = int(flags.flag("flash_block_k"))
    if (bq, bk) == (128, 128) and seq is not None:
        tuned = _tuned_blocks(seq)
        if tuned:
            return tuned
    return bq, bk


def flash_attention(q, k, v, scale: Optional[float] = None, causal: bool = False,
                    blk_q: Optional[int] = None, blk_k: Optional[int] = None):
    """Blockwise flash attention, layout [batch, seq, heads, head_dim]."""
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    if not _shapes_ok(q, k):
        return _attention_reference(q, k, v, scale, causal)
    sq, sk = q.shape[1], k.shape[1]
    dq, dk = _default_blocks(seq=sk)
    # a measured tile that does not divide the sequence halves until it
    # does (1,536 positions run 512-row tiles, not the 128s)
    blk_q = blk_q or math.gcd(dq, sq)
    blk_k = blk_k or math.gcd(dk, sk)
    # block sizes must tile the sequence, and the backward's lane-broadcast
    # lse tiling (reps = blk_k // 128 in _bwd_common) needs blk_k to
    # be <=128 or a multiple of 128; fall back to the safe 128s otherwise
    if (sq % min(blk_q, sq) or sk % min(blk_k, sk)
            or (blk_k > _LANES and blk_k % _LANES)
            or blk_q % 8):
        blk_q = blk_k = 128
    return _flash_attention(q, k, v, scale, causal, blk_q, blk_k)
