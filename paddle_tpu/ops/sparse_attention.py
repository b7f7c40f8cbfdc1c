"""Attention over the tokens a learned indexer picks (the ``"sparse"`` kind
of layer state, ``models/serving_seam.py``): grouped-query attention whose
softmax runs, for every query, over the ``topk`` earlier tokens of largest
INDEX SCORE alone.

A token leaves three rows behind: its key and its value (``[kv_heads,
head_dim]``, as a ``"kv"`` layer's) and ONE index key of ``index_dim``
values. A query brings ``index_heads`` index queries and as many head
weights ``w`` (float32, the scale folded in), and scores a key by::

    I[t, s] = sum_j w[t, j] * relu(qi[t, j] . ki[s])          s <= t

(products of the served dtype, every sum in float32). The ``topk`` positions
of largest ``I[t, :]`` are kept (all of them while there are no more); one
kept set serves every head of the layer. Only the set matters, not its
order.

**The decode step** (one query a lane, keys in the paged pools):

* :func:`paged_index_scores`: every lane's scores over its live index keys,
  read through the block tables. The index pool packs ``pack`` tokens a row
  (``paged_attention.latent_pack``: two of 64 values fill a 128-lane tile).
  The kernel ``paged_index_scores`` copies the live pages alone, a tile of
  pages at a time and one tile ahead (as ``paged_latent_decode`` does), and
  meets a tile with ONE product: the lane's index queries laid block-
  diagonally, ``[pack * heads, pack * index_dim]``, so that row ``h * heads
  + j`` holds head ``j``'s score of the token at packed position ``h``.
* :func:`select_topk`: ``lax.top_k`` of the scores (positions and which of
  them are live).
* :func:`gathered_attention`: the chosen positions' K and V rows gathered
  through the block table (``topk`` rows of each a lane, whatever the
  context) and plain masked attention over them.

**A prefill** (every query of a prompt; keys are the prompt's own): no
``[positions, positions]`` array exists at any time.

* :func:`index_thresholds`: the scores of a CHUNK of queries against every
  key (kernel ``index_scores``: tiles above the diagonal are neither
  multiplied nor copied), and of each row its ``topk``-th largest
  (:func:`kth_largest`: exact, by bisection over the scores' bits, no
  sort), the row's threshold (``NEG_INF`` while a row has no more than ``topk`` keys).
* :func:`sparse_prefill_attention`: the flash kernel ``sparse_prefill_flash``
  recomputes a tile's index scores (the same tile function on the same
  tiles as ``index_scores``: the same numbers), keeps ``score >= threshold``
  under the diagonal, and runs the online softmax of all query heads over
  what is kept: one mask a (query tile, key tile) serves every head.
  ``kernel=False`` (the CPU's small prompts): the same in ``jax.numpy``, a
  chunk of queries at a time.

Off a TPU the kernels run in the Pallas interpreter
(``pallas_ops._use_interpret``).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .paged_attention import (_TILE_BYTES, _VMEM_LIMIT, _next_live_lane,
                              _tile_pages, latent_rows)
from .pallas_ops import NEG_INF, _use_interpret

__all__ = ["index_scores", "paged_index_scores", "kth_largest", "select_topk",
           "gathered_attention", "index_thresholds", "keep_mask",
           "sparse_prefill_attention"]

F32 = jnp.float32

#: pages of a decode tile whose copies start together under one branch
_GROUP = 16
#: bytes of index keys a decode tile aims for (256 pages of 2 KB)
_INDEX_TILE_BYTES = _TILE_BYTES // 2
#: query rows and key rows of a prefill tile, and the most queries a chunk
#: of :func:`index_thresholds` scores at a time
_BLOCK_Q, _BLOCK_K, _CHUNK = 128, 512, 1024
#: the most bytes of a chunk's scores (``[chunk, positions]`` float32):
#: ``kth_largest`` passes over them 32 times, and the compiler keeps them
#: in VMEM between the passes while they fit (its own text, compiled for a
#: v5e: ``S(1)`` on ``[1024, 24576]``, 101 MB, and none on ``[1024,
#: 30720]``, 126 MB, whose every pass then reads HBM: 5.9 ms a chunk where
#: 2.5 x fewer elements take 0.51). A longer prompt halves its chunk.
_SCORES_BYTES = 1024 * 24576 * 4


def index_scores(qi, ki, w):
    """``qi`` ``[..., n, heads, d]``, ``ki`` ``[..., m, d]``, ``w`` ``[...,
    n, heads]`` -> ``I`` ``[..., n, m]`` float32, in ``jax.numpy``: the
    products in the operands' dtype, both sums in float32 (the weighted
    one elementwise, so that no matmul precision reaches it)."""
    s = jnp.einsum("...nhd,...md->...nhm", qi, ki,
                   preferred_element_type=F32)
    return jnp.sum(jnp.maximum(s, 0.0) * w.astype(F32)[..., None], axis=-2)


def _ordered(x):
    """float32 -> int32 of the same order (the sign-magnitude bits turned
    into two's complement)."""
    b = jax.lax.bitcast_convert_type(x.astype(F32), jnp.int32)
    return jnp.where(b < 0, b ^ 0x7FFFFFFF, b)


def kth_largest(scores, k: int):
    """``[rows, T]`` float32 -> ``[rows]``: each row's ``k``-th largest,
    EXACTLY, without a sort: a bisection over the 32 bits of the ordered
    integer view, each round ONE pass over the scores that counts the
    entries at or over the midpoint (XLA's ``top_k`` sorts every row whole,
    with its positions, for this one number: 33 ms for ``[1024, 30720]`` on
    a v5e against 6 for these 32 passes; PERF.md section 6, PR 48)."""
    key = _ordered(scores)

    def halve(_, bounds):
        lo, hi = bounds                       # lo <= the answer <= hi
        mid = (lo >> 1) + (hi >> 1) + ((lo | hi) & 1)   # ceil((lo + hi) / 2)
        enough = jnp.sum(key >= mid[..., None], axis=-1,
                         dtype=jnp.int32) >= k
        return jnp.where(enough, mid, lo), jnp.where(enough, hi, mid - 1)

    rows = scores.shape[:-1]
    lo, _ = jax.lax.fori_loop(0, 32, halve, (
        jnp.full(rows, -2 ** 31, jnp.int32),
        jnp.full(rows, 2 ** 31 - 1, jnp.int32)))
    return jax.lax.bitcast_convert_type(
        jnp.where(lo < 0, lo ^ 0x7FFFFFFF, lo), F32)


def select_topk(scores, topk: int):
    """``scores`` ``[S, T]`` (``NEG_INF`` where there is no key) ->
    ``(positions [S, k] int32, live [S, k] bool)``, ``k = min(topk, T)``:
    the positions of the ``k`` largest, and which of them hold a key
    (``lax.top_k``: here the POSITIONS are wanted, and of what was tried a
    threshold with a compaction by running count and binary search cost
    ten times XLA's sort of ``[32, 30720]``, 10.6 ms against 0.94)."""
    vals, idx = jax.lax.top_k(scores, min(int(topk), scores.shape[-1]))
    return idx.astype(jnp.int32), vals > 0.5 * NEG_INF


# ------------------------------------------------------------------ decode


def _paged_scores_kernel(bt_ref, len_ref, nxt_ref, q_ref, w_ref, pool_hbm,
                         o_ref, buf, sem, slot_ref, *, bs, pages, group,
                         pack, heads):
    """One lane (grid step): its block-diagonal index queries against its
    live tiles of the index pool, the pages copied one tile ahead, across
    lanes too (``paged_attention._latent_kernel``'s copies)."""
    s = pl.program_id(0)
    n_lanes = pl.num_programs(0)
    t_tile = pages * bs
    rpb = bs // pack                      # pool rows a block
    cols = pages * rpb

    def each_live_group(lane, j, act):
        for g in range(0, pages, group):
            @pl.when((j * pages + g) * bs < len_ref[lane])
            def _():
                act(g)

    def start(lane, j, slot):
        last = (len_ref[lane] - 1) // bs  # the lane's last live page

        def whole(g):
            # past the lane's last page that page again (its columns are
            # masked): no table entry past a lane's length is read
            for i in range(g, g + group):
                page = jnp.minimum(j * pages + i, last)
                pltpu.make_async_copy(pool_hbm.at[bt_ref[lane, page]],
                                      buf.at[slot, i], sem.at[slot]).start()

        each_live_group(lane, j, whole)

    def wait(lane, j, slot):
        def whole(g):  # a group's copies count as one of their bytes
            dst = buf.at[slot, pl.ds(g, group)]
            pltpu.make_async_copy(dst, dst, sem.at[slot]).wait()

        each_live_group(lane, j, whole)

    @pl.when(s == 0)
    def _first():
        buf[...] = jnp.zeros_like(buf)
        slot_ref[0] = 0

        @pl.when(nxt_ref[0] < n_lanes)
        def _():
            start(nxt_ref[0], 0, 0)

    length = len_ref[s]
    n_tiles = (length + t_tile - 1) // t_tile
    # a tile with no live token, and a lane with no request: no key
    o_ref[...] = jnp.full_like(o_ref, NEG_INF)

    @pl.when(n_tiles > 0)
    def _live():
        col = jax.lax.broadcasted_iota(jnp.int32, (1, cols), 1)
        tok0 = (col // rpb) * bs + (col % rpb) * pack  # of packed position 0
        slot0 = slot_ref[0]

        def tile(j, _):
            slot = (slot0 + j) % 2

            @pl.when(j + 1 < n_tiles)
            def _():
                start(s, j + 1, 1 - slot)

            @pl.when(j + 1 == n_tiles)
            def _():
                nxt = nxt_ref[s + 1]

                @pl.when(nxt < n_lanes)
                def _():
                    start(nxt, 0, 1 - slot)

            wait(s, j, slot)
            rows = buf[slot].reshape(cols, buf.shape[-1])
            sc = jax.lax.dot_general(            # [pack * heads, cols]
                q_ref[0], rows, (((1,), (1,)), ((), ())),
                preferred_element_type=F32)
            sc = jnp.maximum(sc, 0.0) * w_ref[0]
            left = length - j * t_tile
            out = [jnp.where(tok0 + h < left,
                             jnp.sum(sc[h * heads:(h + 1) * heads], axis=0,
                                     keepdims=True), NEG_INF)
                   for h in range(pack)]
            o_ref[0, j] = jnp.concatenate(out, axis=0)
            return 0

        jax.lax.fori_loop(0, n_tiles, tile, 0)
        slot_ref[0] = (slot0 + n_tiles) % 2


@functools.partial(jax.jit, static_argnames=("pages",))
def _paged_scores_call(qi, w, pool, block_tables, lengths, pages):
    """The launch; jitted so that a model's layers share one lowered
    kernel inside the step program."""
    S, H, D = qi.shape
    NB, rpb, pw = pool.shape
    pack = pw // D
    bs = rpb * pack
    MB = block_tables.shape[1]
    pages = _tile_pages(MB, rpb * pw * pool.dtype.itemsize, pages,
                        _INDEX_TILE_BYTES)
    n_tiles = -(-MB // pages)
    cols = pages * rpb
    lengths = lengths.astype(jnp.int32)
    # head j's query of packed position h in row h * H + j, in the lanes
    # that hold that position's key; its weight beside it
    q2 = jnp.concatenate(
        [jnp.pad(qi, ((0, 0), (0, 0), (h * D, pw - (h + 1) * D)))
         for h in range(pack)], axis=1).astype(pool.dtype)
    w2 = jnp.tile(w.astype(F32), (1, pack))[:, :, None]
    out = pl.pallas_call(
        functools.partial(_paged_scores_kernel, bs=bs, pages=pages,
                          group=min(_GROUP, pages), pack=pack, heads=H),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(S,),
            in_specs=[pl.BlockSpec((1, pack * H, pw), lambda s, *_: (s, 0, 0)),
                      pl.BlockSpec((1, pack * H, 1), lambda s, *_: (s, 0, 0)),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((1, n_tiles, pack, cols),
                                   lambda s, *_: (s, 0, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((2, pages, rpb, pw), pool.dtype),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.SMEM((1,), jnp.int32),  # buffer of the next tile
            ]),
        out_shape=jax.ShapeDtypeStruct((S, n_tiles, pack, cols), F32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=_use_interpret(),
        name="paged_index_scores",
    )(block_tables, lengths, _next_live_lane(lengths), q2, w2, pool)
    # [S, tile, packed position, page, pool row] -> tokens in order
    out = out.reshape(S, n_tiles, pack, pages, rpb)
    return jnp.transpose(out, (0, 1, 3, 4, 2)).reshape(S, -1)[:, :MB * bs]


def paged_index_scores(qi, w, pool, block_tables, positions, active=None,
                       kernel: bool = False, pages=None):
    """Index scores of one new token a lane over its table's context:
    ``qi`` ``[S, heads, d]``, ``w`` ``[S, heads]`` float32, ``pool`` the
    packed index pool ``[num_blocks, block_size / pack, pack * d]`` ->
    ``[S, max_blocks * block_size]`` float32, ``NEG_INF`` past
    ``positions[s]`` and everywhere in a lane that is not ``active``.
    ``kernel``: through the block tables, the live pages alone; else the
    XLA gather of the whole table."""
    lengths = positions.astype(jnp.int32) + 1
    if active is not None:
        lengths = jnp.where(active, lengths, 0)
    if kernel:
        return _paged_scores_call(qi, w, pool, block_tables, lengths,
                                  pages or 0)
    with jax.named_scope("kv_gather"):
        ctx = latent_rows(pool, qi.shape[-1])[block_tables]
    ctx = ctx.reshape(qi.shape[0], -1, qi.shape[-1])          # [S, T, d]
    sc = index_scores(qi[:, None].astype(pool.dtype), ctx, w[:, None])[:, 0]
    live = jnp.arange(sc.shape[1])[None, :] < lengths[:, None]
    return jnp.where(live, sc, NEG_INF)


def gathered_attention(q, k_pool, v_pool, block_tables, idx, live):
    """``q`` ``[S, 1, heads, D]`` over the K and V rows at positions
    ``idx`` ``[S, k]`` of each lane's table (``live`` ``[S, k]``: which of
    them hold a key): ``k`` rows of each pool a lane are read, through one
    gather of whole token rows. -> ``[S, 1, heads, D]``."""
    nb, bs = k_pool.shape[:2]
    lanes, per_lane = block_tables.shape
    # (one gather of the flat table: ``take_along_axis`` lowers to a
    # select over every column of a lane's table, 0.67 ms a layer at 1,920)
    blk = block_tables.reshape(-1)[
        jnp.arange(lanes, dtype=idx.dtype)[:, None] * per_lane + idx // bs]
    rows = jnp.where(live, blk * bs + idx % bs, 0)
    from ..models.serving_seam import masked_attention  # (it imports ops)

    with jax.named_scope("kv_gather"):
        ks = k_pool.reshape((nb * bs,) + k_pool.shape[2:])[rows]
        vs = v_pool.reshape((nb * bs,) + v_pool.shape[2:])[rows]
    return masked_attention(q, ks.astype(q.dtype), vs.astype(q.dtype),
                            live[:, None, None, :])


# ----------------------------------------------------------------- prefill


def _index_tile(qi_ref, w, ki):
    """A tile's index scores ``[bq, bk]`` float32: ``qi_ref`` ``[heads, bq,
    d]`` (a ref), ``w`` ``[bq, heads]`` float32, ``ki`` ``[bk, d]``. The
    one function both prefill kernels call, on the same tiles: what the
    flash kernel compares with a row's threshold is bit for bit what the
    threshold was taken from."""
    acc = None
    for j in range(qi_ref.shape[0]):
        s = jax.lax.dot_general(qi_ref[j], ki, (((1,), (1,)), ((), ())),
                                preferred_element_type=F32)
        s = jnp.maximum(s, 0.0) * w[:, j:j + 1]
        acc = s if acc is None else acc + s
    return acc


def _under_diagonal(shape, row0, col0):
    rows = row0 + jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    cols = col0 + jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    return cols <= rows


def _scores_kernel(r0_ref, qi_ref, w_ref, ki_ref, o_ref, *, bq, bk):
    """One (query tile, key tile) of a chunk's index scores; the chunk's
    first row is ``r0_ref[0]``. ``NEG_INF`` above the diagonal."""
    i, j = pl.program_id(0), pl.program_id(1)
    row0 = r0_ref[0] + i * bq

    @pl.when(j * bk > row0 + bq - 1)
    def _above():
        o_ref[...] = jnp.full_like(o_ref, NEG_INF)

    @pl.when(j * bk <= row0 + bq - 1)
    def _score():
        sc = _index_tile(qi_ref, w_ref[...], ki_ref[...])
        o_ref[...] = jnp.where(_under_diagonal(sc.shape, row0, j * bk), sc,
                               NEG_INF)


def _tiles(p: int, block_q, block_k):
    """``(bq, bk, padded positions)``: the tiles of the prefill kernels
    for ``p`` positions (a short prompt is one tile)."""
    bk = min(int(block_k or _BLOCK_K), -(-p // 16) * 16)
    bq = min(int(block_q or _BLOCK_Q), bk)
    if bk % bq:
        raise ValueError("a key tile holds whole query tiles")
    return bq, bk, -(-p // bk) * bk


@functools.partial(jax.jit, static_argnames=("bq", "bk"))
def _scores_call(qi_t, w, ki, row0, bq, bk):
    """``qi_t`` ``[heads, c, d]`` (a chunk's queries, head-major), ``w``
    ``[c, heads]``, ``ki`` ``[pp, d]`` (every key, padded to whole tiles),
    ``row0`` the chunk's first position -> ``[c, pp]`` float32."""
    h, c, d = qi_t.shape
    pp = ki.shape[0]
    # the last key tile a query tile needs: the pipeline copies a block
    # only when its index changes, so what is not multiplied is not copied
    key_tile = lambda i, j, r0: (jnp.minimum(j, (r0[0] + (i + 1) * bq - 1)
                                             // bk), 0)
    return pl.pallas_call(
        functools.partial(_scores_kernel, bq=bq, bk=bk),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(c // bq, pp // bk),
            in_specs=[pl.BlockSpec((h, bq, d), lambda i, j, r0: (0, i, 0)),
                      pl.BlockSpec((bq, h), lambda i, j, r0: (i, 0)),
                      pl.BlockSpec((bk, d), key_tile)],
            out_specs=pl.BlockSpec((bq, bk), lambda i, j, r0: (i, j))),
        out_shape=jax.ShapeDtypeStruct((c, pp), F32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=_use_interpret(),
        name="index_scores",
    )(jnp.reshape(row0, (1,)).astype(jnp.int32), qi_t, w, ki)


def _causal_scores(qi, ki, w, row0):
    """:func:`index_scores` of queries at positions ``row0 ..`` against
    keys at positions ``0 ..``, ``NEG_INF`` above the diagonal."""
    sc = index_scores(qi, ki, w)
    return jnp.where(_under_diagonal(sc.shape, row0, 0), sc, NEG_INF)


def _chunked(a, n: int, c: int):
    """``[p, ...]`` -> ``[n, c, ...]``, zero rows behind."""
    return jnp.pad(a, ((0, n * c - a.shape[0]),) + ((0, 0),) * (a.ndim - 1)
                   ).reshape((n, c) + a.shape[1:])


def index_thresholds(qi, ki, w, topk: int, kernel: bool = False,
                     block_q=None, block_k=None, chunk=None):
    """Every query's threshold: ``qi`` ``[p, heads, d]``, ``ki`` ``[p, d]``,
    ``w`` ``[p, heads]`` float32 -> ``[p]`` float32, query ``t``'s
    ``topk``-th largest score over the keys ``s <= t``; ``NEG_INF`` for
    ``t < topk`` (every key is kept). A chunk of queries at a time
    (``chunk``, or as many as :data:`_SCORES_BYTES` allows)."""
    p = qi.shape[0]
    if p <= topk:
        return jnp.full((p,), NEG_INF, F32)
    c = int(chunk or _CHUNK)
    if kernel:
        bq, bk, pp = _tiles(p, block_q, block_k)
        while chunk is None and c * pp * 4 > _SCORES_BYTES and c > bq:
            c //= 2
        c = pp if pp <= c else -(-c // bq) * bq
        keys = jnp.pad(ki, ((0, pp - p), (0, 0)))
        score = lambda q_c, w_c, r0: _scores_call(
            jnp.swapaxes(q_c, 0, 1), w_c, keys, r0, bq, bk)
    else:
        c, pp = min(c, p), p
        score = lambda q_c, w_c, r0: _causal_scores(q_c, ki, w_c, r0)
    n = -(-pp // c)
    tau = jax.lax.map(lambda xs: kth_largest(score(*xs), topk), (
        _chunked(qi, n, c), _chunked(w.astype(F32), n, c),
        jnp.arange(n) * c))
    return tau.reshape(-1)[:p]


def keep_mask(qi, ki, w, tau, row0=0):
    """``[n, m]`` bool: the keys each query keeps, in ``jax.numpy``
    (queries at positions ``row0 ..``): under the diagonal, at or over the
    query's threshold."""
    sc = _causal_scores(qi, ki, w.astype(F32), row0)
    return (sc >= tau[:, None]) & (sc > 0.5 * NEG_INF)


def _flash_kernel(q_ref, k_ref, v_ref, qi_ref, w_ref, ki_ref, tau_ref, o_ref,
                  m_scr, l_scr, acc_scr, *, scale, bq, bk, kvh, group):
    """One (query tile, key tile): the tile's kept keys, then every K/V
    head's query heads (laid one after another on the row axis) through
    the online softmax over them."""
    i, j = pl.program_id(0), pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when(j * bk <= i * bq + bq - 1)
    def _step():
        sc = _index_tile(qi_ref, w_ref[...], ki_ref[...])
        keep = (sc >= tau_ref[...]) & _under_diagonal(sc.shape, i * bq,
                                                      j * bk)
        bias = jnp.where(keep, 0.0, NEG_INF)
        bias = jnp.concatenate([bias] * group, axis=0)     # [group bq, bk]
        rows = group * bq
        for g in range(kvh):
            at = slice(g * rows, (g + 1) * rows)
            q = q_ref[g * group:(g + 1) * group].reshape(rows, -1)
            s = jax.lax.dot_general(q, k_ref[g], (((1,), (1,)), ((), ())),
                                    preferred_element_type=F32) * scale
            s = s + bias
            m_prev = m_scr[at]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            # a row that keeps nothing of this tile adds nothing (its own
            # key is not always among the kept: no later tile repairs it)
            p = jnp.where(s > 0.5 * NEG_INF, jnp.exp(s - m_new), 0.0)
            corr = jnp.exp(m_prev - m_new)
            l_scr[at] = corr * l_scr[at] + jnp.sum(p, axis=1, keepdims=True)
            acc_scr[at] = acc_scr[at] * corr + jax.lax.dot_general(
                p.astype(v_ref.dtype), v_ref[g], (((1,), (0,)), ((), ())),
                preferred_element_type=F32)
            m_scr[at] = m_new

    @pl.when(j == pl.num_programs(1) - 1)
    def _finish():
        o = acc_scr[...] / l_scr[...]
        o_ref[...] = o.reshape(o_ref.shape).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bq", "bk"))
def _flash_call(q, k, v, qi, ki, w, tau, bq, bk):
    p, h, d = q.shape
    kvh = k.shape[1]
    group = h // kvh
    pp = -(-p // bk) * bk
    pad = lambda a: jnp.pad(a, ((0, pp - p),) + ((0, 0),) * (a.ndim - 1))
    # head-major copies; the pad rows are keys past every real query and
    # queries nobody reads (their threshold keeps what the diagonal leaves)
    qf, kf, vf, qif = (jnp.swapaxes(pad(a), 0, 1) for a in (q, k, v, qi))
    tau = jnp.pad(tau, (0, pp - p), constant_values=NEG_INF)[:, None]
    last = lambda i, j: jnp.minimum(j, (i * bq + bq - 1) // bk)
    heads = lambda n, b: pl.BlockSpec((n, b, d), lambda i, j: (0, i, 0))
    keys = lambda n, w_: pl.BlockSpec((n, bk, w_),
                                      lambda i, j: (0, last(i, j), 0))
    di = qi.shape[-1]
    out = pl.pallas_call(
        functools.partial(_flash_kernel, scale=1.0 / math.sqrt(d), bq=bq,
                          bk=bk, kvh=kvh, group=group),
        grid=(pp // bq, pp // bk),
        in_specs=[heads(h, bq), keys(kvh, d), keys(kvh, d),
                  pl.BlockSpec((qi.shape[1], bq, di), lambda i, j: (0, i, 0)),
                  pl.BlockSpec((bq, qi.shape[1]), lambda i, j: (i, 0)),
                  pl.BlockSpec((bk, di), lambda i, j: (last(i, j), 0)),
                  pl.BlockSpec((bq, 1), lambda i, j: (i, 0))],
        out_specs=heads(h, bq),
        out_shape=jax.ShapeDtypeStruct((h, pp, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((h * bq, 1), F32),
                        pltpu.VMEM((h * bq, 1), F32),
                        pltpu.VMEM((h * bq, d), F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=_use_interpret(),
        name="sparse_prefill_flash",
    )(qf, kf, vf, qif, pad(w.astype(F32)), pad(ki), tau)
    return jnp.swapaxes(out, 0, 1)[:p]


def sparse_prefill_attention(q, k, v, qi, ki, w, tau, kernel: bool = False,
                             block_q=None, block_k=None, chunk=None):
    """One prompt's attention over the keys each query keeps: ``q`` ``[p,
    heads, D]``, ``k``, ``v`` ``[p, kv_heads, D]`` (query head ``h`` reads
    K/V head ``h // (heads / kv_heads)``), ``qi`` ``[p, index_heads, d]``,
    ``ki`` ``[p, d]``, ``w`` ``[p, index_heads]`` float32, ``tau`` ``[p]``
    (:func:`index_thresholds`): query ``t`` attends the keys ``s <= t``
    with ``I[t, s] >= tau[t]``, scores over ``sqrt(D)``. -> ``[p, heads,
    D]`` in ``q.dtype``."""
    p = q.shape[0]
    if kernel:
        bq, bk, _ = _tiles(p, block_q, block_k)
        return _flash_call(q, k, v, qi, ki, w, tau, bq, bk)
    c = min(int(chunk or _CHUNK), p)
    n = -(-p // c)
    from ..models.serving_seam import masked_attention

    def one(xs):
        q_c, qi_c, w_c, tau_c, r0 = xs
        keep = keep_mask(qi_c, ki, w_c, tau_c, r0)
        return masked_attention(q_c[None], k[None], v[None],
                                keep[None, None])[0]

    o = jax.lax.map(one, tuple(_chunked(a, n, c) for a in (q, qi, w, tau))
                    + (jnp.arange(n) * c,))
    return o.reshape((n * c,) + q.shape[1:])[:p]
