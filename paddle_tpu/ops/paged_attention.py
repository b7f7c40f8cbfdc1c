"""Pallas paged-attention kernels over KV-arena block tables.

The serving engine's XLA path pays a *gather tax* on every decode step:
``cache_views.gather_ctx`` materializes each lane's whole logical context
(``kp[table]`` — ``[S, max_blocks*block_size, H, D]`` of mostly-masked
rows, dequantized from int8 first when the arena is quantized) before
``masked_attention`` reads a single useful element. These kernels read
K/V **directly through the block tables instead**: the table rides as a
scalar-prefetch operand and each grid step's BlockSpec ``index_map``
resolves one logical block to its physical pool row, so HBM traffic is
the pool blocks themselves — no contiguous copy, no f32 materialization
of an int8 arena (per-block scales stream alongside the payload and fold
into the scores and probabilities in VMEM —
:func:`paddle_tpu.quantization.dequantize_kv`'s math, see
:func:`_attend_block`).

Two kernels, same online-softmax core as the training flash kernel
(:mod:`paddle_tpu.ops.pallas_ops`):

* :func:`paged_decode_attention` — one new token per slot. A lane's
  context is read in tiles of several pages, double buffered, only the
  pages that hold a live token (``positions`` is the ``start_pos`` of
  ``cache_views.PagedCacheView``; a lane that is not ``active`` reads
  nothing); all heads of a tile in one pair of matmuls (see "decode"
  below).
* :func:`paged_prefill_attention` — a suffix/chunk of queries for ONE
  slot against its table (the ``cache_views.PrefixPrefillView`` contract):
  query ``i`` sits at global position ``prefix_len + i`` and attends
  keys at global index ``<= prefix_len + i``. ``prefix_len`` is runtime
  data (scalar prefetch), so every chunk of every admission reuses one
  compiled program per suffix bucket.

Block tables, positions and prefix lengths are *runtime data*
(scalar-prefetch operands): admit/retire/accept/reject churn never
recompiles — the same invariant the XLA path holds. Launch parameters
(decode's ``pages`` per tile, prefill's ``block_q`` query tiling and
``block_h`` head grouping) are derived from the launch's shapes, here
(:func:`_tile_pages`, :func:`_query_block`, :func:`_head_group`); the
arguments of those names are for a test that forces a tile.

Numerics: the online softmax is mathematically identical to the gather
path's full-width softmax but associates differently, so parity is
*tolerance*, not bitwise — see docs/performance.md ("Paged attention
kernels") for the documented bound and the greedy token-parity gate.
Off-TPU the kernels run in the Pallas interpreter
(:func:`~paddle_tpu.ops.pallas_ops._use_interpret`), so tier-1 exercises
these exact kernel bodies on the CPU mesh.

SPMD partitioning (ISSUE 16): every public entry takes ``mesh=``. On a
multi-device mesh the call routes through
:func:`~paddle_tpu.distributed.sharding_util.headwise_shard_map` —
``shard_kv_entry`` already committed the K/V payload pools heads-sharded
over the "model" axis, so each device runs this SAME kernel on its local
head shard (the launch sees the local ``H``) through the
replicated per-slot block tables, with zero cross-chip K/V traffic; the
heads-sharded output hands straight to the row-parallel output
projection's psum. A 1-device mesh (or ``mesh=None``) skips the wrapper
entirely — bit-identical to PR 13.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .pallas_ops import NEG_INF, _LANES, _causal_mask, _use_interpret

__all__ = ["available", "decode_in_place", "paged_decode_attention",
           "paged_prefill_attention", "paged_full_prefill_attention",
           "write_token", "paged_latent_decode", "latent_prefill_attention",
           "swa_prefill_attention", "latent_pack", "latent_rows",
           "write_latent_token"]


#: scoped VMEM the kernels may use (the compiler's default, 16 MiB of the
#: chip's 128, is 260 KB short of the prefill kernel's tiles at 30 heads)
_VMEM_LIMIT = 64 * 1024 * 1024


def available() -> bool:
    """Whether the paged kernels can run here (Pallas with
    scalar-prefetch support). The engine checks ONCE at construction and
    refuses to build a kernel engine without them — never a traced
    branch, never a silent gather path."""
    return hasattr(pltpu, "PrefetchScalarGridSpec")


def _head_group(num_heads: int, block_h) -> int:
    """Clamp a (tuned) head-group size to a divisor of ``num_heads``.
    Default: all heads in one grid step (fewest steps — the right call
    for small pools and the interpreter; a chip tune may prefer smaller
    groups to fit VMEM at large head_dim)."""
    g = int(block_h) if block_h else num_heads
    g = max(1, min(g, num_heads))
    while num_heads % g:
        g -= 1
    return g


def _query_block(sq: int, block_q) -> int:
    """Clamp a (tuned) query tile to a divisor of the (bucketed) suffix
    length."""
    b = int(block_q) if block_q else min(sq, 128)
    b = max(1, min(b, sq))
    while sq % b:
        b -= 1
    return b


def _mesh_routes(mesh) -> bool:
    """Whether ``mesh`` routes a call through the manual shard_map wrapper:
    only a MULTI-device mesh does — a 1-device mesh (the default
    deployment posture) or no mesh calls pallas directly, so those two
    stay bit-identical by construction."""
    return mesh is not None and int(mesh.devices.size) > 1


#: int8 scale pools stream in whole f32 sublane tiles: a ``(1, bs)`` block
#: of a ``[num_blocks, bs]`` array is not a legal TPU block shape, an
#: ``(8, bs)`` one is. The kernel picks its row out of the tile.
_SCALE_ROWS = 8


def _scale_spec(bs, block_of):
    """BlockSpec of one scale pool: the sublane tile holding the physical
    block that ``block_of(*grid_and_prefetch_args)`` names."""
    return pl.BlockSpec(
        (_SCALE_ROWS, bs), lambda *a: (block_of(*a) // _SCALE_ROWS, 0))


def _scale_row(ref, block):
    """``[1, bs]`` per-token scales of physical ``block`` out of its
    tile (keys on lanes — the layout of a score row)."""
    return ref[pl.ds(block % _SCALE_ROWS, 1), :]


def _attend_block(q, k, v, k_scale, v_scale, visible, scale,
                  m_scr, l_scr, acc_scr):
    """Online-softmax update of ``[blk_h, rows, ...]`` scratch with one
    KV block: ``q`` ``[blk_h, rows, D]`` (head-major — the batch dim leads
    the lhs and the lhs keeps a free dim, the one form of this contraction
    Mosaic lowers), ``k``/``v`` ``[bs, blk_h, D]``, ``visible`` a mask
    broadcastable to ``[blk_h, rows, bs]``.

    An int8 block is NOT dequantized element-wise: its per-token scales
    (``[1, bs]``, keys on lanes) fold into the score columns and the
    probabilities — ``q.(k*s) == (q.k)*s`` and ``p@(v*s) == (p*s)@v`` —
    which is :func:`paddle_tpu.quantization.dequantize_kv`'s math without
    a relayout of the scale row across the block's leading axis (int8
    values are exact in every compute dtype)."""
    if k_scale is not None:
        k, v = k.astype(q.dtype), v.astype(q.dtype)
    sc = jax.lax.dot_general(  # [blk_h, rows, bs]
        q, k, (((2,), (2,)), ((0,), (1,))),
        preferred_element_type=jnp.float32) * scale
    if k_scale is not None:
        sc = sc * k_scale
    sc = jnp.where(visible, sc, NEG_INF)
    m_prev = m_scr[:, :, 0:1]
    m_new = jnp.maximum(m_prev, jnp.max(sc, axis=2, keepdims=True))
    p = jnp.exp(sc - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_new = corr * l_scr[:, :, 0:1] + jnp.sum(p, axis=2, keepdims=True)
    if v_scale is not None:
        p = p * v_scale
    pv = jax.lax.dot_general(  # [blk_h, rows, D]
        p.astype(v.dtype), v, (((2,), (0,)), ((0,), (1,))),
        preferred_element_type=jnp.float32)
    acc_scr[:] = acc_scr[:] * corr + pv
    m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)
    l_scr[:] = jnp.broadcast_to(l_new, l_scr.shape)


def _normalized(l_scr, acc_scr):
    denom = l_scr[:, :, 0:1]
    return acc_scr[:] / jnp.where(denom == 0.0, 1.0, denom)


# ---------------------------------------------------------------- decode
#
# Grid ``(lanes,)``; inside a grid step a loop over the lane's LIVE tiles.
# A tile is ``pages`` pages of K and of V (``_TILE_BYTES`` each), each page
# an explicit ``make_async_copy`` through the block table into one of two
# VMEM buffers while the other is computed on: the next tile, or the next
# live lane's first, is always in flight. Pages past a lane's length are
# neither copied nor computed, and a lane of length 0 (not active) costs
# nothing.
#
# The kernel sees a pool as page SLABS ``[num_blocks, block_size*H, D]``,
# a view of the ``[num_blocks, block_size, H, D]`` pool that moves no
# byte: the chip keeps a pool whose head count fills whole sublane groups
# token-major (a slab's rows are ``(token, head)``), and one whose head
# count does not (30 heads: Olmo-Hybrid) head-major (``(head, token)``:
# no padding) — :func:`_head_major` mirrors that choice, and a wrong guess
# costs a copy, not a wrong answer. A slab's rows always fill whole
# sublane tiles, so any head count is sliced and copied the same way.
#
# A tile in VMEM is ``[pages*block_size*H, D]``: ONE matmul operand for
# all heads. ``q [H, D] . K^T`` gives every head's query against every
# head's keys, ``[H, rows]``, of which row h wants only the columns of
# head h. The rest is masked off with a constant bias and the masked
# probabilities multiply ``V [rows, D]`` directly: the zeros pick each
# head's own values. The MXU streams every K and V byte once either way;
# nothing is transposed and no head is handled alone.

#: bytes of K (and of V) a decode tile aims for: large enough that the
#: per-tile and per-copy costs are small beside the HBM time, small
#: enough that two buffers of each, the scores and the bias fit VMEM
_TILE_BYTES = 1 << 20


def _tile_pages(max_blocks, page_bytes, pages=None,
                tile_bytes=_TILE_BYTES) -> int:
    """Pages per decode tile: ``pages`` if given (a test), else what fills ``tile_bytes``; a power of two of at least 8
    (so a tile's rows fill whole lane tiles of the scores at every head
    count) unless the table itself is shorter."""
    n = int(pages) if pages else max(8, tile_bytes // max(page_bytes, 1))
    n = 1 << (max(n, 1).bit_length() - 1)
    return max(1, min(n, 1 << (max(max_blocks, 1) - 1).bit_length()))


def _head_major(num_heads: int) -> bool:
    """Whether the chip keeps a ``[blocks, block_size, H, D]`` pool with
    the heads outside the tokens (what XLA's TPU layout does when H does
    not fill whole 8-row sublane groups and would have to be padded:
    ``{3,1,2,0}`` for 30 heads, ``{3,2,1,0}`` for 8, 16, 24, 32; 4 heads
    get a 4-row tile and stay token-major)."""
    return num_heads % 4 != 0


def decode_in_place(head_dim: int) -> bool:
    """Whether the decode kernel reads a pool of this head dim where it
    lies. A page is sliced out of the pool in whole 128-lane tiles; a
    narrower head dim (64: GPT 124M) is served from a lane-padded COPY of
    the pool, made every call: correct, and not what a default should
    choose."""
    return head_dim % _LANES == 0


def _page_slabs(pool):
    """``[NB, bs, H, D]`` -> ``[NB, bs*H, D']``, rows ``(head, token)`` if
    the pool lies head-major (:func:`_head_major`) else ``(token, head)``;
    ``D'`` is D padded with zeros to whole lane tiles
    (:func:`decode_in_place`)."""
    nb, bs, h, d = pool.shape
    if _head_major(h):
        pool = jnp.swapaxes(pool, 1, 2)
    pool = pool.reshape(nb, bs * h, d)
    return jnp.pad(pool, ((0, 0), (0, 0), (0, -d % _LANES)))


def write_token(pool, blocks, offsets, rows):
    """One new token a lane into a full-precision pool, where the pool
    lies: ``rows`` ``[S, H, D]`` go to token ``offsets[s]`` of block
    ``blocks[s]`` (``pool.at[blocks, offsets].set(rows)``). XLA's scatter
    wants its ``(H, D)`` window on the minor dims: in a token-major pool
    it is, and the plain scatter runs in place; a head-major pool it
    copies to a token-major layout and back to make it so (24 ms a decode
    step at Olmo-Hybrid's shapes), so there each head's row is scattered
    on its own into the :func:`_page_slabs` view, where one row is
    minor-most. Returns the pool, same shape. A prefill's write is the
    sibling ``serving.cache_views.scatter_blocks``, and the two differ
    because what they write does: here one row a lane, in as many blocks as lanes,
    so the window can only be made minor by the view; there whole blocks
    of one lane, a window that covers every minor dimension under either
    layout (and the swapped view would cost a token-major pool two
    copies)."""
    nb, bs, h, d = pool.shape
    if not _head_major(h):  # the window is minor already: 4 us a pool
        return pool.at[blocks, offsets].set(rows)
    at = jnp.arange(h, dtype=offsets.dtype)[None, :] * bs + offsets[:, None]
    slab = jnp.swapaxes(pool, 1, 2).reshape(nb, h * bs, d)
    slab = slab.at[blocks[:, None], at].set(rows)
    return jnp.swapaxes(slab.reshape(nb, h, bs, d), 1, 2)


def _tile_columns(pages, bs, heads, hq, head_major, group=1):
    """What every tile's mask is made of (host constants): the bias
    ``[hq, cols]`` that leaves query row h only the columns of K/V head
    ``h // group`` (``group`` query heads read one K/V head; 1: head h),
    and the token within the tile ``[1, cols]`` of each column, for the
    row order of :func:`_page_slabs`."""
    import numpy as np

    r = np.arange(bs * heads)
    head, tok = (r // bs, r % bs) if head_major else (r % heads, r // heads)
    head = np.tile(head, pages)
    tok = (np.arange(pages)[:, None] * bs + tok[None, :]).reshape(-1)
    bias = np.where(head[None, :] == np.arange(hq)[:, None] // group, 0.0,
                    NEG_INF)
    return bias.astype(np.float32), tok.astype(np.int32)[None, :]


def _decode_kernel(bt_ref, len_ref, nxt_ref, q_ref, bias_ref, tok_ref,
                   k_hbm, v_hbm, *rest, bs, pages, scale, quantized):
    """One lane (grid step): its single query against its live tiles, the
    pages copied one tile ahead, across lanes too."""
    ks_ref, vs_ref = rest[:2] if quantized else (None, None)
    o_ref, kbuf, vbuf, sem, slot_ref = rest[-5:]
    s = pl.program_id(0)
    n_lanes = pl.num_programs(0)
    t_tile = pages * bs
    hq, d = q_ref.shape[1], q_ref.shape[2]
    rows = kbuf.shape[2]
    cols = pages * rows

    def each_live_page(lane, j, slot, act):
        """``act(K copy, V copy)`` for every page of tile ``j`` of ``lane``
        that holds a live token."""
        for i in range(pages):
            page = j * pages + i

            @pl.when(page * bs < len_ref[lane])
            def _():
                block = bt_ref[lane, page]
                act(pltpu.make_async_copy(k_hbm.at[block], kbuf.at[slot, i],
                                          sem.at[0, slot]),
                    pltpu.make_async_copy(v_hbm.at[block], vbuf.at[slot, i],
                                          sem.at[1, slot]))

    def start(lane, j, slot):
        each_live_page(lane, j, slot, lambda ck, cv: (ck.start(), cv.start()))

    def wait(lane, j, slot):
        each_live_page(lane, j, slot, lambda ck, cv: (ck.wait(), cv.wait()))

    @pl.when(s == 0)
    def _first():
        # rows of a half-filled tile that were never copied are multiplied
        # by zero probabilities: they must be finite, so start from zeros
        kbuf[...] = jnp.zeros_like(kbuf)
        vbuf[...] = jnp.zeros_like(vbuf)
        slot_ref[0] = 0

        @pl.when(nxt_ref[0] < n_lanes)
        def _():
            start(nxt_ref[0], 0, 0)

    length = len_ref[s]
    n_tiles = (length + t_tile - 1) // t_tile

    @pl.when(n_tiles == 0)
    def _idle():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(n_tiles > 0)
    def _live():
        q = q_ref[0]
        slot0 = slot_ref[0]

        def tile(j, carry):
            m_prev, l_prev, acc = carry
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
            k = kbuf[slot].reshape(cols, d)
            v = vbuf[slot].reshape(cols, d)
            if quantized:  # int8 values are exact in every compute dtype
                k, v = k.astype(q.dtype), v.astype(q.dtype)
                at = (pl.multiple_of(j * cols, _LANES) if cols % _LANES == 0
                      else j * cols)
            sc = jax.lax.dot_general(  # [hq, cols]: all heads x all heads
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale
            if quantized:
                # per-token scales fold into the score columns and the
                # probabilities: q.(k*s) == (q.k)*s, p@(v*s) == (p*s)@v
                sc = sc * ks_ref[0, :, pl.ds(at, cols)]
            sc = jnp.where(tok_ref[...] < length - j * t_tile,
                           sc + bias_ref[...], NEG_INF)
            m_new = jnp.maximum(m_prev, jnp.max(sc, axis=1, keepdims=True))
            p = jnp.exp(sc - m_new)
            corr = jnp.exp(m_prev - m_new)
            l_new = corr * l_prev + jnp.sum(p, axis=1, keepdims=True)
            if quantized:
                p = p * vs_ref[0, :, pl.ds(at, cols)]
            pv = jax.lax.dot_general(  # [hq, d]: the zeros pick own head
                p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            return m_new, l_new, acc * corr + pv

        _, l, acc = jax.lax.fori_loop(
            0, n_tiles, tile,
            (jnp.full((hq, 1), NEG_INF, jnp.float32),
             jnp.zeros((hq, 1), jnp.float32),
             jnp.zeros((hq, d), jnp.float32)))
        slot_ref[0] = (slot0 + n_tiles) % 2
        o_ref[0] = (acc / l).astype(o_ref.dtype)


def _next_live_lane(lengths):
    """The decode kernels' copies run one tile ahead, across lanes:
    ``nxt[0]`` is the first live lane, ``nxt[s + 1]`` the next after lane
    ``s``, ``S`` where there is none."""
    S = lengths.shape[0]
    lane = jnp.arange(S, dtype=jnp.int32)
    nxt = jax.lax.cummin(jnp.where(lengths > 0, lane, S), axis=0,
                         reverse=True)
    return jnp.concatenate([nxt, jnp.full((1,), S, jnp.int32)])


@functools.partial(jax.jit, static_argnames=("pages",))
def _decode_call(q, entry, block_tables, lengths, pages):
    """The kernel launch on one device's heads (``lengths`` ``[S]``: live
    tokens per lane, 0 for a lane that is not active). Jitted so that a
    model's layers, which call it with the same shapes, share ONE traced
    and lowered kernel inside the step program (24 tracings of the kernel
    cost the serving cell a minute of set-up). ``pages``: the tuned tile
    size, or a falsy value for the default."""
    S, HQ, D = q.shape
    quantized = len(entry) == 4
    NB, bs, H = entry[0].shape[:3]  # H: the pool's (K/V) heads
    if HQ % H:
        raise ValueError(f"{HQ} query heads do not divide over {H} K/V "
                         "heads")
    MB = block_tables.shape[1]
    head_major = _head_major(H)
    kp, vp = (_page_slabs(pool) for pool in entry[:2])
    rows, Dp = bs * H, kp.shape[2]
    hq = -(-HQ // 8) * 8  # query rows: whole f32 sublane tiles
    pages = _tile_pages(MB, rows * Dp * kp.dtype.itemsize, pages)
    cols = pages * rows
    lengths = lengths.astype(jnp.int32)
    nxt = _next_live_lane(lengths)
    bias, tok = _tile_columns(pages, bs, H, hq, head_major, HQ // H)
    q_spec = pl.BlockSpec((1, hq, Dp), lambda s, *_: (s, 0, 0))
    whole = lambda a: pl.BlockSpec(a.shape, lambda s, *_: (0,) * a.ndim)
    any_spec = pl.BlockSpec(memory_space=pl.ANY)
    in_specs = [q_spec, whole(bias), whole(tok), any_spec, any_spec]
    args = [block_tables, lengths, nxt,
            jnp.pad(q, ((0, 0), (0, hq - HQ), (0, Dp - D))), bias, tok, kp,
            vp]
    if quantized:
        # per-token scales of each lane's table, laid out like a tile's
        # columns so they multiply score columns as they lie (a
        # [S, MB*bs] gather: 1/(H*D) of the payload)
        n_tiles = -(-MB // pages)

        def expand(pool):
            page = jnp.pad(pool[block_tables],
                           ((0, 0), (0, n_tiles * pages - MB), (0, 0)))
            page = (jnp.broadcast_to(page[:, :, None, :],
                                     page.shape[:2] + (H, bs))
                    if head_major else jnp.repeat(page, H, axis=2))
            return page.reshape(S, 1, n_tiles * cols)

        in_specs += [pl.BlockSpec((1, 1, n_tiles * cols),
                                  lambda s, *_: (s, 0, 0))] * 2
        args += [expand(entry[2]), expand(entry[3])]
    out = pl.pallas_call(
        functools.partial(_decode_kernel, bs=bs, pages=pages,
                          scale=1.0 / math.sqrt(D), quantized=quantized),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(S,), in_specs=in_specs,
            out_specs=q_spec,
            scratch_shapes=[
                pltpu.VMEM((2, pages, rows, Dp), kp.dtype),
                pltpu.VMEM((2, pages, rows, Dp), vp.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
                pltpu.SMEM((1,), jnp.int32),  # buffer of the next tile
            ]),
        out_shape=jax.ShapeDtypeStruct((S, hq, Dp), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=_use_interpret(),
        name="paged_decode",
    )(*args)
    return out[:, :HQ, :D]


def paged_decode_attention(q, entry, block_tables, positions, active=None,
                           pages=None, mesh=None):
    """Decode attention straight through the block tables.

    ``q`` is ``[S, HQ, D]`` (each slot's new token, heads unflattened;
    ``HQ`` a multiple of the pool's ``H``: query head ``h`` reads K/V head
    ``h // (HQ // H)``, the mask of the tile's bias, so grouped queries
    cost no second read of a K/V row);
    ``entry`` is one layer's whole arena pool entry — ``(k, v)`` pools
    shaped ``[num_blocks, block_size, H, D]``, or int8
    ``(k, v, k_scale, v_scale)`` with ``[num_blocks, block_size]`` scale
    pools (dequantized in-kernel — the f32 full-width context of the
    gather path is never materialized). ``block_tables`` is ``[S, MB]``
    int32, ``positions`` ``[S]`` int32 (the new token's write position —
    keys at global index ``<= positions[s]`` are attended, matching
    ``masked_attention``'s mask in ``PagedCacheView``). ``active``
    (``[S]`` bool, default all) marks the lanes that hold a request: a
    lane that does not reads no page and returns zeros. Returns
    ``[S, H, D]`` in ``q.dtype``. Tables, positions and ``active`` are
    runtime data: one compiled program serves every churn pattern, and a
    lane costs the pages it has live. ``pages`` (pages per tile) is a
    launch parameter; None takes what fills ``_TILE_BYTES``.
    On a multi-device ``mesh`` the call runs per model-shard (module
    docstring, "SPMD partitioning")."""
    lengths = positions.astype(jnp.int32) + 1
    if active is not None:
        lengths = jnp.where(active, lengths, 0)
    if _mesh_routes(mesh):
        return _sharded_decode(q, entry, block_tables, lengths, pages, mesh)
    return _decode_call(q, entry, block_tables, lengths, pages or 0)


def _sharded_decode(q, entry, block_tables, lengths, pages, mesh):
    """Per-shard decode: map the plain kernel over the mesh —
    heads-sharded q/K/V in, replicated tables/lengths/scales through,
    heads-sharded output back."""
    from ..distributed.sharding_util import headwise_shard_map

    H = entry[0].shape[2]  # the pool's heads shard
    n = len(entry)

    def kernel(q, *rest):
        return _decode_call(q, rest[:n], rest[n], rest[n + 1], pages or 0)

    mapped = headwise_shard_map(
        kernel, mesh,
        in_head_dims=(1, 2, 2) + (None,) * (n - 2) + (None, None),
        out_head_dim=1, num_heads=H)
    return mapped(q, *entry, block_tables, lengths)


# --------------------------------------------------------------- prefill


def _prefill_kernel(bt_ref, meta_ref, q_ref, k_ref, v_ref, *rest, bs,
                    blk_q, blk_h, scale, quantized, sq=None):
    """One (head-group, query-tile, logical-block) step of suffix/chunk
    prefill: flash-style causal attention at global positions
    ``prefix_len + i`` (``meta_ref[0]`` = the runtime prefix length).
    ``sq`` (grouped queries only): the query axis holds the ``sq`` rows of
    each query head of a K/V head one after another, so a tile's first row
    sits at position ``(qi * blk_q) % sq``."""
    if quantized:
        ks_ref, vs_ref, o_ref, m_scr, l_scr, acc_scr = rest
    else:
        (o_ref, m_scr, l_scr, acc_scr), ks_ref, vs_ref = rest, None, None
    qi = pl.program_id(1)
    j = pl.program_id(2)
    nj = pl.num_programs(2)

    @pl.when(j == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    prefix = meta_ref[0]
    if sq is None:
        row0, past = qi * blk_q, prefix + (qi + 1) * blk_q - 1
    else:
        row0 = (qi * blk_q) % sq
        past = prefix + row0 + blk_q - 1

    # a block strictly past this tile's last global row is fully masked
    @pl.when(j * bs <= past)
    def _step():
        block = bt_ref[j]
        rows = prefix + row0 + jax.lax.broadcasted_iota(
            jnp.int32, (1, blk_q, bs), 1)
        cols = j * bs + jax.lax.broadcasted_iota(
            jnp.int32, (1, blk_q, bs), 2)
        _attend_block(  # q is [blk_h, blk_q, D], head-major (the wrapper)
            q_ref[:], k_ref[0], v_ref[0],
            _scale_row(ks_ref, block) if quantized else None,
            _scale_row(vs_ref, block) if quantized else None,
            cols <= rows, scale, m_scr, l_scr, acc_scr)

    @pl.when(j == nj - 1)
    def _fin():
        o_ref[:] = _normalized(l_scr, acc_scr).astype(o_ref.dtype)


def paged_prefill_attention(q, entry, bt_row, prefix_len,
                            block_q=None, block_h=None, mesh=None):
    """Suffix/chunk prefill attention for ONE slot through its table.

    ``q`` is ``[sq, H, D]`` (the padded suffix bucket — padded rows
    produce garbage the caller discards, exactly like the XLA path);
    ``bt_row`` is ``[MB]`` int32, ``prefix_len`` a (traced) scalar: query
    ``i`` attends keys at global index ``<= prefix_len + i``, the
    ``PrefixPrefillView`` mask verbatim. The suffix's own K/V must
    already be scattered into the pools (same call order as the XLA
    path: scatter, then attend). Returns ``[sq, H, D]``. On a
    multi-device ``mesh`` the call runs per model-shard (module
    docstring, "SPMD partitioning")."""
    if _mesh_routes(mesh):
        return _sharded_prefill(q, entry, bt_row, prefix_len,
                                block_q, block_h, mesh)
    sq, HQ, D = q.shape
    quantized = len(entry) == 4
    kp, vp = entry[0], entry[1]
    bs, H = kp.shape[1], kp.shape[2]  # H: the pool's (K/V) heads
    group = HQ // H
    if HQ % H:
        raise ValueError(f"{HQ} query heads do not divide over {H} K/V "
                         "heads")
    MB = bt_row.shape[0]
    blk_q = _query_block(sq, block_q)
    blk_h = _head_group(H, block_h)
    kern = functools.partial(_prefill_kernel, bs=bs, blk_q=blk_q,
                             blk_h=blk_h, scale=1.0 / math.sqrt(D),
                             quantized=quantized,
                             **({"sq": sq} if group > 1 else {}))
    # head-major query/output layout so neither the kernel nor Mosaic
    # transposes inside VMEM; the swapaxes below stay in XLA. Grouped
    # queries: the rows of a K/V head's query heads lie one after another
    # on the query axis, [H, group * sq, D], so a grid step still pairs
    # one K/V head with one tile of rows
    q_hm = jnp.swapaxes(q, 0, 1)  # [HQ, sq, D]
    out_heads = HQ
    if group > 1:
        q_hm, sq = q_hm.reshape(H, group * sq, D), group * sq
    grid = (H // blk_h, sq // blk_q, MB)
    in_specs = [
        pl.BlockSpec((blk_h, blk_q, D),
                     lambda g, qi, j, bt, meta: (g, qi, 0)),
        pl.BlockSpec((1, bs, blk_h, D),
                     lambda g, qi, j, bt, meta: (bt[j], 0, g, 0)),
        pl.BlockSpec((1, bs, blk_h, D),
                     lambda g, qi, j, bt, meta: (bt[j], 0, g, 0)),
    ]
    args = [bt_row, jnp.reshape(jnp.asarray(prefix_len, jnp.int32), (1,)),
            q_hm, kp, vp]
    if quantized:
        in_specs += [_scale_spec(
            bs, lambda g, qi, j, bt, meta: bt[j])] * 2
        args += [entry[2], entry[3]]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((blk_h, blk_q, D),
                               lambda g, qi, j, bt, meta: (g, qi, 0)),
        scratch_shapes=[
            pltpu.VMEM((blk_h, blk_q, _LANES), jnp.float32),
            pltpu.VMEM((blk_h, blk_q, _LANES), jnp.float32),
            pltpu.VMEM((blk_h, blk_q, D), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((H, sq, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=_use_interpret(),
        name="paged_prefill",
    )(*args)
    return jnp.swapaxes(out.reshape(out_heads, -1, D), 0, 1)


def _sharded_prefill(q, entry, bt_row, prefix_len, block_q, block_h, mesh):
    """Per-shard suffix/chunk prefill — same structure as
    :func:`_sharded_decode`; ``prefix_len`` rides replicated like the
    table (runtime data, identical on every device)."""
    from ..distributed.sharding_util import headwise_shard_map

    H = entry[0].shape[2]  # the pool's heads shard
    n = len(entry)

    def kernel(q, *rest):
        return paged_prefill_attention(q, rest[:n], rest[n], rest[n + 1],
                                       block_q=block_q, block_h=block_h)

    mapped = headwise_shard_map(
        kernel, mesh,
        in_head_dims=(1, 2, 2) + (None,) * (n - 2) + (None, None),
        out_head_dim=1, num_heads=H)
    return mapped(q, *entry, bt_row,
                  jnp.asarray(prefix_len, jnp.int32))


def paged_full_prefill_attention(q, k, v, block_size,
                                 block_q=None, block_h=None, mesh=None):
    """Full (no-table) causal prefill through the SAME kernel — the PR 13
    open item: a cache-miss admission has no resident prefix and no block
    table yet, but the flash-style kernel above is exactly the right
    attention for it too. Contiguous ``k``/``v`` (``[sq, H, D]``, the
    chunk's own keys/values) are viewed as ``ceil(sq/bs)`` **pseudo-blocks**
    and addressed through an identity (``arange``) pseudo-table with
    ``prefix_len = 0``: query ``i`` attends keys ``<= i`` — the
    ``CapturePrefillView`` causal mask verbatim. The pad rows a non-divisible
    ``sq`` adds sit at key positions ``>= sq``, above every query row, so
    the mask discards them like the XLA path's padding. One reshape/pad in
    XLA; no gather, no ``[sq, sq]`` materialized probability matrix —
    kernel-on engines have no gather-path prefill left. ``k``/``v`` may
    have fewer heads than ``q`` (grouped queries)."""
    sq = q.shape[0]
    bs = int(block_size)
    nb = -(-sq // bs)
    pad = nb * bs - sq
    if pad:
        k = jnp.pad(k, ((0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, pad), (0, 0), (0, 0)))
    entry = tuple(a.reshape((nb, bs) + a.shape[1:]) for a in (k, v))
    table = jnp.arange(nb, dtype=jnp.int32)
    return paged_prefill_attention(q, entry, table, jnp.int32(0),
                                   block_q=block_q, block_h=block_h,
                                   mesh=mesh)


# ---------------------------------------------------------------- latent
#
# A latent (MLA) layer's state is ONE row a token, W values (the compressed
# key/value ``c_kv`` and the one shared rotary key: 512 + 64). The chip
# keeps an array's minor dimension in whole 128-lane tiles: a ``[blocks,
# block, 576]`` pool would lie 640 wide (an eighth more bytes than it
# counts) and Mosaic refuses to copy a 576-lane slice of it. So the pool
# packs ``pack`` consecutive tokens into one row (:func:`latent_pack`: 2 at
# 576, a row of 1152 = 9 tiles): ``[blocks, block / pack, pack * W]``, the
# same bytes in the same order as ``[blocks, block, W]``, nothing padded.
#
# Decode is ABSORBED: every head's query has been carried into the latent
# space (``[H, W]``), so a token's row is every head's key, and its first
# ``value_dim`` values are every head's value. The kernel is
# :func:`_decode_kernel`'s scheme with one pool and no head mask: per lane
# the live tiles alone, a page copied ONCE and used as keys and as values,
# the softmax state in float32. Packed position ``h`` of a row meets all
# ``H`` queries in one product over the whole lane tiles that hold its key
# (:func:`_latent_spans`: lanes ``[0, 640)`` and ``[512, 1152)`` of a
# 1152-lane row; the query is zero in what those tiles hold of the
# neighbour), and its probabilities meet the tiles that hold its value
# (``[0, 512)`` and ``[512, 1152)``); the kernel keeps lanes ``[h W, h W +
# value_dim)`` of each and writes their sum. A product costs what it
# pushes through the MXU (its copied rows, cut into 128 x 128 tiles, times
# the rows that stream against them), so the tiles of the neighbour's lanes
# are left out: 19 tile passes of ``H`` rows per 128 pool rows where whole
# rows would take 36 (on the chip the kernel alone, PR 36: 0.42 ms against
# 0.59 a call of 310k live tokens; stacking both positions' queries into
# one product of ``2 H`` rows over whole rows halves the passes and gains
# nothing). No lane is sliced off a tile's boundary before the last step.
#
# What the kernel costs beside its products is its page descriptors, on the
# same instruction stream: 16 ns to start one in straight code, whatever
# its bytes (a page of 18 KB is 22 ns of HBM), and a branch costs as much
# again. So the pages of a tile are started in groups of ``_LATENT_GROUP``
# under one branch a group, and a group is waited for as one. A group with
# a live page is copied whole; past the lane's last page it copies that
# page again, so no table entry past a lane's length is ever read.


def latent_pack(width: int) -> int:
    """Tokens a pool row holds so that its lanes fill whole tiles."""
    return 1 if width % _LANES == 0 else 2


def latent_rows(pool, width: int):
    """The pool as ``[blocks, block, W]`` (its logical form; on the chip
    this moves bytes: for the XLA routes and the tests)."""
    return pool.reshape(pool.shape[0], -1, width)


def write_latent_token(pool, blocks, offsets, rows):
    """One new token a lane into a packed latent pool: ``rows`` ``[S, W]``
    go to token ``offsets[s]`` of block ``blocks[s]``. The packed row is
    read, the token's lanes replaced, and the row written back (lanes of
    different requests never share a block; lanes that are not active all
    write scratch block 0, where anything may land)."""
    width = rows.shape[-1]
    pack = pool.shape[2] // width
    if pack == 1:
        return pool.at[blocks, offsets].set(rows.astype(pool.dtype))
    r, h = offsets // pack, offsets % pack
    old = pool[blocks, r]                                  # [S, pack W]
    lane = jnp.arange(pool.shape[2], dtype=jnp.int32)[None, :] // width
    new = jnp.where(lane == h[:, None],
                    jnp.tile(rows.astype(pool.dtype), (1, pack)), old)
    return pool.at[blocks, r].set(new)


#: pages of a tile whose copies start together (one branch, then straight
#: code) and are waited for as one
_LATENT_GROUP = 16
#: a latent tile: 64 pages of 18 KB at block 16 (the per-tile costs, four
#: small products' fill and drain among them, are paid half as often as at
#: ``_TILE_BYTES``; twice this again gains 2% and wastes twice the products
#: on a lane's last tile)
_LATENT_TILE_BYTES = 2 * _TILE_BYTES


def _latent_spans(width, value_dim, pack):
    """Per packed position ``h`` the whole lane tiles of a pool row that
    hold its key and its value: ``(lo, hi, hv)`` with the key in lanes
    ``[lo, hi)`` and the value in ``[lo, hv)`` (576 wide, values 512:
    ``(0, 640, 512)`` and ``(512, 1152, 1152)``)."""
    pw = pack * width

    def up(n):
        return min(-(-n // _LANES) * _LANES, pw)

    return tuple((h * width // _LANES * _LANES, up((h + 1) * width),
                  up(h * width + value_dim)) for h in range(pack))


def _latent_kernel(bt_ref, len_ref, nxt_ref, *refs, bs, pages, group, scale,
                   width, spans):
    pack = len(spans)
    q_refs, (kv_hbm, o_ref, buf, sem, slot_ref) = refs[:pack], refs[pack:]
    s = pl.program_id(0)
    n_lanes = pl.num_programs(0)
    t_tile = pages * bs
    rpb = bs // pack                       # pool rows a block
    hq = o_ref.shape[1]
    cols = pages * rpb

    def each_live_group(lane, j, act):
        """``act(its first page)`` for every group of pages of tile ``j``
        that holds a live token."""
        for g in range(0, pages, group):
            @pl.when((j * pages + g) * bs < len_ref[lane])
            def _():
                act(g)

    def start(lane, j, slot):
        last = (len_ref[lane] - 1) // bs  # the lane's last live page

        def whole(g):
            # the group a lane ends in is copied whole too: in place of a
            # page past the lane's last, that last one again (a block of
            # the lane's own; its columns there are masked)
            for i in range(g, g + group):
                page = jnp.minimum(j * pages + i, last)
                pltpu.make_async_copy(kv_hbm.at[bt_ref[lane, page]],
                                      buf.at[slot, i], sem.at[slot]).start()

        each_live_group(lane, j, whole)

    def wait(lane, j, slot):
        def whole(g):  # its copies count as one of their bytes together
            dst = buf.at[slot, pl.ds(g, group)]
            pltpu.make_async_copy(dst, dst, sem.at[slot]).wait()

        each_live_group(lane, j, whole)

    @pl.when(s == 0)
    def _first():
        # rows never copied meet zero probabilities: they must be finite
        buf[...] = jnp.zeros_like(buf)
        slot_ref[0] = 0

        @pl.when(nxt_ref[0] < n_lanes)
        def _():
            start(nxt_ref[0], 0, 0)

    length = len_ref[s]
    n_tiles = (length + t_tile - 1) // t_tile

    @pl.when(n_tiles == 0)
    def _idle():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(n_tiles > 0)
    def _live():
        col = jax.lax.broadcasted_iota(jnp.int32, (1, cols), 1)
        tok0 = (col // rpb) * bs + (col % rpb) * pack  # of packed position 0

        def tile(j, carry):
            m_prev, l_prev, accs = carry
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

            def rows(lo, hi):
                return buf[slot, :, :, lo:hi].reshape(cols, hi - lo)

            left = length - j * t_tile
            scs = []
            for h, (lo, hi, _) in enumerate(spans):
                # the row is the key of `pack` tokens: each meets its own
                # lane tiles of it alone
                sc = jax.lax.dot_general(
                    q_refs[h][0], rows(lo, hi), (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32) * scale
                scs.append(jnp.where(tok0 + h < left, sc, NEG_INF))
            m_new = m_prev
            for sc in scs:
                m_new = jnp.maximum(m_new, jnp.max(sc, axis=1, keepdims=True))
            corr = jnp.exp(m_prev - m_new)
            l_new, new_accs = corr * l_prev, []
            for sc, acc, (lo, _, hv) in zip(scs, accs, spans):
                p = jnp.exp(sc - m_new)
                l_new = l_new + jnp.sum(p, axis=1, keepdims=True)
                new_accs.append(acc * corr + jax.lax.dot_general(
                    p.astype(buf.dtype), rows(lo, hv),
                    (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32))  # and the value
            return m_new, l_new, tuple(new_accs)

        slot0 = slot_ref[0]
        _, l, accs = jax.lax.fori_loop(
            0, n_tiles, tile,
            (jnp.full((hq, 1), NEG_INF, jnp.float32),
             jnp.zeros((hq, 1), jnp.float32),
             tuple(jnp.zeros((hq, hv - lo), jnp.float32)
                   for lo, _, hv in spans)))
        slot_ref[0] = (slot0 + n_tiles) % 2
        vd = o_ref.shape[2]
        o = sum(acc[:, h * width - lo:h * width - lo + vd]
                for h, (acc, (lo, _, _)) in enumerate(zip(accs, spans)))
        o_ref[0] = (o / l).astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("width", "value_dim", "scale", "pages"))
def _latent_call(q, pool, block_tables, lengths, width, value_dim, scale,
                 pages):
    """The launch; jitted so that a model's layers share one lowered
    kernel inside the step program (see :func:`_decode_call`)."""
    S, HQ, _ = q.shape
    NB, rpb, pw = pool.shape
    pack = pw // width
    bs = rpb * pack
    MB = block_tables.shape[1]
    hq = -(-HQ // 8) * 8  # query rows: whole f32 sublane tiles
    pages = _tile_pages(MB, rpb * pw * pool.dtype.itemsize, pages,
                        _LATENT_TILE_BYTES)
    lengths = lengths.astype(jnp.int32)
    nxt = _next_live_lane(lengths)
    spans = _latent_spans(width, value_dim, pack)
    # the query of packed position h, in the lane tiles that hold its key:
    # zero outside the key's own lanes
    qs = [jnp.pad(q, ((0, 0), (0, hq - HQ),
                      (h * width - lo, hi - (h + 1) * width))
                  ).astype(pool.dtype) for h, (lo, hi, _) in enumerate(spans)]
    out = pl.pallas_call(
        functools.partial(_latent_kernel, bs=bs, pages=pages,
                          group=min(_LATENT_GROUP, pages), scale=scale,
                          width=width, spans=spans),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(S,),
            in_specs=[pl.BlockSpec((1, hq, hi - lo), lambda s, *_: (s, 0, 0))
                      for lo, hi, _ in spans]
            + [pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((1, hq, value_dim),
                                   lambda s, *_: (s, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((2, pages, rpb, pw), pool.dtype),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.SMEM((1,), jnp.int32),  # buffer of the next tile
            ]),
        out_shape=jax.ShapeDtypeStruct((S, hq, value_dim), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=_use_interpret(),
        name="paged_latent_decode",
    )(block_tables, lengths, nxt, *qs, pool)
    return out[:, :HQ]


def paged_latent_decode(q, pool, block_tables, positions, value_dim: int,
                        scale: float, active=None, pages=None):
    """Absorbed latent attention of one new token a lane, straight through
    the block tables. ``q`` ``[S, H, W]`` (each head's query carried into
    the latent space, its rotary part behind); ``pool`` the packed latent
    pool ``[num_blocks, block_size / pack, pack * W]``, a token's one row
    of ``W`` values; lane ``s`` attends the rows at positions ``<=
    positions[s]`` of its table: scores ``q . row * scale``, output the
    probabilities' sum of the rows' first ``value_dim`` values, ``[S, H,
    value_dim]`` in ``q.dtype`` (the packed positions' parts are summed in
    the kernel, in float32). Each packed position of a copied page meets
    the ``H`` queries in one product over its own lane tiles, and its
    probabilities the tiles of its value (see "latent" above). A lane that
    is not ``active`` reads no page and returns zeros. Tables, positions
    and ``active`` are runtime data. ``pages`` (pages a tile) is a launch
    parameter; None: what fills ``_LATENT_TILE_BYTES``."""
    lengths = positions.astype(jnp.int32) + 1
    if active is not None:
        lengths = jnp.where(active, lengths, 0)
    return _latent_call(q, pool, block_tables, lengths, int(q.shape[-1]),
                        int(value_dim), float(scale), pages or 0)


def _latent_prefill_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr,
                           *, scale, blk):
    """One (head, qi, ki) step of the latent prefill: the online softmax of
    ``pallas_ops._flash_fwd_kernel`` as it stood before that kernel's tiles
    went by ``_causal_tile`` (every tile at or under the diagonal one
    masked step, the tiles above it skipped), kept apart so that a change
    to the training kernel moves no served token (PERF.md section 6,
    PR 46)."""
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    @pl.when(ki <= qi)
    def _step():
        q = q_ref[0]  # [blk, d]
        k = k_ref[0]
        v = v_ref[0]  # [blk, dv]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale  # [blk, blk]
        s = _causal_mask(s, (qi - ki) * blk)
        m_prev = m_scr[:, 0:1]  # [blk, 1]
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)
        correction = jnp.exp(m_prev - m_new)
        l_new = correction * l_scr[:, 0:1] + jnp.sum(p, axis=1, keepdims=True)
        pv = jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)  # [blk, dv]
        acc_scr[:] = acc_scr[:] * correction + pv
        m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[:] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(ki == nk - 1)
    def _finish():
        denom = l_scr[:, 0:1]
        denom = jnp.where(denom == 0.0, 1.0, denom)
        o_ref[0] = (acc_scr[:] / denom).astype(o_ref.dtype)


def latent_prefill_attention(q, k, v, scale: float, block: int = 512):
    """Causal attention of one prompt with keys wider than values (the
    EXPANDED form of latent attention: ``q``, ``k`` ``[s, H, 192]``, ``v``
    ``[s, H, 128]``): a flash forward kernel (:func:`_latent_prefill_kernel`)
    over head-major copies, the blocks above the diagonal skipped. ``s`` is
    cut into the largest blocks of at most ``block`` rows, a multiple of
    128, that divide it (one block where none does). Returns ``[s, H, v
    width]``."""
    s, h, d = q.shape
    dv = v.shape[-1]
    blk = next((b for b in range(block, 0, -_LANES) if s % b == 0), s)
    qf, kf, vf = (jnp.swapaxes(a, 0, 1) for a in (q, k, v))
    out = pl.pallas_call(
        functools.partial(_latent_prefill_kernel, scale=float(scale), blk=blk),
        grid=(h, s // blk, s // blk),
        in_specs=[pl.BlockSpec((1, blk, d), lambda b, i, j: (b, i, 0)),
                  pl.BlockSpec((1, blk, d), lambda b, i, j: (b, j, 0)),
                  pl.BlockSpec((1, blk, dv), lambda b, i, j: (b, j, 0))],
        out_specs=[pl.BlockSpec((1, blk, dv), lambda b, i, j: (b, i, 0))],
        out_shape=[jax.ShapeDtypeStruct((h, s, dv), q.dtype)],
        scratch_shapes=[pltpu.VMEM((blk, _LANES), jnp.float32),
                        pltpu.VMEM((blk, _LANES), jnp.float32),
                        pltpu.VMEM((blk, dv), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=_use_interpret(),
        name="latent_prefill_flash",
    )(qf, kf, vf)[0]
    return jnp.swapaxes(out, 0, 1)


# ------------------------------------------------- banded (window) prefill
#
# A sliding layer's prompt attention: query ``t`` sees keys ``t - window +
# 1 .. t``. At a window of 4,096 the XLA form's scores are gigabytes a
# chunk, so the prompt goes through a flash kernel whose key axis is the
# BAND: of the ``s / tile`` key tiles a query tile could see, the grid
# visits the ``ceil((window - 1) / tile) + 1`` that reach into its window
# (all that lie at or under the diagonal where there is no window), by an
# index map that counts back from the diagonal tile. A tile before the
# sequence's start maps to tile 0 and is skipped: the pipeline copies a
# block only when its index changes, so what is not multiplied is not
# copied either.

#: rows of a query tile and of a key tile of the banded prefill kernel
_SWA_BLOCK = 512


def _swa_prefill_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr,
                        *, scale, blk, band, window):
    """One (query head, query tile, band step) of
    :func:`swa_prefill_attention`: ``pallas_ops._flash_fwd_kernel``'s online
    softmax, the key tile ``band - 1 - j`` tiles under the diagonal one. A
    row that sees nothing of a tile leaves ``exp(0)`` there under a running
    maximum of ``NEG_INF``; the diagonal tile, which comes last and holds
    the row's own key, scales that away (``exp(NEG_INF - m)`` is 0)."""
    qi, j = pl.program_id(1), pl.program_id(2)
    kt = qi - (band - 1) + j

    @pl.when(j == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    def step(masked: bool):
        q, k, v = q_ref[0], k_ref[0], v_ref[0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        if masked:
            rows = qi * blk + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            cols = kt * blk + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            seen = cols <= rows
            if window is not None:
                seen &= rows - cols < window
            s = jnp.where(seen, s, NEG_INF)
        m_prev = m_scr[:, 0:1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_new = corr * l_scr[:, 0:1] + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[:] = acc_scr[:] * corr + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[:] = jnp.broadcast_to(l_new, l_scr.shape)

    # a tile wholly inside the band needs no mask: every key of it is at
    # or under every row's own and within its window
    inside = kt < qi
    if window is not None:
        inside &= (qi - kt + 1) * blk - 1 < window

    @pl.when((kt >= 0) & inside)
    def _whole():
        step(False)

    @pl.when((kt >= 0) & jnp.logical_not(inside))
    def _edge():
        step(True)

    @pl.when(j == band - 1)
    def _finish():
        o_ref[0] = (acc_scr[:] / l_scr[:, 0:1]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("window", "block"))
def _swa_call(q, k, v, window, block):
    """The launch; jitted so that a model's layers of one kind share one
    lowered kernel inside a prefill program (see :func:`_decode_call`)."""
    s, h, d = q.shape
    kvh = k.shape[1]
    group = h // kvh
    blk = min(block, -(-s // 8) * 8)
    n = -(-s // blk)
    band = n if window is None else min(n, -(-(window - 1) // blk) + 1)
    pad = ((0, n * blk - s), (0, 0), (0, 0))
    # head-major copies; the pad rows are keys past every real query and
    # queries nobody reads
    qf, kf, vf = (jnp.swapaxes(jnp.pad(a, pad), 0, 1) for a in (q, k, v))
    key_tile = lambda b, i, j: (b // group,
                                jnp.maximum(i - (band - 1) + j, 0), 0)
    out = pl.pallas_call(
        functools.partial(_swa_prefill_kernel, scale=1.0 / math.sqrt(d),
                          blk=blk, band=band, window=window),
        grid=(h, n, band),
        in_specs=[pl.BlockSpec((1, blk, d), lambda b, i, j: (b, i, 0)),
                  pl.BlockSpec((1, blk, d), key_tile),
                  pl.BlockSpec((1, blk, d), key_tile)],
        out_specs=[pl.BlockSpec((1, blk, d), lambda b, i, j: (b, i, 0))],
        out_shape=[jax.ShapeDtypeStruct((h, n * blk, d), q.dtype)],
        scratch_shapes=[pltpu.VMEM((blk, _LANES), jnp.float32),
                        pltpu.VMEM((blk, _LANES), jnp.float32),
                        pltpu.VMEM((blk, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=_use_interpret(),
        name="swa_prefill_flash",
    )(qf, kf, vf)[0]
    return jnp.swapaxes(out, 0, 1)[:s]


def swa_prefill_attention(q, k, v, window=None, block=None):
    """Attention of one prompt over a window of its own keys: ``q`` ``[s,
    H, D]``, ``k``, ``v`` ``[s, KVH, D]`` (``H`` a multiple of ``KVH``:
    query head ``h`` reads K/V head ``h // (H // KVH)`` through the index
    map, no K/V row repeated in memory); query ``t`` attends keys ``j <=
    t`` with ``t - j < window`` (the window counts the query's own
    position; None: every key at or before it), scores over ``sqrt(D)``.
    Tiles of ``block`` rows (None: :data:`_SWA_BLOCK`; ``s`` is padded to
    whole tiles); the key tiles outside a query tile's band are neither
    multiplied nor copied, so a window costs ``O(s window)`` and none
    ``O(s^2 / 2)``. Returns ``[s, H, D]`` in ``q.dtype``."""
    return _swa_call(q, k, v, None if window is None else int(window),
                     int(block or _SWA_BLOCK))
