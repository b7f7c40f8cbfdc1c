"""Expert layers without dropped tokens: route, sort the assignments by
expert, one grouped matmul over the stacked expert weights, unsort, combine.

``grouped_matmul(lhs [m, k], rhs [E, k, n], group_sizes [E])`` multiplies
the rows of group ``e`` (consecutive, ``group_sizes[e]`` of them, in expert
order) by ``rhs[e]``; rows past the groups' sum are no expert's, and what
comes out there is not defined (the caller masks them). Two routes, chosen
by the device like the paged kernels'
(:func:`~paddle_tpu.ops.pallas_ops._use_interpret`):

* on a TPU, JAX's own megablox ``gmm`` Pallas kernel
  (``jax.experimental.pallas.ops.tpu.megablox``): it visits each (expert,
  row tile) pair that holds a row, so an expert's weights are read once a
  row tile it reaches and an expert with no row is never read; a row meets
  its own expert's weights alone. Tiles by the number of rows:
  :data:`_TILES`.
* elsewhere ``jax.lax.ragged_dot`` (XLA's own lowering; on the CPU that is
  a dense masked product, which tiny test sizes bear).

No capacity and no dropping at any load: every assignment has a row.

**A share of the experts** (``first``, ``E_held`` of ``num_experts``: what
one chip of an expert-parallel deployment holds) moves the rows its OWN
experts take, not every assignment's: the assignments are sorted with the
share's in front, and the grouped matmuls take them ``row_cap`` rows a pass
(:func:`row_cap`: static, twice what an even routing sends the share,
the whole of them for a share that is the whole), as many passes as the
share's assignments need (a ``fori_loop`` whose count is the step's own).
So the bound on what a pass gathers is static and nothing is dropped at any
load: routing skewed wholly onto the share costs ``assignments / row_cap``
passes, an even one costs one, a step that sends the share nothing none.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .pallas_ops import _use_interpret

__all__ = ["grouped_matmul", "route_sigmoid_topk", "route_softmax_topk",
           "zero_expert_weight", "row_cap", "expert_ffn", "load_counters"]

#: megablox tiles ``(tm, tk, tn)`` by rows ``m`` (the first entry whose
#: bound ``m`` is under): a decode step's few rows an expert take the
#: smallest row tile and wide weight tiles (the weights' stream is the
#: time); a prefill's hundreds of rows an expert take taller row tiles, so
#: that a weight tile meets more rows each time it is read. Each fits the
#: 16 MiB of scoped VMEM with both buffers (tests/test_tpu_compile.py).
_TILES = ((2048, (128, 1792, 1024)),
          (32768, (256, 896, 1024)),
          (1 << 62, (512, 896, 1024)))


def _tiling(m: int, k: int, n: int):
    tm, tk, tn = next(t for bound, t in _TILES if m < bound)

    def fit(size, tile):  # the largest multiple of 128 up to `tile` that
        return next((t for t in range(min(tile, size), 127, -128)  # divides
                     if size % t == 0), size)         # the size, or all of it

    return min(tm, m), fit(k, tk), fit(n, tn)


def grouped_matmul(lhs, rhs, group_sizes, out_dtype=None,
                   interpret: bool = False):
    """See the module's head. ``interpret`` runs the megablox kernel in
    the Pallas interpreter whatever the device (the tests run its body on
    the CPU so); without it the device chooses the route."""
    out_dtype = out_dtype or lhs.dtype
    m, k = lhs.shape
    n = rhs.shape[2]
    if not interpret and _use_interpret():
        return jax.lax.ragged_dot(lhs, rhs, group_sizes.astype(jnp.int32),
                                  preferred_element_type=out_dtype)
    from jax.experimental.pallas.ops.tpu.megablox import gmm

    tm, tk, tn = _tiling(m, k, n)
    pad = -m % tm
    if pad:  # rows past the groups' sum belong to no expert: cut off below
        lhs = jnp.pad(lhs, ((0, pad), (0, 0)))
    out = gmm(lhs, rhs, group_sizes.astype(jnp.int32),
              preferred_element_type=out_dtype, tiling=(tm, tk, tn),
              interpret=interpret)
    return out[:m]


def route_sigmoid_topk(x, router_w, bias, k: int, scaling: float,
                       normalize: bool = True):
    """Sigmoid scores over every expert in float32; the ``k`` largest of
    score + selection bias are chosen; weights are the chosen scores (not
    the biased ones), normalized to sum 1 where ``normalize``, times
    ``scaling``. ``x`` ``[T, h]`` -> ``(experts [T, k] int32, weights [T,
    k] float32)``."""
    s = jax.nn.sigmoid(jnp.matmul(x.astype(jnp.float32),
                                  router_w.astype(jnp.float32),
                                  precision=jax.lax.Precision.HIGHEST))
    _, idx = jax.lax.top_k(s + bias.astype(jnp.float32), k)
    w = jnp.take_along_axis(s, idx, axis=-1)
    if normalize:
        w = w / jnp.sum(w, -1, keepdims=True)
    return idx.astype(jnp.int32), w * scaling


def route_softmax_topk(x, router_w, bias, k: int, scaling: float):
    """Softmax over EVERY column in float32 (routed experts first, then
    the zero-compute ones); the ``k`` largest of probability + selection
    bias are chosen; weights are the chosen probabilities (not the biased
    ones) times ``scaling``, NOT renormalized. ``x`` ``[T, h]`` ->
    ``(columns [T, k] int32, weights [T, k] float32)``."""
    p = jax.nn.softmax(jnp.matmul(x.astype(jnp.float32),
                                  router_w.astype(jnp.float32),
                                  precision=jax.lax.Precision.HIGHEST), -1)
    _, idx = jax.lax.top_k(p + bias.astype(jnp.float32), k)
    return idx.astype(jnp.int32), jnp.take_along_axis(p, idx, -1) * scaling


def zero_expert_weight(idx, w, routed: int):
    """``[T]`` float32: the weight each token gave its zero-compute
    experts (the columns from ``routed`` on). An identity expert returns
    its input, so their part of the layer is this times the input: no row
    moved, no weight read."""
    return jnp.sum(jnp.where(idx >= routed, w, 0.0), axis=-1)


#: a pass of a share's grouped matmuls takes a multiple of this many rows
_ROW_TILE = 64


def row_cap(assignments: int, held: int, num_experts: int) -> int:
    """Rows one pass of :func:`expert_ffn` gathers for a share of ``held``
    of ``num_experts`` columns out of ``assignments``: twice what an even
    routing sends it, in whole row tiles, and never more than there are
    (a share of half the experts or more takes all in one pass)."""
    even = 2 * assignments * held
    cap = -(-even // (num_experts * _ROW_TILE)) * _ROW_TILE
    return min(max(cap, _ROW_TILE), assignments)


@functools.partial(jax.jit,
                   static_argnames=("num_experts", "first", "interpret"))
def expert_ffn(x, idx, w, e_up, e_down, num_experts: int, first: int = 0,
               interpret: bool = False):
    """``sum_i w[t, i] SwiGLU_{idx[t, i]}(x[t])`` over the experts held
    (``e_up`` ``[E_held, h, 2 width]`` = ``[gate | up]``, ``e_down``
    ``[E_held, width, h]``, the experts ``first .. first + E_held`` of the
    ``num_experts`` that ``idx`` ranges over; an assignment to an expert
    that is not held adds nothing). ``x`` ``[T, h]`` -> ``[T, h]``
    float32."""
    t, k = idx.shape
    held = e_up.shape[0]
    n, cap = t * k, row_cap(t * k, held, num_experts)
    flat = idx.reshape(-1)
    local = (flat >= first) & (flat < first + held)
    # assignments by expert, the share's own in front of the others'
    key = jnp.where(local, flat - first, held)
    order = jnp.argsort(key, stable=True)
    sizes = jnp.sum(key[order][:, None] == jnp.arange(held)[None, :],
                    axis=0, dtype=jnp.int32)
    wf = jnp.where(local, w.reshape(-1), 0.0)

    def ffn(src, counts, live):
        """The share's experts over the assignments ``src`` (sorted
        positions' assignments, ``counts`` of them an expert, ``live`` of
        them the share's): ``[len(src), h]`` in ``x``'s dtype (an expert's
        output is rounded as a dense MLP's is). A row past ``live`` is no
        expert's and was not written: zero."""
        gu = grouped_matmul(x[src // k], e_up, counts, interpret=interpret)
        width = gu.shape[-1] // 2
        act = (jax.nn.silu(gu[:, :width].astype(jnp.float32))
               * gu[:, width:].astype(jnp.float32)).astype(x.dtype)
        y = grouped_matmul(act, e_down, counts, interpret=interpret)
        if held == num_experts:
            return y
        return jnp.where(jnp.arange(src.shape[0])[:, None] < live, y, 0)

    if cap == n:
        # one pass takes every assignment: unsort by the inverse
        # permutation (a gather), then weigh and add each token's k in
        # float32
        y = ffn(order, sizes, jnp.sum(sizes))[jnp.argsort(order)]
        return jnp.sum(y.reshape(t, k, -1).astype(jnp.float32)
                       * wf.reshape(t, k, 1), axis=1)

    ends = jnp.cumsum(sizes)
    padded = jnp.pad(order, (0, -n % cap))

    def one_pass(j, out):
        """Sorted positions ``[j cap, (j + 1) cap)``: each expert's rows
        among them, through the experts, added to their tokens."""
        lo = j * cap
        src = jax.lax.dynamic_slice(padded, (lo,), (cap,))
        part = jnp.clip(ends - lo, 0, cap) - jnp.clip(ends - sizes - lo, 0,
                                                      cap)
        y = ffn(src, part, ends[-1] - lo)
        return out.at[src // k].add(y.astype(jnp.float32)
                                    * wf[src][:, None])

    return jax.lax.fori_loop(0, -(-ends[-1] // cap), one_pass,
                             jnp.zeros((t, x.shape[1]), jnp.float32))


def load_counters(idx, num_experts: int, rows=None, first: int = 0,
                  held: int = None, routed: int = None):
    """What one expert layer's step adds to the load counters, as int32
    scalars. ``rows`` ``[T]`` bool: the tokens that count (the lanes that
    hold a request). ``first``, ``held``: the share of the ``num_experts``
    columns that is computed here (all of them where not given); ``routed``:
    the columns from there on are zero-compute experts. Assignments: all,
    those to zero-compute experts, those to the share; over the share's
    experts the busiest one's and how many got any; and the rows the
    grouped matmuls' path gathered (its passes times :func:`row_cap`,
    every lane's token counted: the path does not know an idle lane)."""
    held = num_experts - first if held is None else held
    hits = idx[..., None] == jnp.arange(first, first + held, dtype=idx.dtype)
    n_local = jnp.sum(hits, dtype=jnp.int32)
    counted = jnp.ones(idx.shape, bool) if rows is None else \
        jnp.broadcast_to(rows[:, None], idx.shape)
    load = jnp.sum(hits & counted[..., None], axis=(0, 1), dtype=jnp.int32)
    cap = row_cap(idx.size, held, num_experts)
    zero = counted & (idx >= (num_experts if routed is None else routed))
    return {"moe.assignments": jnp.sum(counted, dtype=jnp.int32),
            "moe.zero_assignments": jnp.sum(zero, dtype=jnp.int32),
            "moe.local_assignments": jnp.sum(load),
            "moe.max_expert_assignments": jnp.max(load),
            "moe.experts_touched": jnp.sum(load > 0, dtype=jnp.int32),
            "moe.rows_moved": -(-n_local // cap) * cap,
            "moe.layer_steps": jnp.int32(1)}
