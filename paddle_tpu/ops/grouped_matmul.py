"""Expert layers without dropped tokens: route, sort the assignments by
expert, one grouped matmul over the stacked expert weights, unsort, combine.

``grouped_matmul(lhs [m, k], rhs [E_held, k, n], group_sizes [E])`` multiplies
the rows of group ``e`` (consecutive, ``group_sizes[e]`` of them, in expert
order) by ``rhs[e - first]``; rows of experts that are not held
(``first <= e < first + E_held``) come out zero. Two routes, chosen by the
device like the paged kernels' (:func:`~paddle_tpu.ops.pallas_ops._use_interpret`):

* on a TPU, JAX's own megablox ``gmm`` Pallas kernel
  (``jax.experimental.pallas.ops.tpu.megablox``): it visits each (expert,
  row tile) pair that holds a row, so an expert's weights are read once a
  row tile it reaches and an expert with no row is never read; a row meets
  its own expert's weights alone. Its ``group_offset`` is the share's
  ``first``. Tiles by the number of rows: :data:`_TILES`.
* elsewhere ``jax.lax.ragged_dot`` (XLA's own lowering; on the CPU that is
  a dense masked product, which tiny test sizes bear), a share's experts
  framed by two zero matrices that take the rows before and after it.

No capacity and no dropping at any load: every assignment has a row.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .pallas_ops import _use_interpret

__all__ = ["grouped_matmul", "route_sigmoid_topk", "expert_ffn",
           "load_counters"]

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


def grouped_matmul(lhs, rhs, group_sizes, first: int = 0,
                   out_dtype=None, interpret: bool = False):
    """See the module's head. ``interpret`` runs the megablox kernel in
    the Pallas interpreter whatever the device (the tests run its body on
    the CPU so); without it the device chooses the route."""
    out_dtype = out_dtype or lhs.dtype
    m, k = lhs.shape
    held, _, n = rhs.shape
    total = group_sizes.shape[0]
    if not interpret and _use_interpret():
        if held != total:
            zero = jnp.zeros((1,) + rhs.shape[1:], rhs.dtype)
            rhs = jnp.concatenate([zero, rhs, zero])
            group_sizes = jnp.concatenate([
                jnp.sum(group_sizes[:first], keepdims=True),
                group_sizes[first:first + held],
                jnp.sum(group_sizes[first + held:], keepdims=True)])
        return jax.lax.ragged_dot(lhs, rhs, group_sizes.astype(jnp.int32),
                                  preferred_element_type=out_dtype)
    from jax.experimental.pallas.ops.tpu.megablox import gmm

    tm, tk, tn = _tiling(m, k, n)
    pad = -m % tm
    if pad:  # rows past the groups' sum belong to no expert: cut off below
        lhs = jnp.pad(lhs, ((0, pad), (0, 0)))
    out = gmm(lhs, rhs, group_sizes.astype(jnp.int32),
              preferred_element_type=out_dtype, tiling=(tm, tk, tn),
              group_offset=jnp.asarray(first, jnp.int32),
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
    flat = idx.reshape(-1)
    order = jnp.argsort(flat, stable=True)          # assignments by expert
    sorted_e = flat[order]
    sizes = jnp.sum(sorted_e[:, None] == jnp.arange(num_experts)[None, :],
                    axis=0, dtype=jnp.int32)
    rows = x[order // k]                            # [T k, h]
    gu = grouped_matmul(rows, e_up, sizes, first, interpret=interpret)
    width = gu.shape[-1] // 2
    act = (jax.nn.silu(gu[:, :width].astype(jnp.float32))
           * gu[:, width:].astype(jnp.float32)).astype(x.dtype)
    y = grouped_matmul(act, e_down, sizes, first, interpret=interpret)
    mine = (sorted_e >= first) & (sorted_e < first + held)
    # unsort by the inverse permutation (a gather of rows in x's dtype: an
    # expert's output is rounded as a dense MLP's is), then weigh and add
    # each token's k in float32
    inv = jnp.argsort(order)
    wk = jnp.where(mine, w.reshape(-1)[order], 0.0)[inv].reshape(t, k, 1)
    return jnp.sum(y[inv].reshape(t, k, -1).astype(jnp.float32) * wk, axis=1)


def load_counters(idx, num_experts: int, rows=None):
    """What one expert layer's step adds to the load counters, as int32
    scalars: assignments, the busiest expert's assignments, experts that
    got any. ``rows`` ``[T]`` bool: the tokens that count (the lanes that
    hold a request)."""
    hits = idx[..., None] == jnp.arange(num_experts, dtype=idx.dtype)
    if rows is not None:
        hits = hits & rows[:, None, None]
    load = jnp.sum(hits, axis=(0, 1), dtype=jnp.int32)
    return {"moe.assignments": jnp.sum(load),
            "moe.max_expert_assignments": jnp.max(load),
            "moe.experts_touched": jnp.sum(load > 0, dtype=jnp.int32),
            "moe.layer_steps": jnp.int32(1)}
