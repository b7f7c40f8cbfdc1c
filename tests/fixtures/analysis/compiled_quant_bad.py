"""known-bad twin of the quantized-serving dequant pattern
(quantization.quantize_kv / cache_views.scatter_rows): a compiled dequant
must be all-array math. This one (1) computes its scale THROUGH a host
cast — ``float()`` on a traced absmax is traced-cast: it forces a
device sync per call and bakes the first batch's scale into the
executable as a constant; and (2) derives the quantization support
from the DATA — boolean-mask indexing gives a data-dependent shape
(shape-from-data), so every distinct sparsity pattern mints a new
executable."""
import jax
import jax.numpy as jnp


def dequant_step(pools, q, w):
    # BAD: host cast of a traced reduction — the scale becomes a python
    # float (sync + burned-in constant), not a traced array
    scale = float(jnp.abs(w).max()) / 127.0
    # BAD: data-dependent shape — the nonzero support of w picks how
    # many elements get dequantized
    live = w[w != 0]
    deq = q.astype(jnp.float32) * scale
    return deq, live.sum(), pools


def run(pools, q, w):
    step = jax.jit(dequant_step)
    return step(pools, q, w)
