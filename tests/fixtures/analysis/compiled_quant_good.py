"""known-good twin of the quantized-serving dequant pattern
(quantization.quantize_kv / cache_views.scatter_rows): the scale is a traced
ARRAY (no host cast — it rides the program as data, one executable for
every batch), and the dequant covers every element unconditionally with
masking expressed as ``where`` over a static shape — no data-dependent
shapes anywhere."""
import jax
import jax.numpy as jnp


def dequant_step(pools, q, w):
    # scale stays an array: traced, never synced, never a constant
    scale = jnp.maximum(jnp.abs(w).max(), 1e-9) / 127.0
    # masking instead of boolean indexing: static shape, data as data
    live_sum = jnp.where(w != 0, w, 0.0).sum()
    deq = q.astype(jnp.float32) * scale
    return deq, live_sum, pools


def run(pools, q, w):
    step = jax.jit(dequant_step)
    return step(pools, q, w)
