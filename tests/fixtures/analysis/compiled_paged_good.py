"""known-good twin of the paged-attention kernel dispatch pattern
(ops.paged_attention / cache_views.PagedCacheView): the block table is
runtime data with a STATIC shape — every table entry is covered
unconditionally (scratch rows are masked by position, never filtered
out), and launch-shaping decisions come from static shapes
(``block_tables.shape``), not traced contents. One executable serves
every admit/retire pattern."""
import jax
import jax.numpy as jnp


def paged_step(pools, q, block_tables, positions):
    # static shape: gather EVERY table entry; garbage rows are masked by
    # position below, not filtered into a data-dependent shape
    k = pools[0][block_tables]  # [S, MB, bs, H, D]
    scores = jnp.einsum("shd,smbhd->smb", q, k)
    # the workload bound is the table's static WIDTH, not its contents
    max_blocks = block_tables.shape[1]
    scale = 0.5 if max_blocks > 4 else 1.0
    bs = k.shape[2]
    gk = jnp.arange(max_blocks * bs).reshape(max_blocks, bs)
    valid = gk[None] <= positions[:, None, None]
    scores = jnp.where(valid, scores * scale, -1e30)
    return scores.sum(), positions


def run(pools, q, block_tables, positions):
    step = jax.jit(paged_step)
    return step(pools, q, block_tables, positions)
