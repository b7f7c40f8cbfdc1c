"""known-bad twin of the paged-attention kernel dispatch pattern
(ops.paged_attention / cache_views.PagedCacheView): block tables and
positions must ride compiled programs as runtime DATA. This one
(1) derives the kernel's workload from the table's CONTENTS — boolean-
mask indexing over the non-scratch entries gives a data-dependent shape
(shape-from-data), so every distinct table fill mints a new executable;
and (2) branches the trace on the filled block COUNT — ``int()`` of a
traced reduction is a traced cast feeding a python ``if`` (traced
branch): admit/retire churn would recompile, the exact invariant the
paged kernels exist to keep."""
import jax
import jax.numpy as jnp


def paged_step(pools, q, block_tables, positions):
    # BAD: data-dependent shape — the set of live (non-scratch) table
    # entries picks how many blocks the "kernel" covers
    live_rows = block_tables[block_tables != 0]
    k = pools[0][live_rows]
    # BAD: traced cast + branch on the block count — the trace forks on
    # runtime data, so a table that fills one more block re-lowers
    n_blocks = int((block_tables != 0).sum())
    if n_blocks > 4:
        scores = jnp.einsum("shd,nbhd->snb", q, k) * 0.5
    else:
        scores = jnp.einsum("shd,nbhd->snb", q, k)
    return scores.sum(), positions


def run(pools, q, block_tables, positions):
    step = jax.jit(paged_step)
    return step(pools, q, block_tables, positions)
