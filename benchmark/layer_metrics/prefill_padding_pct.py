"""Engine: the share of the positions the window's prefill programs
computed that were padding: 100 x (1 - ``tokens.prefill`` /
``prefill.positions_computed``). A compiled prefill call runs its whole
bucket (``core.compile_cache.prefill_bucket``: 2^k or 3 x 2^(k-1)), the
chip computes a padded position like a real one, and both MFU readers
count it as work. ``tokens.prefill`` is bumped at an admission's end by
its whole suffix, a chunked admission's chunks included, so
``chunk.tokens`` is not added; a chunked admission that began before the
window and ended in it, or the reverse, puts its calls and its tokens on
two sides of a snapshot. A program that does not count the computed
positions gives nothing."""


def read(run):
    computed = run["counters"].get("prefill.positions_computed")
    real = run["counters"].get("tokens.prefill")
    if not computed or real is None:
        return None
    return 100.0 * (1.0 - real / computed)
