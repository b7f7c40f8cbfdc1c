"""KV arena: 1 - the least ``arena.blocks_free`` over the total, polled
once a second inside the window."""


def read(run):
    rows = [r for r in run["polls"] if r.get("arena.blocks_total")]
    if not rows:
        return None
    return 100.0 * max(1.0 - r["arena.blocks_free"] / r["arena.blocks_total"]
                       for r in rows)
