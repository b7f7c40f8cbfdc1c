"""KV arena: the share of a lane's live K/V rows that a decode step's
attention reads: 100 x ``sparse.rows_read`` / ``sparse.rows_live``, both
summed by the step program over its layers and the lanes that hold a
request and read back with the step's tokens. ``rows_read`` is counted by
the cache view where it gathers (the rows handed to the attention that
hold a key: ``min(position + 1, topk)`` a lane), ``rows_live`` is what a
dense read would have touched (``position + 1``). Near 100 x ``topk`` /
context; 100 where the view attends every live row or the context is no
longer than ``topk``. A program that counts none has nothing here to
read."""


def read(run):
    c = run["counters"]
    live = c.get("sparse.rows_live", 0)
    if not live:
        return None
    return 100.0 * c.get("sparse.rows_read", 0) / live
