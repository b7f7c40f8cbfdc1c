"""KV arena: the share of the window rings' rows that hold a key when a
decode step reads them: 100 x ``window.rows_live`` / ``window.rows_read``,
both summed by the step program over its sliding layers and the lanes that
hold a request (``min(position + 1, window)`` against ``window``) and read
back with the step's tokens. The step's attention reads the ring whole:
100 less this is what a kernel over the live rows alone, or window layers
held as blocks, would not read. A program that counts none has nothing
here to read."""


def read(run):
    c = run["counters"]
    read_rows = c.get("window.rows_read", 0)
    if not read_rows:
        return None
    return 100.0 * c.get("window.rows_live", 0) / read_rows
