"""Engine: the host's part of a compiled prefill call, a mean in ms:
(``time_us.prefill`` - ``time_us.prefill.wait``) / ``prefill.calls``.
What is left of an admission once the wait for the device is taken out:
slot claim, reservation, prefix lookup, the sampling and mask rows
(``prefill.setup``), the bucket's padding and the uploads
(``prefill.upload``), the dispatch, the draft's prefill, the prefix
insert, mirrors and gauges (``prefill.finish``). ``counters_moved`` has
each child. A program without the children gives nothing."""


def read(run):
    c = run["counters"]
    total, wait = c.get("time_us.prefill"), c.get("time_us.prefill.wait")
    calls = c.get("prefill.calls")
    if total is None or wait is None or not calls:
        return None
    return (total - wait) / calls / 1e3
