"""Engine: the host's wait for a prefill's first token, a mean in ms over
the compiled prefill calls: ``time_us.prefill.wait`` / ``prefill.calls``.
The device's part of an admission as the host sees it: the decode step
that was in flight ahead of the prefill, then the prefill. A chunk that
yields no token waits for nothing and counts as a call. A program
without the phase gives nothing."""


def read(run):
    wait = run["counters"].get("time_us.prefill.wait")
    calls = run["counters"].get("prefill.calls")
    if wait is None or not calls:
        return None
    return wait / calls / 1e3
