"""Gateway: how often a stream consumer came back from its wait
(``gateway.stream_wakeups``: ``ReplicaPool.stream`` on a background pool,
a return by the backstop included), a mean per token the window generated
(``tokens.generated``). About one where a consumer blocks until its
producer has a token for it; some forty where every consumer polled a
thousand times a second. A program that does not count them gives
nothing."""


def read(run):
    wakeups = run["counters"].get("gateway.stream_wakeups")
    tokens = run["counters"].get("tokens.generated")
    if wakeups is None or not tokens:
        return None
    return wakeups / tokens
