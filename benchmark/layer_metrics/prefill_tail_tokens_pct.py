"""Engine: tokens that went through the layers of the model's prefill tail
(``prefill.tail_tokens``: one a prefill where the tail runs on the last
valid token alone) over tokens that went through the layers before it
(``prefill.body_tokens``), in the window. About 100 / (mean prompt length)
where the split holds, 100 where every layer runs on every token. A program
without these counters gives nothing."""


def read(run):
    body = run["counters"].get("prefill.body_tokens")
    tail = run["counters"].get("prefill.tail_tokens")
    if not body or tail is None:
        return None
    return 100.0 * tail / body
