"""KV arena: bytes of paged pool a token of context holds, over every layer
that OWNS a pool (``kv_bytes_per_token`` of the run's program facts: the
arena's bytes over its tokens). A layer that reads another layer's pool, or
keeps a fixed window per lane, adds nothing here."""


def read(run):
    return run["program"].get("kv_bytes_per_token")
