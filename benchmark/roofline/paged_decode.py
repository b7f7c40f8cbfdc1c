"""The least the paged decode kernel must do in one decode step: read the
live context's K and V once (bytes; every layer's, which is what
``kv_bytes_per_token`` counts). One query row a lane makes the operations
(4 per head-dim element of live K/V) a small fraction of what the bytes
cost, so the bytes bound it."""


def least_seconds(kv_bytes_per_token: float, live_tokens: float,
                  peaks: dict) -> dict:
    b = kv_bytes_per_token * live_tokens
    return {"seconds": b / peaks["hbm_bytes_per_s"], "bound": "memory",
            "bytes": b}
