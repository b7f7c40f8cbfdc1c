"""KV arena: bytes of the slot-indexed store that holds the recurrent
layers' state beside the paged pools (the program's gauge
``state.bytes_total``, set when the engine is built), in GB (1e9 bytes). A
program without such a store has no such gauge and gives nothing."""


def read(run):
    try:
        from paddle_tpu.serving import metrics
    except ImportError:
        return None
    value = metrics.gauges().get("state.bytes_total")
    return None if value is None else value / 1e9
