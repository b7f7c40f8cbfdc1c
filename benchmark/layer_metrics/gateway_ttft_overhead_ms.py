"""Gateway: the client's median first-token time minus the median of the
scheduler's own ``latency.ttft`` over the window: HTTP, routing and the
pump, good to one histogram bucket (25%)."""
from benchmark.harness.readers import client_ms, hist_ms


def read(run):
    client, inside = client_ms(run, "ttft_s", 50.0), hist_ms(
        run, "latency.ttft", 50.0)
    return None if client is None or inside is None else client - inside
