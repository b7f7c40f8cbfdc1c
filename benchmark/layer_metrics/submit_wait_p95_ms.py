"""Gateway: 95th percentile of (the 200 of ``POST /v1/stream`` - sent), at
the client: accept, routing and ``ServingAPI.submit``, which waits for the
lock that the pump loop holds through every step."""
from benchmark.harness.readers import client_ms


def read(run):
    return client_ms(run, "accept_s", 95.0)
