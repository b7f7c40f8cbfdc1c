"""Load generator: 95th percentile of (sent - due), its own clock. A
starved generator must not be read as a fast server."""
from benchmark.harness.readers import client_ms


def read(run):
    return client_ms(run, "late_s", 95.0)
