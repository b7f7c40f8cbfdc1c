"""Device: ``memory_stats()["peak_bytes_in_use"]`` when the window closed,
on the fullest chip, in GB (1e9 bytes)."""
from benchmark.harness.readers import peak_hbm_gb


def read(run):
    return peak_hbm_gb(run)
