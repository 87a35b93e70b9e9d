"""Layer: device. memory_stats() peak_bytes_in_use of the fullest chip."""
from harness import readers


def read(ctx):
    return readers.hbm_peak_gb(ctx)
