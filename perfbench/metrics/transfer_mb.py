"""Layer: backend. MB per proof across the device boundary, up and down:
the manifest's `transfer_bytes`, summed from the `bytes` of the program's
`encode` and `wait` spans."""
from harness import spans


def read(ctx):
    return spans.transfer_mb(ctx)
