"""Layer: backend. Host-clock seconds per proof inside backend calls of kind
msm (limb conversion, transfer, kernel, decode), from the traced run's
wrapper."""
from harness import readers


def read(ctx):
    return readers.kind_host_seconds(ctx, "msm")
