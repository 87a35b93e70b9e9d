"""Layer: backend. Innermost calls per proof to the backend's operations of
kinds msm and ntt (the ones that reach the device), from the wrapper of the
traced run. The run prints every operation and shape on an earlier line."""
from harness import readers


def read(ctx):
    return readers.calls_per_proof(ctx)
