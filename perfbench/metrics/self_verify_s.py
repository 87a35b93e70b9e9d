"""Layer: host verifier. Manifest phase prove/self_verify, mean per proof."""
from harness import readers


def read(ctx):
    return readers.phase_mean(ctx, "prove/self_verify")
