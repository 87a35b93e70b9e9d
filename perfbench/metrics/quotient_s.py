"""Layer: prover phases. Manifest phase prove/quotient, mean per proof."""
from harness import readers


def read(ctx):
    return readers.phase_mean(ctx, "prove/quotient")
