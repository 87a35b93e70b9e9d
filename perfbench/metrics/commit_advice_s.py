"""Layer: prover phases. Manifest phase prove/commit_advice, mean per proof."""
from harness import readers


def read(ctx):
    return readers.phase_mean(ctx, "prove/commit_advice")
