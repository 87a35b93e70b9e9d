"""Layer: witness. Span `job/witness` per proof: context build and
assignment, where the program does them (`witness_s` is a subtraction)."""
from harness import spans


def read(ctx):
    return spans.named_seconds(ctx, "job/witness")
