"""Layer: kernels. Span `quotient/wait` per proof: the quotient's one
blocking read, in which the device drains the queue of LDEs and field
operations while the host waits. Host clock, not device seconds."""
from harness import spans


def read(ctx):
    return spans.named_seconds(ctx, "quotient/wait")
