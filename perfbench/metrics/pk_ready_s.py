"""Layer: prover state. Span state/create_pk_* of the warm-up request:
keygen on a checkout's first run, a load of the pickle after."""


def read(ctx):
    ph = ctx["warmup"].manifest["phase_seconds"]
    vals = [v for k, v in ph.items() if k.startswith("state/create_pk_")]
    return sum(vals) if vals else None
