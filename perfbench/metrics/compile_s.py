"""Layer: prover state. The warm-up request's manifest compile.seconds."""


def read(ctx):
    return ctx["warmup"].manifest["compile"]["seconds"]
