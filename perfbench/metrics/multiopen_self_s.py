"""Layer: prover phases. Span `prove/multiopen` per proof minus what the
backend's calls inside it cover: the phase's own host arithmetic."""
from harness import spans


def read(ctx):
    return spans.per_proof(ctx, lambda evs: spans.self_s(
        evs, "prove/multiopen", lambda n: n.startswith("backend/")))
