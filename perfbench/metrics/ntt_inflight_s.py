"""Layer: backend. Host-clock seconds per proof in which a backend call of
an NTT kind (`ntt`, `intt`, `*_many`, `coset_lde_many`) was in flight, by
the program's own spans; a call that crosses the boundary twice has two
such stretches (harness/spans.py). Never a device metric."""
from harness import spans


def read(ctx):
    return spans.inflight_seconds(ctx, spans.NTT_WORDS)
