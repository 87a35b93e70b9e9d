"""Layer: witness. Manifest prove_s minus the sum of the prover's own
phases (prove/*) and the preprocess span, mean per proof: witness build and
assignment, pure host Python outside every prove/* span."""
from harness import readers


def read(ctx):
    vals = []
    for s in readers.served(ctx):
        ph = s.manifest["phase_seconds"]
        inside = sum(v for k, v in ph.items() if k.startswith("prove/"))
        vals.append(s.manifest["prove_s"] - inside)
    return readers.mean(vals)
