"""Arithmetic the metric files share. A reader takes the run's context
(`ctx`, see run.py) and returns a number, or None when it finds nothing to
read; it never returns 0 for a share of a roofline or of a peak."""

from __future__ import annotations


def served(ctx) -> list:
    return [s for s in ctx["window"].sent
            if s.error is None and s.manifest is not None]


def mean(values):
    values = [v for v in values if v is not None]
    return sum(values) / len(values) if values else None


def phase_mean(ctx, phase: str):
    """Mean seconds per served proof of one manifest phase."""
    return mean([s.manifest["phase_seconds"].get(phase) for s in served(ctx)])


DEVICE_KINDS = ("msm", "ntt")    # `tracing.kind_of`; "other" is host arithmetic


def calls_per_proof(ctx, kinds=DEVICE_KINDS):
    """Innermost backend calls of these kinds per served proof, from the
    wrapper of the traced run."""
    calls, n = ctx.get("backend_calls"), len(served(ctx))
    if not calls or not n:
        return None
    return sum(1 for c in calls if c.kind in kinds) / n


def kind_host_seconds(ctx, kind: str):
    """Host-clock seconds per proof inside innermost calls of one kind."""
    calls, n = ctx.get("backend_calls"), len(served(ctx))
    if not calls or not n:
        return None
    total = sum(c.t1 - c.t0 for c in calls if c.kind == kind)
    return total / n if total else None


def hbm_peak_gb(ctx):
    peak = ctx.get("memory_peak_bytes")
    return peak / 1e9 if peak else None
