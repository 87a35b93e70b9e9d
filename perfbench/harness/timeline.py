"""The program's own device timeline (ISSUE 40; the program's
`observability/tracing.py`), for the metric files that read it. Every site
that enqueues device work marks it; a watcher thread stamps when each
mark's outputs are ready, on the clock of the spans; the device runs one
queue in order, so each mark is a stretch of device time. A job's manifest
sums its stretches (`device`: `busy_s`, `idle_s`, `by_kind`, ...), and its
`getTrace` holds them as a `device` track of "B"/"E" events, which no
reader of the spans (`spans.events`, `breakdown.spans_of`: "X" alone) sees.

A program without the timeline gives every reader here nothing to read:
it returns None and does not raise."""

from __future__ import annotations

from . import readers
from .spans import clip, union_s

DEVICE_CAT = "device"


def device_block(sent) -> dict | None:
    return (sent.manifest or {}).get("device")


def per_proof(ctx, fn):
    """Mean over the served proofs that carry a `device` block of
    `fn(block)`; None where none does."""
    return readers.mean([fn(b) for b in map(device_block, readers.served(ctx))
                         if b is not None])


def kind_seconds(ctx, kind: str, reading: str = "by_kind"):
    """Device seconds a proof of one kind (`msm`, `ntt`, `quotient`), by
    the timeline's upper reading (`by_kind`: each stretch from its first
    enqueue) or its lower (`by_kind_lower`: from its last)."""
    return per_proof(ctx, lambda b: b[reading].get(kind, 0.0))


def idle_seconds(ctx):
    """A job's root span seconds outside every stretch of its own."""
    return per_proof(ctx, lambda b: b["idle_s"])


def stretches(events) -> list:
    """(t0, t1) in seconds of wall clock of the "B"/"E" pairs of the device
    track in one job's Chrome trace events."""
    out, open_at = [], {}
    for e in events or []:
        if e.get("cat") != DEVICE_CAT:
            continue
        key = (e.get("pid"), e.get("tid"))
        if e.get("ph") == "B":
            open_at[key] = e["ts"] / 1e6
        elif e.get("ph") == "E" and key in open_at:
            out.append((open_at.pop(key), e["ts"] / 1e6))
    return out


def window_idle_s(ctx):
    """Seconds of the window, a proof served, outside the device stretches
    of every served job, merged on the wall clock the jobs share: the
    construction of `spans.no_inflight_s` with the device's own stretches
    where that takes the host's in-flight stretches."""
    w, srv = ctx["window"], readers.served(ctx)
    busy = [(a - w.t_first, b - w.t_first)
            for s in srv for a, b in stretches(s.spans)]
    if not busy:
        return None
    return (w.wall_s - union_s(clip(busy, 0.0, w.wall_s))) / len(srv)
