"""The `breakdown` of a traced run. `device_ops`: the device programs of
the shapes that were traced whole, by seconds a call x the calls in the
window; a shape that was not traced (today the 2^14 MSM, 57 % of the
proof) is NOT in the list, since nothing stands in for device seconds.
`idle_gaps`: the window's seconds outside every backend call of a kind that
reaches the device, by the program's span the host was in. Span boundaries
come from the jobs' span trees and the calls from the wrapper, both by the
host's wall clock."""

from __future__ import annotations

from . import readers, trace_reduce

TOP = 10


def phases(ctx) -> dict:
    """Mean seconds per proof of every manifest phase (those that are not
    metrics yet are printed from here)."""
    out: dict = {}
    srv = readers.served(ctx)
    for s in srv:
        for k, v in s.manifest["phase_seconds"].items():
            out[k] = out.get(k, 0.0) + v / len(srv)
    return out


def spans_of(sent: list) -> list:
    """(wall t0, wall t1, name) of the jobs' span trees (Chrome trace
    events, microseconds of wall clock)."""
    return [(e["ts"] / 1e6, (e["ts"] + e["dur"]) / 1e6, e["name"])
            for s in sent for e in (s.spans or []) if e.get("ph") == "X"]


def host_only(calls: list, spans: list, lo: float, hi: float) -> dict:
    """Seconds of [lo, hi] outside every call of `calls`, by the shortest
    span that holds them ("outside every span" where none does)."""
    out: dict = {}
    cuts = sorted({t for t0, t1, _ in spans for t in (t0, t1)})
    for g0, g1 in trace_reduce.gaps([(c.t0, c.t1) for c in calls], lo, hi):
        edges = [g0] + [t for t in cuts if g0 < t < g1] + [g1]
        for a, b in zip(edges, edges[1:]):
            mid = (a + b) / 2
            holding = [(t1 - t0, name) for t0, t1, name in spans
                       if t0 <= mid < t1]
            name = min(holding)[1] if holding else "outside every span"
            out[name] = out.get(name, 0.0) + (b - a)
    return out


def idle_gaps(ctx) -> list:
    est, calls, w = ctx.get("trace"), ctx.get("backend_calls"), ctx["window"]
    if not est or calls is None:
        return []
    device_calls = [c for c in calls if c.kind in readers.DEVICE_KINDS]
    out = host_only(device_calls, spans_of(w.sent), w.t_first, w.t_last)
    # inside a traced shape's calls the host converts limbs, transfers and
    # decodes; what an untraced shape's calls hold is not known
    for kind in est["kinds"]:
        inside = sum(s["host_s"] - s["device_s"] for s in est["shapes"]
                     if s["kind"] == kind and s["device_s"])
        if inside > 0:
            out[f"backend/{kind} host side (traced shapes)"] = inside
    return _top(out)


def _top(seconds: dict) -> list:
    return sorted(([k, v] for k, v in seconds.items()),
                  key=lambda kv: -kv[1])[:TOP]


def build(ctx) -> dict:
    est = ctx.get("trace")
    return {"device_ops": _top(est["by_program"]) if est else [],
            "idle_gaps": idle_gaps(ctx)}
