"""Arithmetic on the program's own spans, for the metric files that read
them. The program opens, below its phases, one span for every backend call
that reaches the device (`backend/msm`, `backend/ntt`, ...) and for the
device quotient (`quotient/*`), and inside each the stages it is in, named
by their last segment: `encode` (host work and the upload), `dispatch` (the
calls that enqueue device programs and return), `wait` (the blocking
read), `decode` (host work after it). From those alone:

- a call is IN FLIGHT from the start of a `dispatch` to the end of the
  `wait` that follows it (several dispatches before one wait are one
  stretch; a call that crosses twice has two). The device has work of the
  job exactly then, as far as the host can know: host-clock seconds, an
  upper bound on device seconds and never a device metric;
- HOST-ONLY is the job's root span outside every in-flight stretch: the
  device had nothing of this job, a lower bound on its idle time;
- a span's SELF time is the span minus what the spans inside it cover.

A job's spans are the Chrome trace events `getTrace` serves (`Sent.spans`:
name, `ts` and `dur` in microseconds of wall clock, parents before their
children). A program that has no such spans gives every reader here
nothing to read: it returns None and does not raise.

The same stages are written into the profiler's trace as annotations of
the same names, so in the replay session (harness/tracing.py) the device's
module events can be set against the in-flight stretches of the replayed
calls on one clock: `inflight_device_pct`. `trace_reduce.load_xplane` keeps
no host annotation but the benchmark's own, so `load_rows` reads the file
again."""

from __future__ import annotations

import glob
import os

from . import readers, trace_reduce, tracing, workdir
from .cells import BENCH_DIR

DISPATCH, WAIT = "dispatch", "wait"
HOST_STAGES = ("encode", "decode")
# a call's kind by what its operation's name holds, as the wrapper sorts them
MSM_WORDS, NTT_WORDS = (dict(tracing.KIND_WORDS)[k] for k in ("msm", "ntt"))


def events(sent) -> list:
    """(name, t0, t1) of one request's spans in seconds since the first
    began (wall-clock microseconds leave a double no digits to spare), in
    the order the program gave them (parents first)."""
    evs = [e for e in (sent.spans or []) if e.get("ph") == "X"]
    base = evs[0]["ts"] if evs else 0
    return [(e["name"], (e["ts"] - base) / 1e6,
             (e["ts"] - base + e["dur"]) / 1e6) for e in evs]


def stage(name: str) -> str:
    return name.rsplit("/", 1)[-1]


def call_of(name: str) -> str:
    """`backend/msm/wait` -> `backend/msm`; `quotient/extend/dispatch` ->
    `quotient/extend`."""
    return name.rsplit("/", 1)[0]


def is_kind(name: str, words) -> bool:
    """Whether a span belongs to a backend call whose operation's name
    holds one of `words` (`backend/intt_many/wait` is of kind ntt)."""
    parts = name.split("/")
    return len(parts) >= 2 and parts[0] == "backend" \
        and any(w in parts[1] for w in words)


def in_flight(evs: list) -> list:
    """(t0, t1, call) for each stretch from the start of a `dispatch` to
    the end of the `wait` that follows it, `call` being the wait's. A wait
    with no dispatch before it counts from its own start."""
    out = []
    start = None
    for name, t0, t1 in sorted(evs, key=lambda e: e[1]):
        st = stage(name)
        if st == DISPATCH and start is None:
            start = t0
        elif st == WAIT:
            out.append((t0 if start is None else start, t1, call_of(name)))
            start = None
    return out


def merged(intervals) -> list:
    """The same stretches as disjoint (start, end) pairs, in order."""
    out: list = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def union_s(intervals) -> float:
    return sum(e - s for s, e in merged(intervals))


def clip(intervals, lo: float, hi: float) -> list:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def self_s(evs: list, name: str, covered_by) -> float | None:
    """Seconds of the spans called `name` that no span for which
    `covered_by(name)` holds covers. None where there is no such span or
    nothing that could cover any."""
    cover = [(t0, t1) for n, t0, t1 in evs if covered_by(n)]
    own = [(t0, t1) for n, t0, t1 in evs if n == name]
    if not own or not cover:
        return None
    return sum((t1 - t0) - union_s(clip(cover, t0, t1)) for t0, t1 in own)


def host_only_s(evs: list) -> float | None:
    """The root span's seconds outside every in-flight stretch."""
    flights = in_flight(evs)
    if not evs or not flights:
        return None
    _, lo, hi = evs[0]               # the root comes first
    return (hi - lo) - union_s(clip([(a, b) for a, b, _ in flights], lo, hi))


def unnamed(evs: list) -> list:
    """(seconds, name) of the longest stretch that no child covers, for
    every span that has children (a leaf names all of its time itself),
    longest first: where the next span should go. The spans inside a span
    are those that follow it in the list while they begin before it ends
    (parents come first)."""
    out = []
    for i, (name, t0, t1) in enumerate(evs):
        kids = []
        for _, a, b in evs[i + 1:]:
            if a >= t1:
                break
            kids.append((a, b))
        if kids:
            out.append((max((b - a for a, b in
                             trace_reduce.gaps(kids, t0, t1)), default=0.0),
                        name))
    return sorted(out, reverse=True)


# -- per proof, over the served requests of a run (`ctx`, see run.py) -------

def per_proof(ctx, fn):
    """Mean over the served proofs of `fn(events)`; None where it is None
    for every one."""
    return readers.mean([fn(events(s)) for s in readers.served(ctx)])


def inflight_seconds(ctx, words):
    def one(evs):
        got = [(a, b) for a, b, call in in_flight(evs)
               if is_kind(call, words)]
        return union_s(got) if got else None
    return per_proof(ctx, one)


def host_stage_seconds(ctx, words):
    def one(evs):
        got = [t1 - t0 for n, t0, t1 in evs
               if stage(n) in HOST_STAGES and is_kind(n, words)]
        return sum(got) if got else None
    return per_proof(ctx, one)


def named_seconds(ctx, name: str):
    def one(evs):
        got = [t1 - t0 for n, t0, t1 in evs if n == name]
        return sum(got) if got else None
    return per_proof(ctx, one)


def no_inflight_s(ctx):
    """Seconds of the window in which NO served job had a call in flight,
    over the proofs served: `host_only_s` taken across the jobs of a
    window, on the wall clock their spans share. A job's own seconds hold
    its waits for a neighbour; the window's do not, and they divide as
    `prove_s` does."""
    w, srv = ctx["window"], readers.served(ctx)
    flights = [(a, b) for s in srv for a, b, _ in in_flight(
        [(e["name"], e["ts"] / 1e6 - w.t_first,
          (e["ts"] + e["dur"]) / 1e6 - w.t_first)
         for e in (s.spans or []) if e.get("ph") == "X"])]
    if not flights:
        return None
    return (w.wall_s - union_s(clip(flights, 0.0, w.wall_s))) / len(srv)


def transfer_mb(ctx):
    """Bytes a proof across the device boundary, both ways, from the
    manifest's `transfer_bytes` (the spans' `bytes`), in MB."""
    return readers.mean([
        sum(s.manifest["transfer_bytes"].values()) / 1e6
        for s in readers.served(ctx) if "transfer_bytes" in s.manifest])


# -- the replay session, on the profiler's clock ----------------------------

def trace_dir(ctx) -> str:
    """Where the run's profiler session wrote (`workdir.prepare` made it;
    a reader never calls that: it empties the directory)."""
    return os.path.join(BENCH_DIR, "work", f"{ctx['config']['name']}-"
                        f"{workdir.host_fingerprint()}", "trace")


def load_rows(path: str) -> list:
    """(plane, line, name, start_ns, duration_ns) of the device planes'
    module events and of the program's stage annotations on the host."""
    from jax.profiler import ProfileData
    rows = []
    for plane in ProfileData.from_file(path).planes:
        dev = trace_reduce.DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if dev and line.name not in trace_reduce.MODULE_LINES:
                continue
            for ev in line.events:
                if dev or (ev.name.startswith("backend/")
                           and stage(ev.name) in (DISPATCH, WAIT)):
                    rows.append((plane.name, line.name, ev.name,
                                 int(ev.start_ns), int(ev.duration_ns)))
    return rows


def inflight_device_pct(rows: list, words) -> float | None:
    """Of the seconds the replayed calls of this kind were in flight (their
    annotations, a host thread's line at a time), the share in which a
    program ran on the device (module events cut to those stretches, a
    mean over the chips). At most 100 by construction. None where no such
    call was replayed or no device plane is in the trace."""
    lines: dict = {}
    devices: dict = {}
    for plane, line, name, start, dur in rows:
        if trace_reduce.DEVICE_PLANE.match(plane):
            devices.setdefault(plane, []).append((start, start + dur))
        elif is_kind(name, words):
            lines.setdefault((plane, line), []).append(
                (name, start, start + dur))
    flights = merged((a, b) for evs in lines.values()
                     for a, b, _ in in_flight(evs))
    total = union_s(flights)
    if not devices or not total:
        return None
    busy = [sum(union_s(clip(ran, a, b)) for a, b in flights)
            for ran in devices.values()]
    return 100.0 * sum(busy) / len(busy) / total


def replay_inflight_device_pct(ctx, words):
    if not ctx.get("trace"):          # a `--trace 0` run has no session
        return None
    pbs = sorted(glob.glob(os.path.join(trace_dir(ctx), "**", "*.xplane.pb"),
                           recursive=True))
    return inflight_device_pct(load_rows(pbs[-1]), words) if pbs else None
