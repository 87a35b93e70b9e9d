"""Per-job span trees, exportable as Chrome trace-event JSON.

`utils/profiling.phase(...)` is span-aware: while a trace is active on
the current thread, every `phase` becomes a child span of the enclosing
one, so the existing instrumentation in `plonk/prover.py`,
`ProverState.prove_*` and `run_proof_method` yields a full tree per job
with ZERO changes at the call sites. The JobQueue worker opens the
trace (`trace(job_id)`) around the runner call; prove runs on that
worker thread, so propagation is implicit (thread-local).

Finished traces land in a bounded in-memory ring (SPECTRE_TRACE_KEEP,
default 128) served by the `getTrace` RPC, and — when SPECTRE_TRACE_DIR
is set — in `<dir>/<trace_id>.trace.json` files in Chrome trace-event
format (load via chrome://tracing or https://ui.perfetto.dev). The file
sink is best-effort: a full disk never fails a prove.

Below the phases, the device boundary (ISSUE 29): every `TpuBackend`
operation and the device quotient open one span for the call and, inside
it, child spans whose LAST name segment says what the host is doing:

    .../encode    host work before the device has anything of the call:
                  limb split, stacking, padding, the upload (`bytes` up)
    .../dispatch  calls that enqueue device programs and return
    .../wait      the call's blocking read: the host waits for the device
                  and copies the result (`bytes` down)
    .../decode    host work after the last read

A call is IN FLIGHT from the start of a `dispatch` to the end of the
`wait` that follows it; `summary` counts the spans and sums their bytes
for the manifest (`span_counts`, `transfer_bytes`).

`span(...)` also writes a `jax.profiler.TraceAnnotation` of the same name,
with or without a trace on the thread, so that a profiler session holds
the program's stages beside the device's events on one clock. jax is
looked up in `sys.modules`, never imported from here: a process that has
not imported it has no profiler session to write to.

No trace active and no jax => `span(...)` is a no-op; with jax and no
profiler session the annotation adds a fraction of a microsecond to the
two a span costs anyway (some 3,300 spans a served committee proof).
"""

from __future__ import annotations

import collections
import contextlib
import json
import os
import sys
import threading
import time

TRACE_DIR_ENV = "SPECTRE_TRACE_DIR"          # file sink (off when unset)
TRACE_KEEP_ENV = "SPECTRE_TRACE_KEEP"        # in-memory ring size
TRACE_KEEP_DEFAULT = 128

# the last name segment of a device-boundary stage span (module docstring)
ENCODE, DISPATCH, WAIT, DECODE = "encode", "dispatch", "wait", "decode"


class Span:
    __slots__ = ("name", "t0", "t1", "children", "meta")

    def __init__(self, name: str, t0: float):
        self.name = name
        self.t0 = t0                 # perf_counter timestamps
        self.t1: float | None = None
        self.children: list[Span] = []
        # allocated on first use: a proof has some 3,300 spans, the ring
        # keeps 128 proofs, and most of them carry no metadata
        self.meta: dict | None = None

    def seconds(self) -> float | None:
        return None if self.t1 is None else self.t1 - self.t0


class Trace:
    """One span tree; trace id = job id (or a caller's own label)."""

    def __init__(self, trace_id: str):
        self.trace_id = trace_id
        self.started_at = time.time()         # wall anchor for export
        self.perf0 = time.perf_counter()
        self.root = Span("job", self.perf0)
        self.finished_at: float | None = None

    def finish(self):
        if self.root.t1 is None:
            self.root.t1 = time.perf_counter()
        self.finished_at = time.time()


class _Local(threading.local):
    def __init__(self):
        self.trace: Trace | None = None
        self.stack: list[Span] = []


_local = _Local()
_LOCK = threading.Lock()
# finished traces, oldest-first (OrderedDict as a bounded ring)
_RECENT: "collections.OrderedDict[str, Trace]" = collections.OrderedDict()


def _keep() -> int:
    try:
        return max(1, int(os.environ.get(TRACE_KEEP_ENV,
                                         TRACE_KEEP_DEFAULT)))
    except ValueError:
        return TRACE_KEEP_DEFAULT


@contextlib.contextmanager
def trace(trace_id: str):
    """Open a trace on the current thread; on exit it is finished,
    registered for `getTrace`, and (optionally) written to the file
    sink. Nesting restores the previous trace."""
    prev_trace, prev_stack = _local.trace, _local.stack
    tr = Trace(trace_id)
    _local.trace, _local.stack = tr, [tr.root]
    try:
        yield tr
    finally:
        _local.trace, _local.stack = prev_trace, prev_stack
        tr.finish()
        _register(tr)
        _file_sink(tr)


def active() -> Trace | None:
    return _local.trace


_annotation = None      # jax.profiler.TraceAnnotation, once jax is loaded


def _trace_annotation(name: str):
    global _annotation
    if _annotation is None:
        jax = sys.modules.get("jax")
        if jax is None:
            return None
        _annotation = jax.profiler.TraceAnnotation
    return _annotation(name)


@contextlib.contextmanager
def span(name: str, **meta):
    """Child span of the innermost open span (yields it; None without a
    trace), and an annotation of the same name on the profiler's clock.
    `meta` lands in the span's metadata (Chrome `args`)."""
    ann = _trace_annotation(name)
    tr = _local.trace
    if tr is None:
        if ann is None:
            yield None
        else:
            with ann:
                yield None
        return
    s = Span(name, time.perf_counter())
    if meta:
        s.meta = meta
    _local.stack[-1].children.append(s)
    _local.stack.append(s)
    if ann is not None:
        ann.__enter__()
    try:
        yield s
    finally:
        if ann is not None:
            ann.__exit__(None, None, None)
        s.t1 = time.perf_counter()
        if _local.stack and _local.stack[-1] is s:
            _local.stack.pop()


def current_span_name() -> str | None:
    """Name of the innermost open span on this thread, or None when no
    trace is active. The compile-telemetry listener uses this to label
    `spectre_compile_seconds{fn=}` with the phase that triggered the
    compile (e.g. `prove/commit_advice`)."""
    tr = _local.trace
    if tr is None or not _local.stack:
        return None
    return _local.stack[-1].name


def add_completed_span(name: str, seconds: float, **meta):
    """Append an already-finished child span (ending now) under the
    innermost open span; no-op without a trace. This is how events timed
    elsewhere — XLA compile durations reported by `jax.monitoring` —
    land in the tree as `compile/*` children of the phase that was open
    while they ran."""
    tr = _local.trace
    if tr is None or not _local.stack:
        return None
    t1 = time.perf_counter()
    s = Span(name, t1 - max(0.0, float(seconds)))
    s.t1 = t1
    if meta:
        s.meta = meta
    _local.stack[-1].children.append(s)
    return s


def annotate(**kw):
    """Attach key/values to the innermost open span (exported as Chrome
    `args`) — e.g. the CPU-fallback path stamps its oom/compile kind."""
    tr = _local.trace
    if tr is not None and _local.stack:
        top = _local.stack[-1]
        if top.meta is None:
            top.meta = {}
        top.meta.update(kw)


def get_trace(trace_id: str) -> Trace | None:
    with _LOCK:
        return _RECENT.get(trace_id)


def _register(tr: Trace):
    with _LOCK:
        _RECENT[tr.trace_id] = tr          # re-prove overwrites: last wins
        _RECENT.move_to_end(tr.trace_id)
        keep = _keep()
        while len(_RECENT) > keep:
            _RECENT.popitem(last=False)


def _file_sink(tr: Trace):
    d = os.environ.get(TRACE_DIR_ENV)
    if not d:
        return
    try:
        os.makedirs(d, exist_ok=True)
        path = os.path.join(d, f"{tr.trace_id}.trace.json")
        with open(path, "w") as f:
            json.dump(chrome_trace(tr), f)
    except OSError:
        pass                               # the sink never fails a prove


def chrome_trace(tr: Trace) -> dict:
    """Chrome trace-event JSON (the `traceEvents` object form): one "X"
    (complete) event per span, timestamps in microseconds anchored to
    the trace's wall-clock start."""
    pid = os.getpid()
    events = []

    def emit(s: Span):
        t1 = s.t1 if s.t1 is not None else s.t0
        events.append({
            "name": s.name, "ph": "X", "cat": "prove",
            "ts": round((tr.started_at + (s.t0 - tr.perf0)) * 1e6, 3),
            "dur": round((t1 - s.t0) * 1e6, 3),
            "pid": pid, "tid": 0,
            **({"args": dict(s.meta)} if s.meta else {}),
        })
        for c in s.children:
            emit(c)

    emit(tr.root)
    return {"traceEvents": events, "displayTimeUnit": "ms",
            "otherData": {"trace_id": tr.trace_id}}


def summary(tr: Trace | None) -> dict:
    """One walk of the tree (root excluded; no trace, nothing) for the
    manifest:
    `phase_seconds`, total seconds per span name;
    `span_counts`, spans per name; `transfer_bytes`, the `bytes` metadata
    summed over the `encode` spans (`h2d`) and the `wait` spans (`d2h`);
    `msm_columns`, over the spans that carry a `batch` and a `width` (a
    run of the one-device MSM path, plonk/backend.py `_msm_chunks`): the
    columns committed (`real`) and the identity columns that filled the
    runs up to their width (`padded`); `msm_window`, the Pippenger windows
    `c` those runs carried (one, on a prove of one size of commitment);
    `msm_window_passes`, their `active` summed: the window passes the
    columns ran, ceil(254 / c) a column of full-width scalars and as many
    as its largest value reaches for a column committed as values."""
    seconds: dict[str, float] = {}
    counts: dict[str, int] = {}
    moved = {"h2d": 0, "d2h": 0}
    columns = {"real": 0, "padded": 0}
    windows = set()
    passes = 0
    way = {ENCODE: "h2d", WAIT: "d2h"}

    def walk(s: Span):
        nonlocal passes
        for c in s.children:
            counts[c.name] = counts.get(c.name, 0) + 1
            if c.t1 is not None:
                seconds[c.name] = seconds.get(c.name, 0.0) + (c.t1 - c.t0)
            if c.meta and "bytes" in c.meta:
                direction = way.get(c.name.rsplit("/", 1)[-1])
                if direction:
                    moved[direction] += int(c.meta["bytes"])
            if c.meta and "width" in c.meta:
                columns["real"] += int(c.meta["batch"])
                columns["padded"] += int(c.meta["width"] - c.meta["batch"])
                windows.add(int(c.meta["c"]))
                passes += int(c.meta["active"])
            walk(c)

    if tr is not None:
        walk(tr.root)
    return {"phase_seconds": {k: round(v, 6)
                              for k, v in sorted(seconds.items())},
            "span_counts": dict(sorted(counts.items())),
            "transfer_bytes": moved,
            "msm_columns": columns,
            "msm_window": sorted(windows),
            "msm_window_passes": passes}


def phase_seconds(tr: Trace) -> dict[str, float]:
    return summary(tr)["phase_seconds"]


def reset():
    """Test hook: drop all retained traces."""
    with _LOCK:
        _RECENT.clear()
