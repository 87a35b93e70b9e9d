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

The device timeline (ISSUE 40). The spans time the host; in flight is an
upper bound on device time and host-only a lower bound on idle time. Every
site that enqueues device work calls `on_device(outputs)` once its programs
are enqueued (inside the `.../dispatch` span, whose start is the mark's
`enq_first`; sites without one pass `kind` and `since`), and every
`.../encode` that puts host data on the device calls `on_upload`, so that
a program's stretch starts once its input has arrived and the wait for
the transfer is the upload's. A mark goes onto one FIFO a process, in push
order across job threads, and one watcher thread takes them in turn,
blocks until the outputs are ready and stamps `done` on the spans' clock.
The device runs one queue in order, so mark i kept the device busy over
`[max(enq_first, prev_done), done]` (upper) and at least
`[max(enq_last, prev_done), done]` (lower). Where two jobs' enqueues
interleave, a stretch also holds the other job's programs ahead of its own
(the union stays right, the split by job does not): `interleaved` counts
such marks. A stamp is late by the runtime's notice that the programs are
done (0.35-4.3 ms on the chip, median 0.63:
scripts/calibrate_device_timeline.py), more while a job thread holds the
interpreter lock. No trace, no mark, no thread; the watcher exits once the
FIFO has stayed empty `WATCH_IDLE_S`. `summary` adds the job's `device`
block, `chrome_trace` a `device` track of "B"/"E" events (never "X": the
benchmark's readers keep "X" alone).
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

# a mark's kind -> the manifest's `device.by_kind`, by the words of
# perfbench/harness/tracing.py:KIND_WORDS; `quotient/*` is the quotient's,
# `.../upload` (`on_upload`) a transfer's
UPLOAD = "upload"
DEVICE_KINDS = (("msm", ("msm",)), ("ntt", ("ntt", "lde")))
WATCH_IDLE_S = 2.0     # the watcher exits after the FIFO is empty this long
IDLE_TOP = 10          # `device.idle_by_span` keeps the longest this many


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
        # the device timeline: (kind, enq_first, enq_last, lo, lo_lower,
        # done) a mark, in stamp order; filled by the watcher
        self.device: list[tuple] = []
        self.pending = 0              # marks pushed and not stamped yet
        self.interleaved = 0          # marks whose enqueues met another job's

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
        # the job's last read has returned, so its marks are done on the
        # device: only their stamps are left
        _WATCHER.drain(tr)
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


# -- the device timeline ------------------------------------------------------

def on_device(outputs, kind: str | None = None,
              since: float | None = None) -> float | None:
    """Mark device work enqueued: `outputs` are the arrays (any pytree) the
    enqueued programs write. Called inside a `.../dispatch` span once its
    programs are enqueued, the kind is that span's call (`backend/msm_many`
    of `backend/msm_many/dispatch`) and `enq_first` the span's start; a site
    without a dispatch span names both (`kind`, `since`). Returns now, the
    mark's `enq_last` (None without a trace, where it does nothing)."""
    tr = _local.trace
    if tr is None or not _local.stack:
        return None
    now = time.perf_counter()
    top = _local.stack[-1]
    _WATCHER.push(_Mark(tr, kind or top.name.rsplit("/", 1)[0],
                        top.t0 if since is None else since, now, outputs))
    return now


def on_upload(arrays, call: str) -> float | None:
    """Mark host data put on the device in `call`'s `encode` (kind
    `<call>/upload`), from now: the host's packing before the put is not
    the device's, the transfer after it is."""
    return on_device(arrays, kind=f"{call}/{UPLOAD}",
                     since=time.perf_counter())


class _Mark:
    __slots__ = ("trace", "kind", "enq_first", "enq_last", "outputs")

    def __init__(self, trace, kind, enq_first, enq_last, outputs):
        self.trace, self.kind = trace, kind
        self.enq_first, self.enq_last = enq_first, enq_last
        self.outputs = outputs


def stretch(enq_first: float, enq_last: float,
            prev_done: float) -> tuple:
    """The start of one mark's device stretch, which ends at its `done`:
    (lo, lo_lower). The device runs its queue in order, so the mark's
    programs ran after the previous mark's were done and after they were
    enqueued: `lo` from the first enqueue (upper reading), `lo_lower` from
    the last. One watcher stamps in push order on a monotonic clock, so
    `done` is never before `prev_done` nor `enq_last`."""
    return max(enq_first, prev_done), max(enq_last, prev_done)


def _block_until_ready(outputs):
    sys.modules["jax"].block_until_ready(outputs)


class _Watcher:
    """The process's one FIFO of marks and the daemon thread that stamps
    them (module docstring). `clock` and `ready` are seams for the tests:
    the spans' clock and the blocking wait."""

    def __init__(self, clock=time.perf_counter, ready=_block_until_ready):
        lock = threading.Lock()
        self._work = threading.Condition(lock)
        self._drained = threading.Condition(lock)
        self._fifo: collections.deque = collections.deque()
        self._thread: threading.Thread | None = None
        self._idle = False
        self._prev: _Mark | None = None   # the last stamped (outputs gone)
        self._prev_done = float("-inf")
        self._clock, self._ready = clock, ready

    def push(self, mark: _Mark):
        with self._work:
            self._fifo.append(mark)
            mark.trace.pending += 1
            if self._thread is None:
                self._thread = threading.Thread(
                    target=self._run, daemon=True,
                    name="spectre-device-timeline")
                self._thread.start()
            elif self._idle:
                self._work.notify()

    def drain(self, tr: Trace, timeout: float = 60.0) -> bool:
        """Wait until every mark of `tr` is stamped (False at `timeout`)."""
        with self._drained:
            return self._drained.wait_for(lambda: tr.pending == 0, timeout)

    def _run(self):
        try:
            while True:
                with self._work:
                    if not self._fifo:
                        self._idle = True
                        self._work.wait(WATCH_IDLE_S)
                        self._idle = False
                        if not self._fifo:
                            self._thread = None   # no leaked thread: a
                            return                # later push starts one
                    mark = self._fifo.popleft()
                try:
                    self._ready(mark.outputs)
                except RuntimeError:      # a deleted or donated buffer:
                    pass                  # its programs ran all the same
                finally:                  # stamped whatever happened, so
                    done = self._clock()  # that no drain waits for it
                    mark.outputs = None
                    self._stamp(mark, done)
        except BaseException:
            with self._work:              # the next push starts another
                self._thread = None
            raise

    def _stamp(self, mark: _Mark, done: float):
        tr = mark.trace
        with self._work:
            prev = self._prev
            lo, lo_lower = stretch(mark.enq_first, mark.enq_last,
                                   self._prev_done)
            if prev is not None and prev.trace is not tr \
                    and mark.enq_first < prev.enq_last:
                # the two jobs' programs may alternate in the device's
                # queue: each stretch may hold some of the other's
                tr.interleaved += 1
                prev.trace.interleaved += 1
            self._prev, self._prev_done = mark, done
            tr.device.append((mark.kind, mark.enq_first, mark.enq_last,
                              lo, lo_lower, done))
            tr.pending -= 1
            if tr.pending == 0:
                self._drained.notify_all()


_WATCHER = _Watcher()


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


DEVICE_TID = 1          # the device track's thread id in `chrome_trace`


def chrome_trace(tr: Trace) -> dict:
    """Chrome trace-event JSON (the `traceEvents` object form): one "X"
    (complete) event per span, timestamps in microseconds anchored to
    the trace's wall-clock start; then, where the job marked device work,
    a thread named `device` (an "M" event) holding each stretch as a
    "B"/"E" pair named for its kind, the upper reading, with the lower
    reading's seconds and `done - enq_last` as the B's args."""
    pid = os.getpid()
    events = []

    def us(t: float) -> float:
        return round((tr.started_at + (t - tr.perf0)) * 1e6, 3)

    def emit(s: Span):
        t1 = s.t1 if s.t1 is not None else s.t0
        events.append({
            "name": s.name, "ph": "X", "cat": "prove",
            "ts": us(s.t0),
            "dur": round((t1 - s.t0) * 1e6, 3),
            "pid": pid, "tid": 0,
            **({"args": dict(s.meta)} if s.meta else {}),
        })
        for c in s.children:
            emit(c)

    emit(tr.root)
    if tr.device:
        events.append({"name": "thread_name", "ph": "M", "pid": pid,
                       "tid": DEVICE_TID, "args": {"name": "device"}})
    for kind, _, enq_last, lo, lo_lower, done in tr.device:
        events.append({"name": kind, "ph": "B", "cat": "device",
                       "ts": us(lo), "pid": pid, "tid": DEVICE_TID,
                       "args": {"lower_s": round(done - lo_lower, 6),
                                "after_enqueue_s": round(done - enq_last,
                                                         6)}})
        events.append({"name": kind, "ph": "E", "cat": "device",
                       "ts": us(done), "pid": pid, "tid": DEVICE_TID})
    return {"traceEvents": events, "displayTimeUnit": "ms",
            "otherData": {"trace_id": tr.trace_id}}


def device_kind(kind: str) -> str:
    """A mark's kind (`backend/intt_many`, `quotient/extend`, ...) as
    `device.by_kind` sums it: upload, msm, ntt, quotient or other."""
    if kind.endswith("/" + UPLOAD):
        return UPLOAD
    if kind.startswith("quotient/"):
        return "quotient"
    for name, words in DEVICE_KINDS:
        if any(w in kind for w in words):
            return name
    return "other"


def _charge(s: Span, a: float, b: float, out: dict):
    """Charge [a, b] (inside `s`) to the innermost spans open over it."""
    cur = a
    for c in sorted(s.children, key=lambda c: c.t0):
        if c.t1 is None or c.t1 <= cur:
            continue
        if c.t0 >= b:
            break
        c0, c1 = max(c.t0, cur), min(c.t1, b)
        if c0 > cur:
            out[s.name] = out.get(s.name, 0.0) + (c0 - cur)
        _charge(c, c0, c1, out)
        cur = c1
    if b > cur:
        out[s.name] = out.get(s.name, 0.0) + (b - cur)


def device_summary(tr: Trace) -> dict | None:
    """The job's `device` block (None where it marked nothing), every
    stretch cut to the job's root span: `busy_s` and `idle_s` by the upper
    reading and `busy_lower_s` by the lower; `by_kind` and `by_kind_lower`;
    `marks`; `interleaved`, the marks whose enqueues met another job's
    (where not 0, `by_kind` and `idle_by_span` are not this job's alone;
    the earlier job of a pair counts it only while its trace is open);
    `idle_by_span`, each idle stretch charged to the innermost span open on
    the job's thread, the longest IDLE_TOP names. The stretches of one
    process never overlap and come in order (`stretch`), so they are
    summed as they are."""
    if not tr.device:
        return None
    lo, hi = tr.root.t0, tr.root.t1 if tr.root.t1 is not None else tr.root.t0

    def cut(a, b):
        return max(0.0, min(b, hi) - max(a, lo))

    upper, lower, idle = {}, {}, {}
    cur = lo
    for kind, _, _, a, a_low, done in tr.device:
        k = device_kind(kind)
        upper[k] = upper.get(k, 0.0) + cut(a, done)
        lower[k] = lower.get(k, 0.0) + cut(a_low, done)
        if min(a, hi) > cur:
            _charge(tr.root, cur, min(a, hi), idle)
        cur = max(cur, min(done, hi))
    if hi > cur:
        _charge(tr.root, cur, hi, idle)
    busy_s = sum(upper.values())

    def r(v):
        return round(v, 6)

    return {"busy_s": r(busy_s), "idle_s": r((hi - lo) - busy_s),
            "busy_lower_s": r(sum(lower.values())),
            "by_kind": {k: r(v) for k, v in sorted(upper.items())},
            "by_kind_lower": {k: r(v) for k, v in sorted(lower.items())},
            "marks": len(tr.device), "interleaved": tr.interleaved,
            "idle_by_span": [[k, r(v)] for k, v in sorted(
                idle.items(), key=lambda kv: -kv[1])[:IDLE_TOP]]}


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
    as its largest value reaches for a column committed as values;
    `device`, where the job marked device work: `device_summary`."""
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
    out = {"phase_seconds": {k: round(v, 6)
                             for k, v in sorted(seconds.items())},
           "span_counts": dict(sorted(counts.items())),
           "transfer_bytes": moved,
           "msm_columns": columns,
           "msm_window": sorted(windows),
           "msm_window_passes": passes}
    device = device_summary(tr) if tr is not None else None
    if device is not None:
        out["device"] = device
    return out


def phase_seconds(tr: Trace) -> dict[str, float]:
    return summary(tr)["phase_seconds"]


def reset():
    """Test hook: drop all retained traces."""
    with _LOCK:
        _RECENT.clear()
