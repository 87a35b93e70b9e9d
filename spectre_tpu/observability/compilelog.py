"""JIT-compile telemetry: a `jax.monitoring` event-duration listener.

XLA compile time dominates first-prove latency on a reconfigured
accelerator (mode flip, new k, fresh process), yet it is invisible in
the phase histograms — `prove/quotient` taking 90s tells you nothing
about whether that was math or `backend_compile`. jax emits
`/jax/core/compile/*_duration` events (jaxpr trace, MLIR lowering,
backend compile) through `jax.monitoring`; `install()` registers one
process-global listener that fans each event into three sinks:

  1. `spectre_compile_seconds{fn=}` (metrics.COMPILE_SECONDS) — fn is
     the innermost open `entry_point(...)` (the named jitted entry that
     actually missed its trace cache — sharded MSM/NTT runners push one),
     falling back to the innermost open tracing span
     (`prove/commit_advice`, ...) so compile cost is attributed to the
     phase that triggered it.
     Only `backend_compile` events are observed (the others are
     sub-steps of the same compilation; counting all three would
     triple-count one cache miss).
  2. a completed `compile/<kind>` child span in the active trace, so
     `getTrace` / Chrome trace JSON shows compiles nested inside their
     phase.
  3. the thread-local `capture(...)` collector, which the JobQueue
     worker opens around the runner — this is what lands in the job's
     provenance manifest. A second identical prove collects ZERO events
     (jit cache hit); that invariant is pinned in tests.

Listeners cannot be unregistered in this jax version, so `install()`
is idempotent and the hook lives for the process. The module itself is
stdlib-only at import time (the jax import happens inside `install()`,
and degrades to a no-op when jax is absent) — scraping /metrics or
building a manifest never pulls in jax.
"""

from __future__ import annotations

import contextlib
import threading

from . import metrics, tracing

COMPILE_EVENT_PREFIX = "/jax/core/compile/"

# the event that represents one actual XLA compilation (cache-miss
# signal); the others are phases of the same miss
BACKEND_COMPILE = "backend_compile"

# plain (duration-less) jax.monitoring events fired by the PERSISTENT
# compilation cache on every lookup: a hit means the XLA compile step was
# skipped entirely (tracing/lowering still ran). Surfaced so a manifest
# can distinguish "warm disk cache" from "genuinely recompiled" — the
# multichip SPMD programs are minutes-scale compiles
PERSISTENT_CACHE_EVENTS = {
    "/jax/compilation_cache/cache_hits": "hit",
    "/jax/compilation_cache/cache_misses": "miss",
}

UNATTRIBUTED = "unattributed"

_LOCK = threading.Lock()
_installed = False
_install_failed: str | None = None


class _Local(threading.local):
    def __init__(self):
        self.events: list | None = None
        # innermost-wins stack of named compile entry points (see
        # `entry_point`): sharded/batched runner entries push their own
        # name so a compile triggered inside e.g. `prove/commit_advice`
        # is attributed to the jitted entry that actually missed its
        # trace cache, not lumped into the parent phase span
        self.entry_points: list[str] = []


_local = _Local()


@contextlib.contextmanager
def entry_point(name: str):
    """Attribute compile events fired inside this block to `name`.

    Nested entry points win innermost-first (a two-level jit compiles
    under the inner name); with no entry point open, attribution falls
    back to the innermost tracing span (the phase) as before."""
    _local.entry_points.append(name)
    try:
        yield
    finally:
        _local.entry_points.pop()


def current_entry_point() -> str | None:
    st = _local.entry_points
    return st[-1] if st else None


def _attribution() -> str:
    return (current_entry_point() or tracing.current_span_name()
            or UNATTRIBUTED)


def _kind(event: str) -> str:
    # "/jax/core/compile/backend_compile_duration" -> "backend_compile"
    k = event[len(COMPILE_EVENT_PREFIX):]
    return k[:-len("_duration")] if k.endswith("_duration") else k


def _listener(event: str, duration_secs: float, **_kw):
    # fires synchronously on the compiling thread => the thread-local
    # trace/collector of the job that triggered the compile is active
    if not event.startswith(COMPILE_EVENT_PREFIX):
        return
    kind = _kind(event)
    fn = _attribution()
    # round ONCE and feed the same float to histogram and manifest sink:
    # tests pin exact (not approximate) parity between the two
    secs = round(float(duration_secs), 6)
    if kind == BACKEND_COMPILE:
        metrics.COMPILE_SECONDS.labels(fn=fn).observe(secs)
    tracing.add_completed_span(f"compile/{kind}", duration_secs, fn=fn)
    sink = _local.events
    if sink is not None:
        sink.append({"event": kind, "fn": fn, "seconds": secs})


def _event_listener(event: str, **_kw):
    """Plain-event listener: persistent compile-cache hit/miss counts."""
    tag = PERSISTENT_CACHE_EVENTS.get(event)
    if tag is None:
        return
    with _LOCK:
        _cache_counts[tag] += 1
    sink = _local.events
    if sink is not None:
        sink.append({"event": f"persistent_cache_{tag}",
                     "fn": _attribution(),
                     "seconds": 0.0})


_cache_counts = {"hit": 0, "miss": 0}


def cache_counts() -> dict:
    """Process-lifetime persistent compile-cache hit/miss totals."""
    with _LOCK:
        return dict(_cache_counts)


def install() -> bool:
    """Register the listeners (idempotent). Returns True when the hook
    is live; False when jax is unavailable in this process."""
    global _installed, _install_failed
    with _LOCK:
        if _installed:
            return True
        if _install_failed is not None:
            return False
        try:
            from jax import monitoring
            monitoring.register_event_duration_secs_listener(_listener)
            # older jax lacks the plain-event hook; duration telemetry
            # still works without cache hit/miss counts
            if hasattr(monitoring, "register_event_listener"):
                monitoring.register_event_listener(_event_listener)
        except Exception as exc:  # no jax / ancient jax: telemetry off
            _install_failed = f"{type(exc).__name__}: {exc}"
            return False
        _installed = True
        return True


def installed() -> bool:
    with _LOCK:
        return _installed


@contextlib.contextmanager
def capture(into: list | None = None):
    """Collect this thread's compile events into `into` (or a fresh
    list) for the duration of the block; yields the list. Nested
    captures shadow the outer one (innermost wins — one job, one
    manifest)."""
    sink = into if into is not None else []
    prev = _local.events
    _local.events = sink
    try:
        yield sink
    finally:
        _local.events = prev


def summarize(events) -> dict:
    """Manifest-shape summary of captured events: `count`/`seconds`
    cover backend_compile only (one entry per actual XLA cache miss —
    the "zero new compiles on a warm cache" signal); `by_fn` breaks the
    same backend seconds down by triggering phase; `events` keeps the
    full list including trace/lowering sub-steps."""
    backend = [e for e in events if e["event"] == BACKEND_COMPILE]
    by_fn: dict[str, dict] = {}
    for e in backend:
        slot = by_fn.setdefault(e["fn"], {"count": 0, "seconds": 0.0})
        slot["count"] += 1
        slot["seconds"] = round(slot["seconds"] + e["seconds"], 6)
    return {
        "count": len(backend),
        "seconds": round(sum(e["seconds"] for e in backend), 6),
        "by_fn": {k: by_fn[k] for k in sorted(by_fn)},
        # persistent DISK cache lookups captured in this block (a hit =
        # XLA compile skipped; tracing/lowering still ran)
        "persistent_cache": {
            tag: sum(1 for e in events
                     if e["event"] == f"persistent_cache_{tag}")
            for tag in ("hit", "miss")},
        "events": list(events),
    }


def reset_for_tests():
    """Drop the installed/failed flags so a test can exercise install()
    again. The underlying jax listener (if any) stays registered —
    re-install just won't double-register thanks to the flag staying
    set after the first successful call in a process... so tests that
    reset MUST NOT call install() again unless they accept a second
    listener. Prefer asserting on capture() output instead."""
    global _install_failed
    with _LOCK:
        _install_failed = None
