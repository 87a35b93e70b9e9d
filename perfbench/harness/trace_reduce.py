"""From the profiler's trace of whole backend calls to numbers. The reduction
works on rows (plane, line, name, start_ns, duration_ns), so that a small
recorded trace can be kept beside it as its test (tests/recorded_calls.json);
`load_xplane` turns the profiler's `.xplane.pb` into such rows with nothing
but JAX.

- the device planes are those named `/device:TPU:<i>`;
- what ran on a device is read from its `XLA Modules` line: one event per
  run of a compiled program, whatever the program is called (their union
  equals the union of the `XLA Ops` events, 1.5198 s both ways on a 1.54 s
  trace; PR 27), so nothing here knows a kernel by name;
- a program's name is its jitted function's module name with the `jit_`
  prefix and the `(<fingerprint>)` suffix cut;
- a traced call's interval on the trace's clock is its host annotation
  `perfbench/call/<n>`; its device seconds are the union of the module
  events whose middle lies inside it, averaged over the chips;
- `reduce_session` does the same for the whole session
  (`perfbench/replay`): the seconds in which a program ran on the device
  and the session's length, which are what a run reports as `busy_s` and
  `window_s`;
- `estimate` multiplies a call's device seconds by the exact number of
  calls of that operation and shape in the window. No time is scaled, and
  a shape of which no call was traced gets no device seconds at all."""

from __future__ import annotations

import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
MODULE_LINES = ("XLA Modules",)
ANNOTATION_PREFIX = "perfbench/"


def load_xplane(path: str) -> list:
    from jax.profiler import ProfileData
    rows = []
    for plane in ProfileData.from_file(path).planes:
        dev = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            # the operation lines hold millions of events: never iterated
            if dev and line.name not in MODULE_LINES:
                continue
            for ev in line.events:
                if dev or ev.name.startswith(ANNOTATION_PREFIX):
                    rows.append((plane.name, line.name, ev.name,
                                 int(ev.start_ns), int(ev.duration_ns)))
    return rows


def program_name(event_name: str) -> str:
    name = re.sub(r"\(\d+\)$", "", event_name.strip())
    return name[4:] if name.startswith("jit_") else name


def union_seconds(intervals) -> float:
    """Total length of the union of (start, end) intervals, in seconds when
    the ends are nanoseconds."""
    total = 0
    end = None
    for s, e in sorted(intervals):
        if end is None or s > end:
            total += e - s
            end = e
        elif e > end:
            total += e - end
            end = e
    return total / 1e9


def gaps(intervals, lo, hi) -> list:
    """The (start, end) stretches of [lo, hi] that no interval covers, in
    order."""
    out = []
    cur = lo
    for s, e in sorted(intervals):
        if s > cur:
            out.append((cur, min(s, hi)))
        cur = max(cur, e)
        if cur >= hi:
            break
    if cur < hi:
        out.append((cur, hi))
    return out


def reduce_session(rows: list, name: str = "perfbench/replay") -> dict:
    """The whole traced session: `busy_s`, the seconds in which a program
    ran on the device inside the annotation `name` (union, averaged over
    the chips), and `window_s`, that annotation's length."""
    out = _inside(rows, name)
    return {"devices": out["devices"], "busy_s": out["device_s"],
            "window_s": out["span_s"]}


def reduce_call(rows: list, session: int) -> dict:
    """One traced backend call -> the call's device seconds and its
    programs. `annotated` is false where the trace does not hold the call's
    annotation: it then has no device seconds to give."""
    out = _inside(rows, f"{ANNOTATION_PREFIX}call/{session}")
    del out["span_s"]
    return out


def _inside(rows: list, want: str) -> dict:
    span = None
    devices: dict = {}
    for plane, line, name, start, dur in rows:
        if DEVICE_PLANE.match(plane):
            if line in MODULE_LINES:
                devices.setdefault(plane, []).append(
                    (start, start + dur, name))
        elif name == want:
            span = (start, start + dur)
    span_s = (span[1] - span[0]) / 1e9 if span else 0.0
    if not devices or span is None:
        return {"devices": len(devices), "device_s": 0.0, "by_program": {},
                "annotated": span is not None, "span_s": span_s}
    busy = []
    by_program: dict = {}
    for plane in sorted(devices):
        inside = [(s, e, name) for s, e, name in devices[plane]
                  if span[0] <= (s + e) / 2 < span[1]]
        busy.append(union_seconds([(s, e) for s, e, _ in inside]))
        for s, e, name in inside:
            p = program_name(name)
            by_program[p] = by_program.get(p, 0.0) + (e - s) / 1e9
    n = len(busy)
    return {"devices": n, "device_s": sum(busy) / n,
            "by_program": {k: v / n for k, v in by_program.items()},
            "annotated": True, "span_s": span_s}


def estimate(parts: list, calls: list) -> dict:
    """The window's device seconds, as far as whole traced calls and exact
    counts give them. `parts` are `reduce_call` results with `op`, `kind`,
    `shape`; `calls` are the window's innermost backend calls
    (`tracing.Call`).

    For each (operation, shape): calls, host seconds, and where a call of
    it was traced its device seconds a call and a window (x calls). A shape
    of which no call was traced has `device_s` None: nothing stands in for
    what the profiler did not see. For each kind the sums over its traced
    shapes, with the untraced calls and their host seconds beside them.
    `covered` is the traced shapes' share of the host seconds inside
    backend calls: a kind's device seconds, or an idle share of the window,
    mean something only where it is near 1."""
    traced = {(p["op"], p["shape"]): p for p in parts if p["annotated"]}
    shapes: dict = {}
    for c in calls:
        s = shapes.setdefault(c.key, {"op": c.op, "kind": c.kind,
                                      "shape": c.shape, "calls": 0,
                                      "host_s": 0.0})
        s["calls"] += 1
        s["host_s"] += c.t1 - c.t0
    kinds: dict = {}
    by_program: dict = {}
    for key, s in shapes.items():
        k = kinds.setdefault(s["kind"], {
            "calls": 0, "host_s": 0.0, "device_s": 0.0,
            "untraced_calls": 0, "untraced_host_s": 0.0})
        k["calls"] += s["calls"]
        k["host_s"] += s["host_s"]
        got = traced.get(key)
        if got is None:
            s["device_call_s"] = s["device_s"] = None
            k["untraced_calls"] += s["calls"]
            k["untraced_host_s"] += s["host_s"]
            continue
        s["device_call_s"] = got["device_s"]
        s["device_s"] = got["device_s"] * s["calls"]
        k["device_s"] += s["device_s"]
        for name, sec in got["by_program"].items():
            label = f"{name} ({s['op']} x{s['calls']})"
            by_program[label] = by_program.get(label, 0.0) + sec * s["calls"]
    host = sum(k["host_s"] for k in kinds.values())
    untraced = sum(k["untraced_host_s"] for k in kinds.values())
    return {"covered": 1.0 - untraced / host if host else 0.0,
            "kinds": kinds, "by_program": by_program,
            "shapes": sorted(shapes.values(), key=lambda s: -s["host_s"])}
