"""What only a `--trace 1` run has: a wrapper round every public operation
of the backend object, and the profiler round whole calls of it. A
`--trace 0` run uses nothing of this file.

The device tracer of this chip writes every XLA operation of every loop
iteration (one 2^14 MSM of 0.5 s, as it was in PR 28: a 156 MB file and
two minutes of `stop_trace` beside a prove, 40 s and more in a process that
does nothing else), so a window cannot be traced, and neither can a call
of tenths of a second inside a run's wall limit. Device times repeat to a microsecond for the same program
and shape. So once the window has closed a traced run REPLAYS one whole
call of each operation and shape, shortest first, as far as `MAX_REPLAY_S`
reaches, under one profiler session, in a process that by then does nothing
else (`replay`). What the profiler did not see is never given a device second:
a shape too long to replay has host seconds only."""

from __future__ import annotations

import functools
import glob
import os
import threading
import time
from dataclasses import dataclass

# an operation's kind, by what its name holds (`intt` holds `ntt`); first
# match wins, anything else is kind "other". A method a later PR adds
# (`msm_many` as one program, a batched commit) is sorted without an edit.
KIND_WORDS = (("msm", ("msm",)), ("ntt", ("ntt", "lde")))


def kind_of(op: str) -> str:
    for kind, words in KIND_WORDS:
        if any(w in op for w in words):
            return kind
    return "other"


def _shape(arg) -> str:
    if hasattr(arg, "shape") and hasattr(arg, "dtype"):
        return "x".join(str(d) for d in arg.shape)
    if isinstance(arg, (list, tuple)):
        return f"{len(arg)}*[{_shape(arg[0]) if arg else ''}]"
    return "."            # a number is a value (a field element), not a size


def shape_of(args, kwargs) -> str:
    """What decides which programs a call runs, as a printable word: the
    shapes of its arrays and the lengths of its lists."""
    parts = [_shape(a) for a in args]
    parts += [f"{k}={_shape(v)}" for k, v in sorted(kwargs.items())
              if _shape(v) != "."]
    return ",".join(parts)


def hold(backend, ops) -> dict:
    """What the backend OBJECT holds under these names (a wrapper put there
    earlier, or nothing: the class's method), for `restore`."""
    return {op: backend.__dict__.get(op) for op in ops}


def restore(backend, held: dict):
    for op, was in held.items():
        if was is None:
            backend.__dict__.pop(op, None)    # back to the class's method
        else:
            setattr(backend, op, was)


# the wrapped calls this thread is inside of, innermost last: one counter of
# the wrapped calls made inside each
_LOCAL = threading.local()


def _stack() -> list:
    return _LOCAL.__dict__.setdefault("stack", [])


@dataclass
class Call:
    op: str
    kind: str
    shape: str
    t0: float            # host clock, seconds
    t1: float

    @property
    def key(self) -> tuple:
        return (self.op, self.shape)


# What one profiler session may hold, in host seconds of the calls replayed
# under it as the window saw them: the tracer writes some 0.3 MB for every
# millisecond the device runs and `stop_trace` a few MB a second, and a run
# has to end inside its 360 s. A call longer than this is never replayed;
# today that keeps out the 2^14 MSM (a single `msm` 0.106 s and a run of 16
# columns 1.49 s since PR 36; 0.5 s and a 156 MB file when this was set).
# An MSM call shorter than this is replayed like the rest.
MAX_REPLAY_S = 0.1
TPU_TRACE_MODE = "TRACE_COMPUTE"    # the cheapest mode that has the device
SESSION = "perfbench/replay"


class BackendCalls:
    """Wraps every public operation of ONE backend object for the length of
    a `with` block and records each call by the host's clock. An operation
    that calls other wrapped operations (`msm_many` looping `msm`) is kept
    apart under `composite`: `calls` holds the innermost ones, which are
    the ones that reach the device, so that nothing is counted twice.
    `last` keeps, for each operation and shape whose latest innermost call
    took no longer than `MAX_REPLAY_S`, that call's arguments and seconds
    for `replay`."""

    def __init__(self, backend):
        self.backend = backend
        self.ops = sorted(n for n in dir(backend) if not n.startswith("_")
                          and callable(getattr(backend, n)))
        self.calls: list = []
        self.composite: list = []
        self.last: dict = {}    # (op, shape) -> (inner, args, kwargs, seconds)
        self._lock = threading.Lock()

    def _wrap(self, op: str, inner):
        kind = kind_of(op)

        @functools.wraps(inner)
        def wrapped(*args, **kw):
            stack = _stack()
            shape = shape_of(args, kw)
            frame = [0]                       # wrapped calls made inside
            stack.append(frame)
            t0 = time.time()
            try:
                return inner(*args, **kw)
            finally:
                t1 = time.time()
                stack.pop()
                if stack:
                    stack[-1][0] += 1
                call = Call(op, kind, shape, t0, t1)
                leaf = frame[0] == 0
                with self._lock:
                    (self.calls if leaf else self.composite).append(call)
                    if leaf and t1 - t0 <= MAX_REPLAY_S:
                        self.last[call.key] = (inner, args, kw, t1 - t0)
                    else:
                        self.last.pop(call.key, None)

        return wrapped

    def __enter__(self):
        self._held = hold(self.backend, self.ops)
        for op in self.ops:
            setattr(self.backend, op, self._wrap(op, getattr(self.backend, op)))
        return self

    def __exit__(self, *exc):
        restore(self.backend, self._held)
        return False

    def reset(self):
        """Forget the calls seen so far (the warm-up's, before the window)."""
        with self._lock:
            self.calls, self.composite, self.last = [], [], {}


def replay(last: dict, trace_dir: str, on_tpu: bool, reducer) -> dict:
    """One profiler session round one whole call of each (operation, shape)
    of `last` (`BackendCalls.last`), the shortest first while their seconds
    sum to no more than `MAX_REPLAY_S`, one after another, each waited for.
    Call it once the window has closed and nothing else runs. `reducer`
    turns the profiler's file into rows (`trace_reduce.load_xplane`).
    Returns {"rows": the session's rows, "calls": [{op, kind, shape,
    session (the number in its annotation), host_s}], "stop_s", "bytes"}."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0      # host Python frames: not read
    opts.host_tracer_level = 1        # TraceAnnotations
    opts.enable_hlo_proto = False
    if on_tpu:
        opts.advanced_configuration = {"tpu_trace_mode": TPU_TRACE_MODE}
    replayed = []
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation(SESSION):
            budget = MAX_REPLAY_S
            for n, ((op, shape), (inner, args, kw, seconds)) in enumerate(
                    sorted(last.items(), key=lambda kv: kv[1][3])):
                budget -= seconds
                if budget < 0:
                    break
                t0 = time.time()
                with jax.profiler.TraceAnnotation(f"perfbench/call/{n}"):
                    jax.block_until_ready(inner(*args, **kw))
                replayed.append({"op": op, "kind": kind_of(op),
                                 "shape": shape, "session": n,
                                 "host_s": time.time() - t0})
                time.sleep(0.005)     # device events stay clear of the next
    finally:
        t0 = time.time()
        jax.profiler.stop_trace()
        stop_s = time.time() - t0
    pbs = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                           recursive=True))
    return {"rows": reducer(pbs[-1]) if pbs else [], "calls": replayed,
            "stop_s": stop_s,
            "bytes": os.path.getsize(pbs[-1]) if pbs else 0}
