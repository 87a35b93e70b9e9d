"""A shorter warm-up prove. Set-up has to put every program the window will
run into this process's jit caches, and the only entry that reaches all of
them is a whole served prove. Most of that prove is one program run again:
138 column commits through the same two MSM kernels, 0.5 s each. For the
length of the warm-up request only, a backend operation named in the
configuration's `warmup.run_each_shape` runs for real that many times for
each distinct shape of its arguments and answers every further call of
that shape with its last answer. The warm-up proof then does not verify,
and nothing reads it: it is a committee the window never sends, and the
host verifier is off for it. The window runs the backend as the class has
it; a shape this had kept from compiling would read `compiles_in_window`
above 0 there and the run `correct: false`."""

from __future__ import annotations

import functools
import threading

from . import tracing


def _sig(arg):
    if hasattr(arg, "shape") and hasattr(arg, "dtype"):
        return (type(arg).__name__, tuple(arg.shape), str(arg.dtype))
    if isinstance(arg, (list, tuple)):
        return tuple(_sig(a) for a in arg)
    return arg if isinstance(arg, (int, str, bytes, bool, type(None))) \
        else type(arg).__name__


def signature(args, kwargs):
    """What decides which programs a backend call runs: the shapes and
    types of its arguments, and WHICH array the first one is (the backend
    keeps the device copy of a commitment base by identity)."""
    first = (id(args[0]),) if args else ()
    return first + tuple(_sig(a) for a in args) \
        + tuple((k, _sig(v)) for k, v in sorted(kwargs.items()))


class EachShape:
    """Wraps the named methods of ONE backend instance for the length of a
    `with` block. `plan` is {method: times each shape runs for real}.
    While `when()` is false every call runs: the request that warms up is
    also the one that builds the proving key where the checkout has none,
    and a key's commitments are kept."""

    def __init__(self, backend, plan: dict | None, when=lambda: True):
        self.backend = backend
        self.when = when
        self.plan = {op: int(n) for op, n in (plan or {}).items()
                     if hasattr(backend, op)}
        self.real: dict = {}        # op -> calls that ran
        self.answered: dict = {}    # op -> calls answered from the last run
        self._lock = threading.Lock()

    def __enter__(self):
        self._held = tracing.hold(self.backend, self.plan)
        for op, times in self.plan.items():
            inner = getattr(self.backend, op)
            seen: dict = {}         # signature -> [runs, last answer]
            self.real[op] = self.answered[op] = 0

            @functools.wraps(inner)
            def wrapped(*args, _op=op, _inner=inner, _seen=seen,
                        _times=times, **kw):
                if not self.when():
                    return _inner(*args, **kw)
                key = signature(args, kw)
                with self._lock:
                    slot = _seen.setdefault(key, [0, None])
                    if slot[0] >= _times:
                        self.answered[_op] += 1
                        tracing.note_inner_call()
                        return slot[1]
                out = _inner(*args, **kw)
                with self._lock:
                    slot[0] += 1
                    slot[1] = out
                    self.real[_op] += 1
                return out

            setattr(self.backend, op, wrapped)
        return self

    def __exit__(self, *exc):
        tracing.restore(self.backend, self._held)
        return False

    def summary(self) -> dict:
        return {op: {"ran": self.real.get(op, 0),
                     "answered_from_last": self.answered.get(op, 0)}
                for op in self.plan}
