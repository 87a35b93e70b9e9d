"""A shorter warm-up prove. Set-up has to put every program the window will
run into this process's jit caches, and the only entry that reaches all of
them is a whole served prove. For the length of the warm-up request only,
a backend operation named in the configuration's `warmup.run_each_shape`
runs for real that many times for each distinct shape of its arguments and
answers every further call of that shape with its last answer. The warm-up
proof then does not verify where a call was answered, and nothing reads
it: it is a committee the window never sends, and the host verifier is off
for it. The window runs the backend as the class has it; a shape this had
kept from compiling would read `compiles_in_window` above 0 there and the
run `correct: false`.

What it shortens depends on the backend. Where `msm_many` loops `msm`
(`CpuBackend`, and `TpuBackend` until PR 30: 138 column commits of 0.5 s
through the same two kernels) a plan of `{"msm": 2}` answers all but two
commits a shape. Since PR 30 `TpuBackend.msm_many` no longer calls `msm`:
one device commits a run of 16 columns as a call of its own, so on the chip
the plan reaches the prove's two single `msm` calls and answers none (the
run's line reads `"msm": {"ran": 2, "answered_from_last": 0}`), and the
warm-up is a whole prove, 164 commits of 0.0916 s since PR 36. A plan
that names `msm_many` would answer most of those 15 s in every run's
`setup_s`; that changes what set-up does and is an issue of its own
(ROADMAP Queue 1 item 2a)."""

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
