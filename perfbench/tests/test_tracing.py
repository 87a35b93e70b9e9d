"""The traced run's wrapper: every public operation of the backend object is
wrapped and sorted into a kind by its name, a method never seen before
among them; innermost calls are counted, calls that hold calls are kept
apart; the backend is as it was after. And the replay: one whole call of
each short operation and shape under one profiler session."""

import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

import numpy as np  # noqa: E402
import pytest  # noqa: E402

from harness import tracing, warmup  # noqa: E402


class Backend:
    name = "fake"                    # not callable: not an operation

    def msm(self, points, scalars, base_key=None):
        return int(scalars.sum())

    def msm_many(self, points, scalars_list, base_key=None):
        return [self.msm(points, s) for s in scalars_list]

    def commit_batched(self, points, scalars_list):   # a later PR's method
        return [int(s.sum()) for s in scalars_list]

    def coset_lde_many(self, coeffs_list, omega, g, n_out):
        return coeffs_list

    def intt(self, evals, omega):
        return evals

    def mul(self, a, b):
        return a * b

    def _private(self):
        return 1


@pytest.mark.parametrize("op, kind", [
    ("msm", "msm"), ("msm_many", "msm"), ("commit_msm_batched", "msm"),
    ("ntt", "ntt"), ("intt", "ntt"), ("intt_many", "ntt"),
    ("coset_lde_many", "ntt"), ("mul", "other"), ("prefix_prod", "other")])
def test_kind_by_name(op, kind):
    assert tracing.kind_of(op) == kind


def test_shape_is_arrays_and_lists_not_values():
    a, b = np.ones((8, 4), np.uint64), np.ones((16, 4), np.uint64)
    assert tracing.shape_of((a, b), {}) == "8x4,16x4"
    assert tracing.shape_of(([a, a, a], 12345, 7, 32), {}) == "3*[8x4],.,.,."
    assert tracing.shape_of((a,), {"base_key": "srs", "powers": b}) \
        == "8x4,powers=16x4"


def test_every_public_operation_is_wrapped_and_innermost_calls_counted():
    b = Backend()
    pts, one = np.ones((8, 8), np.uint64), np.ones((8, 4), np.uint64)
    with tracing.BackendCalls(b) as calls:
        assert calls.ops == ["commit_batched", "coset_lde_many", "intt",
                             "msm", "msm_many", "mul"]
        b.msm(pts, one)
        b.msm_many(pts, [one, one, one])      # holds three msm calls
        b.commit_batched(pts, [one, one])     # never seen: one innermost call
        b.intt(one, 5)
        b.mul(one, one)
    got = [(c.op, c.kind, c.shape) for c in calls.calls]
    assert got == [("msm", "msm", "8x8,8x4")] * 4 + [
        ("commit_batched", "other", "8x8,2*[8x4]"),
        ("intt", "ntt", "8x4,."), ("mul", "other", "8x4,8x4")]
    assert [(c.op, c.shape) for c in calls.composite] \
        == [("msm_many", "8x8,3*[8x4]")]
    assert all(c.t1 >= c.t0 for c in calls.calls)
    assert not any(op in b.__dict__ for op in calls.ops)
    calls.reset()
    assert calls.calls == [] and calls.composite == []


def test_a_method_named_for_msm_is_kind_msm():
    class Later(Backend):
        def msm_batched(self, points, scalars_list):
            return 0
    b = Later()
    with tracing.BackendCalls(b) as calls:
        b.msm_batched(np.ones((8, 8)), [np.ones((8, 4))] * 5)
    assert [(c.op, c.kind) for c in calls.calls] == [("msm_batched", "msm")]


def test_wrappers_stack_and_unstack():
    """The warm-up's wrapper goes on top of the traced run's and comes off
    first; an answered call never reaches the one below."""
    b = Backend()
    pts, one = np.ones((8, 8), np.uint64), np.ones((8, 4), np.uint64)
    with tracing.BackendCalls(b) as calls:
        with warmup.EachShape(b, {"msm": 1}) as shapes:
            assert [b.msm(pts, one * i) for i in (1, 2, 3)] == [32, 32, 32]
        assert shapes.summary() == {"msm": {"ran": 1,
                                            "answered_from_last": 2}}
        assert len(calls.calls) == 1
        assert b.msm(pts, one * 2) == 64      # the lower wrapper is back
        assert len(calls.calls) == 2
        # a call whose inner calls were all answered still holds calls:
        # the window's profiler must never take it for an innermost one
        calls.reset()
        with warmup.EachShape(b, {"msm": 0}):
            b.msm_many(pts, [one, one])
        assert calls.calls == []
        assert [c.op for c in calls.composite] == ["msm_many"]
    assert "msm" not in b.__dict__


def test_only_short_innermost_calls_are_kept_for_the_replay(monkeypatch):
    class Slow(Backend):
        def msm(self, points, scalars, base_key=None):
            time.sleep(0.05)
            return 0
    monkeypatch.setattr(tracing, "MAX_REPLAY_S", 0.02)
    b = Slow()
    pts, one = np.ones((8, 8), np.uint64), np.ones((8, 4), np.uint64)
    with tracing.BackendCalls(b) as calls:
        b.msm(pts, one)                       # too long
        b.msm_many(pts, [one])                # holds a call
        b.intt(one, 5)
        b.intt(one * 2, 7)                    # the latest call of a shape
    assert sorted(calls.last) == [("intt", "8x4,.")]
    inner, args, kw, seconds = calls.last[("intt", "8x4,.")]
    assert args[1] == 7 and kw == {} and inner(*args) is args[0]
    assert 0.0 <= seconds <= 0.02
    calls.reset()
    assert calls.last == {}


def test_the_profiler_round_the_replay(tmp_path):
    """The real profiler on the CPU: no device plane here, so every call
    reads 0 device seconds and the session reports no device, but the
    session, its annotations and the file are real."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    from harness import trace_reduce
    b = Backend()
    one = np.ones((8, 4), np.uint64)
    with tracing.BackendCalls(b) as calls:
        b.intt(one, 5)
        b.mul(one, one)
    # the shortest first, and a call the session has no room for is left
    inner, args, kw, _ = calls.last[("intt", "8x4,.")]
    calls.last[("intt", "8x4,.")] = (inner, args, kw, 0.01)
    calls.last[("mul", "8x4,8x4")] = calls.last[("mul", "8x4,8x4")][:3] \
        + (0.02,)
    calls.last[("ntt", "64x4,.")] = (inner, args, kw, 0.09)
    got = tracing.replay(calls.last, str(tmp_path), False,
                         trace_reduce.load_xplane)
    assert [(c["op"], c["kind"], c["shape"], c["session"])
            for c in got["calls"]] == [("intt", "ntt", "8x4,.", 0),
                                       ("mul", "other", "8x4,8x4", 1)]
    assert got["bytes"] > 0 and len(calls.calls) == 2     # replays not counted
    for c in got["calls"]:
        part = trace_reduce.reduce_call(got["rows"], c["session"])
        assert part["annotated"] and part["device_s"] == 0.0
    session = trace_reduce.reduce_session(got["rows"])
    assert session["devices"] == 0 and session["busy_s"] == 0.0
    assert 0.0 < session["window_s"] < 5.0
