"""The warm-up's wrapper: each shape runs for real as often as the plan
says, further calls are answered from the last run, nothing is answered
while the key is not ready, and the backend is the class's own after."""

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

import numpy as np  # noqa: E402

from harness import warmup  # noqa: E402


class Backend:
    def __init__(self):
        self.ran = 0

    def msm(self, points, scalars, base_key=None):
        self.ran += 1
        return int(scalars.sum())

    def ntt(self, coeffs, omega):
        self.ran += 1
        return coeffs * omega


def test_each_shape_runs_as_planned_and_the_rest_is_answered():
    b = Backend()
    base, other = np.ones((8, 8), np.uint64), np.ones((8, 8), np.uint64)
    one = np.ones((8, 4), np.uint64)
    with warmup.EachShape(b, {"msm": 2, "absent_op": 1}) as w:
        got = [b.msm(base, one * i) for i in range(1, 6)]
        # a shorter vector, another base, a key: each a shape of its own
        b.msm(base, one[:4])
        b.msm(other, one)
        b.msm(base, one, base_key="srs")
        assert b.ntt(one, 3).sum() == 96         # not in the plan: runs
    assert got == [32, 64, 64, 64, 64]
    assert w.summary() == {"msm": {"ran": 5, "answered_from_last": 3}}
    assert b.ran == 6
    assert "msm" not in b.__dict__               # the class's method again
    assert b.msm(base, one * 7) == 224


def test_nothing_is_answered_before_the_key_is_ready():
    b = Backend()
    ready = []
    base, one = np.ones((8, 8), np.uint64), np.ones((8, 4), np.uint64)
    with warmup.EachShape(b, {"msm": 1}, when=lambda: bool(ready)) as w:
        assert [b.msm(base, one * i) for i in (1, 2, 3)] == [32, 64, 96]
        ready.append(1)
        assert [b.msm(base, one * i) for i in (4, 5)] == [128, 128]
    assert w.summary() == {"msm": {"ran": 1, "answered_from_last": 1}}


def test_no_plan_wraps_nothing():
    b = Backend()
    with warmup.EachShape(b, None) as w:
        assert "msm" not in b.__dict__
    assert w.summary() == {}
