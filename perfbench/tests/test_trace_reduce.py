"""The reduction from whole traced calls to the window's numbers: by hand
on a few rows, and on a small trace recorded on the chip
(`recorded_calls.json`, see its `about` key): two calls of one kind with a
host-only gap between them give that kind's seconds, the idle share and the
gap's span by name, all exact."""

import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

import pytest  # noqa: E402

from harness import breakdown, trace_reduce as tr  # noqa: E402
from harness.tracing import Call  # noqa: E402

DEV = "/device:TPU:0"


def test_union_counts_overlap_once():
    # [0,10) + [5,20) + [30,40) nested [32,35): 20 + 10 = 30 ns
    iv = [(0, 10), (5, 20), (30, 40), (32, 35)]
    assert tr.union_seconds(iv) == pytest.approx(30e-9)


def test_gaps_in_order():
    iv = [(10, 20), (50, 60), (15, 30)]
    assert tr.gaps(iv, 0, 100) == [(0, 10), (30, 50), (60, 100)]
    assert tr.gaps([], 5, 9) == [(5, 9)]
    assert tr.gaps([(0, 100)], 5, 9) == []


def test_program_name():
    assert tr.program_name("jit_msm_windows(1234567890)") == "msm_windows"
    assert tr.program_name("jit__affine_mont") == "_affine_mont"
    assert tr.program_name("combine_windows") == "combine_windows"


def test_reduce_call_by_hand():
    rows = [
        # the call's annotation on the host: [100, 1100)
        ("/host:CPU", "python3", "perfbench/call/3", 100, 1000),
        ("/host:CPU", "python3", "perfbench/call/2", 0, 50),   # another's
        ("/host:CPU", "python3", "np.asarray(jax.Array)", 0, 5000),
        (DEV, "XLA Modules", "jit_msm_windows(1)", 200, 300),
        (DEV, "XLA Modules", "jit_combine_windows(2)", 600, 100),
        (DEV, "XLA Modules", "jit_msm_windows(1)", 650, 100),  # overlaps
        (DEV, "XLA Modules", "jit_next_calls(9)", 1150, 400),  # after it
        (DEV, "XLA Ops", "fusion.1", 200, 300),                # never read
        (DEV, "Steps", "7", 0, 2000),
    ]
    out = tr.reduce_call(rows, 3)
    assert out["devices"] == 1 and out["annotated"]
    assert out["device_s"] == pytest.approx(450e-9)    # 300 + union(100,100)=150
    assert out["by_program"] == {"msm_windows": pytest.approx(400e-9),
                                 "combine_windows": pytest.approx(100e-9)}
    # without its annotation a call has no device seconds to give
    loose = tr.reduce_call(rows, 4)
    assert not loose["annotated"] and loose["device_s"] == 0.0
    # a call that never reached the device: no device plane in the file
    host_only = tr.reduce_call(rows[:3], 3)
    assert host_only == {"devices": 0, "device_s": 0.0, "by_program": {},
                         "annotated": True}
    # the whole session: what ran inside its annotation, and its length
    rows.append(("/host:CPU", "python3", "perfbench/replay", 50, 1500))
    assert tr.reduce_session(rows) == {
        "devices": 1, "busy_s": pytest.approx(850e-9),
        "window_s": pytest.approx(1500e-9)}
    assert tr.reduce_session(rows[:-1]) == {"devices": 1, "busy_s": 0.0,
                                            "window_s": 0.0}


def test_two_chips_are_averaged():
    rows = [("/device:TPU:0", "XLA Modules", "jit_f(1)", 0, 100),
            ("/device:TPU:1", "XLA Modules", "jit_f(1)", 0, 300),
            ("/host:CPU", "python3", "perfbench/call/0", 0, 400)]
    out = tr.reduce_call(rows, 0)
    assert out["devices"] == 2
    assert out["device_s"] == pytest.approx(200e-9)
    assert out["by_program"] == {"f": pytest.approx(200e-9)}


def call(op, kind, shape, t0, t1):
    return Call(op, kind, shape, t0, t1)


def test_estimate_by_hand():
    parts = [
        {"op": "msm", "kind": "msm", "shape": "a", "devices": 1,
         "annotated": True, "device_s": 0.6,
         "by_program": {"w": 0.4, "c": 0.2}},
        {"op": "mul", "kind": "other", "shape": "b", "devices": 0,
         "annotated": True, "device_s": 0.0, "by_program": {}},
        {"op": "intt", "kind": "ntt", "shape": "lost", "devices": 1,
         "annotated": False, "device_s": 0.0, "by_program": {}},
    ]
    calls = [call("msm", "msm", "a", i, i + 0.75) for i in range(4)] \
        + [call("intt", "ntt", "b", 10, 10.25)] \
        + [call("intt", "ntt", "lost", 10.5, 10.75)] \
        + [call("mul", "other", "b", 11, 11.5)] \
        + [call("add", "other", "b", 12, 12.5)]
    est = tr.estimate(parts, calls)
    # msm: the traced call's 0.6 s x 4 calls
    assert est["kinds"]["msm"] == {"calls": 4, "host_s": 3.0,
                                   "device_s": pytest.approx(2.4),
                                   "untraced_calls": 0,
                                   "untraced_host_s": 0.0}
    # intt was never traced, or its annotation was lost: no device seconds,
    # and nothing stands in for them
    assert est["kinds"]["ntt"] == {"calls": 2, "host_s": 0.5,
                                   "device_s": 0.0, "untraced_calls": 2,
                                   "untraced_host_s": 0.5}
    assert est["kinds"]["other"]["device_s"] == 0.0
    assert est["by_program"] == {"w (msm x4)": pytest.approx(1.6),
                                 "c (msm x4)": pytest.approx(0.8)}
    assert est["covered"] == pytest.approx(1 - 1.0 / 4.5)   # intt x2, add
    by_op = {(s["op"], s["shape"]): s for s in est["shapes"]}
    assert by_op[("intt", "b")]["device_s"] is None
    assert by_op[("msm", "a")]["device_call_s"] == 0.6
    assert est["shapes"][0]["op"] == "msm"


def test_host_only_seconds_go_to_the_shortest_span_that_holds_them():
    spans = [(0.0, 100.0, "job"), (10.0, 40.0, "prove/commit_advice"),
             (40.0, 60.0, "prove/self_verify")]
    calls = [call("msm", "msm", "a", 12.0, 20.0),
             call("msm", "msm", "a", 30.0, 45.0)]
    got = breakdown.host_only(calls, spans, 5.0, 110.0)
    assert got == {"job": pytest.approx(5.0 + 40.0),          # 5-10, 60-100
                   "prove/commit_advice": pytest.approx(2.0 + 10.0),
                   "prove/self_verify": pytest.approx(15.0),
                   "outside every span": pytest.approx(10.0)}


def test_recorded_calls():
    with open(os.path.join(HERE, "recorded_calls.json")) as f:
        rec = json.load(f)
    parts = []
    for session in rec["sessions"]:
        part = tr.reduce_call([tuple(r) for r in session["rows"]],
                              session["session"])
        part.update(op=session["op"], kind=session["kind"],
                    shape=session["shape"])
        parts.append(part)
    calls = [Call(*c) for c in rec["calls"]]
    est = tr.estimate(parts, calls)
    want = rec["expected"]
    for kind, seconds in want["kind_device_s"].items():
        assert est["kinds"][kind]["device_s"] == pytest.approx(seconds,
                                                               rel=1e-12)
    # every device kind's shapes were traced, so the parts add up to the
    # window's busy seconds and its idle share
    assert est["kinds"]["msm"]["untraced_calls"] == 0
    busy = sum(k["device_s"] for k in est["kinds"].values())
    assert busy == pytest.approx(want["busy_s"], rel=1e-12)
    assert 100 * (1 - busy / (rec["window"][1] - rec["window"][0])) \
        == pytest.approx(want["idle_pct"], rel=1e-12)
    for name, seconds in want["by_program"].items():
        assert est["by_program"][f"{name} (msm x2)"] \
            == pytest.approx(seconds, rel=1e-12)
    gaps = breakdown.host_only(
        [c for c in calls if c.kind != "other"],
        [tuple(s) for s in rec["spans"]], *rec["window"])
    assert {k: pytest.approx(v, rel=1e-9) for k, v in gaps.items()} \
        == want["host_only"]
