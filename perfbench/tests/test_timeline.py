"""The readers of the program's device timeline (harness/timeline.py) and
the four metric files on it, on the job of `recorded_spans.json` with the
device track and manifest block of `recorded_device.json` (see its
`about`); and the promise the track's form makes: every reader that was
there before it reads the same number with the track as without it."""

import json
import os
import sys
from types import SimpleNamespace

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

import pytest  # noqa: E402

from harness import breakdown, cells, spans, timeline  # noqa: E402

with open(os.path.join(HERE, "recorded_spans.json")) as _f:
    REC = json.load(_f)
with open(os.path.join(HERE, "recorded_device.json")) as _f:
    DEV = json.load(_f)
BASE = REC["traceEvents"][0]["ts"] / 1e6          # the job's start, wall s
NEW = ("msm_device_s", "quotient_device_s", "device_idle_s",
       "device_idle_s.pair")
with open(os.path.join(os.path.dirname(HERE), "..", "BENCHMARK.json")) as _f:
    OLD = [m["name"] for m in json.load(_f)["per_layer"]
           if m["name"] not in NEW]


def shifted(events, s: float) -> list:
    return [dict(e, ts=e["ts"] + s * 1e6) if "ts" in e else dict(e)
            for e in events]


def job(at: float = 0.0, track: bool = True):
    """The recorded job, begun `at` seconds after the first; without its
    device track and block where `track` is false (a parent's job)."""
    manifest = {"transfer_bytes": {"h2d": 212992, "d2h": 33152},
                "phase_seconds": {"prove/quotient": 0.2}, "prove_s": 1.0,
                "queue_wait_s": 0.001}
    evs = shifted(REC["traceEvents"], at)
    if track:
        evs += shifted(DEV["traceEvents"], at)
        manifest["device"] = DEV["manifest_device"]
    return SimpleNamespace(error=None, spans=evs, manifest=manifest)


def ctx_of(sent, wall_s: float):
    window = SimpleNamespace(sent=list(sent), t_first=BASE, wall_s=wall_s,
                             t_last=BASE + wall_s)
    return {"window": window, "trace": None, "config": {"name": "none"},
            "backend_calls": None, "warmup": None}


def read(name: str, ctx):
    return cells.load_plugin("metrics", name).read(ctx)


def test_the_track_pairs_into_the_stretches_of_the_manifest():
    got = timeline.stretches(job().spans)
    assert len(got) == DEV["manifest_device"]["marks"] == 12
    assert spans.union_s([(a - BASE, b - BASE) for a, b in got]) \
        == pytest.approx(DEV["manifest_device"]["busy_s"])
    assert (got[0][0] - BASE, got[0][1] - BASE) \
        == (pytest.approx(0.105), pytest.approx(0.125))


def test_the_readers_on_one_job():
    ctx = ctx_of([job()], 1.0)
    # msm 120 + 70 + 35 ms; ntt 20 + 11 + 11 + 7; quotient by the lower
    # reading 40 + 30 + 10 + 5 + 44; idle: 1 s less the 423 ms of all of
    # them by the upper
    assert read("msm_device_s", ctx) == pytest.approx(0.225)
    assert timeline.kind_seconds(ctx, "ntt") == pytest.approx(0.049)
    assert read("quotient_device_s", ctx) == pytest.approx(0.129)
    assert read("device_idle_s", ctx) == pytest.approx(0.577)
    assert read("device_idle_s.pair", ctx) == pytest.approx(0.577)


def test_the_window_reader_merges_two_jobs_on_the_wall_clock():
    # the second job 0.5 s late in a window of 1.5 s: 794 ms of the two
    # tracks' union (each job's 423 ms, less what they share: 615-625,
    # 635-642 and 830-865 ms lie inside the second's stretches)
    ctx = ctx_of([job(), job(0.5)], 1.5)
    # (wall-clock seconds near 1.7e9 keep microseconds, not nanoseconds)
    assert read("device_idle_s.pair", ctx) \
        == pytest.approx((1.5 - 0.794) / 2, abs=1e-6)
    # a job's own seconds are the same with a neighbour
    assert read("msm_device_s", ctx) == pytest.approx(0.225)


def test_a_program_without_the_timeline_gives_nothing_to_read():
    ctx = ctx_of([job(track=False)], 1.0)
    for name in NEW:
        assert read(name, ctx) is None, name
    assert read("device_idle_s.pair", ctx_of([], 1.0)) is None


def outcome(fn):
    try:
        return ("value", fn())
    except Exception as exc:          # a reader that needs what ctx lacks
        return ("raises", type(exc).__name__)


def test_every_earlier_reader_reads_the_same_with_the_track():
    for sent, wall in (([job()], 1.0), ([job(), job(0.5)], 1.5)):
        plain = ctx_of([job(s.spans[0]["ts"] / 1e6 - BASE, track=False)
                        for s in sent], wall)
        with_track = ctx_of(sent, wall)
        read_some = 0
        for name in OLD:
            a = outcome(lambda: read(name, plain))
            b = outcome(lambda: read(name, with_track))
            assert a == b, name
            read_some += a[0] == "value" and a[1] is not None
        assert read_some >= 10
        for s_plain, s_track in zip(plain["window"].sent, sent):
            evs = spans.events(s_plain)
            assert spans.events(s_track) == evs
            assert spans.in_flight(spans.events(s_track)) \
                == spans.in_flight(evs)
            assert spans.host_only_s(spans.events(s_track)) \
                == spans.host_only_s(evs) == pytest.approx(0.524)
        assert spans.no_inflight_s(with_track) == spans.no_inflight_s(plain)
        assert breakdown.spans_of(sent) \
            == breakdown.spans_of(plain["window"].sent)


def test_idle_gaps_label_nothing_new():
    calls = [SimpleNamespace(t0=BASE + a, t1=BASE + b, kind=k)
             for a, b, k in ((0.100, 0.160, "ntt"), (0.170, 0.390, "msm"),
                             (0.610, 0.650, "ntt"), (0.820, 0.880, "msm"))]
    est = {"kinds": [], "shapes": [], "by_program": {}}
    got = []
    for track in (False, True):
        ctx = ctx_of([job(track=track)], 1.0)
        ctx.update(trace=est, backend_calls=calls)
        got.append(breakdown.build(ctx))
    assert got[0] == got[1]
    assert got[0]["idle_gaps"][0][0] == "multiopen/h_poly"
